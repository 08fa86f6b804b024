"""Test config: force an 8-device virtual CPU mesh before jax is imported.

All sharding/parallelism tests run against this virtual mesh so they exercise
the same pjit/shard_map code paths that run on real TPU slices.
"""
import os
import uuid

# Force the CPU before any backend is used.  XLA_FLAGS must be set before
# first backend use too.
xla_flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in xla_flags:
    os.environ['XLA_FLAGS'] = (
        xla_flags + ' --xla_force_host_platform_device_count=8').strip()
import jax  # noqa: E402

jax.config.update('jax_platforms', 'cpu')

# Fast provisioning polls against the fake cloud APIs (default 10s is
# sized for the real GCP control plane).
os.environ.setdefault('SKYTPU_PROVISION_POLL_S', '0.2')

import pytest  # noqa: E402

# ---------------------------------------------------------------------------
# Test tiers (parity: the reference splits unit / smoke / load / chaos so the
# fast tier stays fast — SURVEY §4).  Tiers are assigned per-module here so
# every test is in exactly one tier without per-file boilerplate:
#   unit  — in-process, fast; the default quick tier (`-m unit`)
#   model — JAX compile-heavy (models/ops/inference); CPU-bound for minutes
#   e2e   — spawns real subprocesses / HTTP servers / agents
#   chaos — fault injection (TCP severing, SIGKILL mid-launch)
#   load  — throughput / soak
# Non-unit modules additionally get an xdist_group: under `-n N --dist
# loadgroup` every test of one group runs on ONE worker.  This machine has
# a SINGLE CPU core (nproc=1) — xdist only time-slices — so heavy tests
# run in exactly TWO serial lanes: one for JAX compile tests (pure CPU
# hogs with no wall-clock deadlines) and one for the timing-sensitive
# e2e/chaos/load scenarios (sleep-bound with CPU bursts and real
# deadlines).  At most one of each runs at any moment, so the e2e lane
# always gets ~half the core — measured round-5: four streams (2 jax + 2
# e2e) starved serve tests to 4x their intrinsic time and past their
# deadlines, two lanes do not.  Light unit tests fill the remaining
# workers.  Round-4's -n4 flakes were exactly this starvation.
# ---------------------------------------------------------------------------
_CHAOS_MODULES = {'test_chaos'}
_LOAD_MODULES = {'test_load'}
_MODEL_MODULES = {
    'test_models_train', 'test_models_zoo', 'test_moe_pipeline',
    'test_ops', 'test_inference', 'test_multislice',
    'test_placement_validate', 'test_rl', 'test_serve_sharded',
    'test_serve_chunked',
}
_E2E_MODULES = {
    'test_agent_events', 'test_api_server', 'test_authentication',
    'test_autostop', 'test_backward_compat',
    'test_client_server_compat', 'test_controller_vm',
    'test_dashboard_misc', 'test_docker_runtime', 'test_execution_e2e',
    'test_fuse_proxy', 'test_managed_jobs', 'test_multiworker',
    'test_serve', 'test_server_daemons', 'test_slurm',
    'test_ssh_gang', 'test_transfer_logs',
}
def pytest_addoption(parser, pluginmanager):
    """Keep bare `pytest` working without pytest-xdist: addopts carries
    `--dist loadgroup` (the only transport that reaches xdist WORKERS),
    which is an xdist-registered option — register a no-op stand-in
    whenever the real plugin is not loaded (absent, `-p no:xdist`,
    PYTEST_DISABLE_PLUGIN_AUTOLOAD, ...)."""
    if not pluginmanager.hasplugin('xdist'):
        parser.addoption('--dist', action='store', default='no',
                         help='no-op (pytest-xdist not loaded)')


@pytest.hookimpl(tryfirst=True)
def pytest_collection_modifyitems(config, items):
    # tryfirst: xdist's WorkerInteractor also hooks modifyitems to bake
    # the xdist_group into each nodeid (remote.py:242) and, being
    # registered after conftest plugins, runs BEFORE this hook by
    # default — the lane markers must exist by then or loadgroup
    # silently degrades to plain load scheduling.
    for item in items:
        stem = item.path.stem if hasattr(item, 'path') else ''
        if stem in _CHAOS_MODULES:
            tier = 'chaos'
        elif stem in _LOAD_MODULES:
            tier = 'load'
        elif stem in _MODEL_MODULES:
            tier = 'model'
        elif stem in _E2E_MODULES:
            tier = 'e2e'
        else:
            tier = 'unit'
        item.add_marker(getattr(pytest.mark, tier))
        if tier == 'model':
            item.add_marker(pytest.mark.xdist_group('lane-jax'))
        elif tier != 'unit':
            item.add_marker(pytest.mark.xdist_group('lane-e2e'))


@pytest.fixture(autouse=True)
def stop_leaked_controllers():
    """Stop jobs/serve controller threads after EVERY test.

    A controller thread outliving its test keeps polling under the NEXT
    test's $HOME (env-resolved paths are read lazily) and corrupts its
    DBs — observed twice now (round 4: 'cluster jobs-1-t1-two lost' inside
    unrelated tests; round 5: a failed test_storage recovery test leaked a
    controller whose 'jobs-1-bktrain' cluster then appeared in
    test_users_workspaces' status output).  Individual fixtures already
    stop what they start — this is the backstop for tests that FAIL
    mid-scenario.  Only acts when the controller modules were imported.
    """
    yield
    import sys
    jc = sys.modules.get('skypilot_tpu.jobs.controller')
    sc = sys.modules.get('skypilot_tpu.serve.controller')
    if sc is not None:
        sc.stop_all_controllers()
    if jc is not None:
        jc.stop_all_controllers()


@pytest.fixture
def enable_all_clouds(monkeypatch):
    """All clouds 'enabled' without credential probes (analog of the
    reference fixture tests/common_test_fixtures.py:176)."""
    monkeypatch.setenv('SKYTPU_ENABLED_CLOUDS', 'gcp,local')


@pytest.fixture
def tmp_home(tmp_path, monkeypatch):
    """Isolated $HOME so state DBs/config files never touch the real one."""
    home = tmp_path / 'home'
    home.mkdir()
    monkeypatch.setenv('HOME', str(home))
    monkeypatch.setenv('SKYTPU_GLOBAL_CONFIG',
                       str(home / '.skytpu' / 'config.yaml'))
    monkeypatch.setenv('SKYTPU_PROJECT_CONFIG',
                       str(home / '.skytpu.yaml'))
    from skypilot_tpu import sky_config
    sky_config.reset_cache_for_tests()
    yield home
    sky_config.reset_cache_for_tests()


@pytest.fixture(scope='session', autouse=True)
def reap_leaked_agents(tmp_path_factory):
    """Kill every agent daemon spawned during this test session.

    Agents are started detached (start_new_session=True) so they outlive
    their spawner; a test that never tears down its cluster leaks one.
    The backend appends each spawned agent PID to SKYTPU_AGENT_PID_FILE
    (per pytest/xdist worker, so parallel workers never reap each
    other's live agents); at session end any PID still running an agent
    is SIGKILLed.
    """
    import signal
    registry = tmp_path_factory.mktemp('agents') / 'agent-pids.txt'
    registry.touch()
    old = os.environ.get('SKYTPU_AGENT_PID_FILE')
    os.environ['SKYTPU_AGENT_PID_FILE'] = str(registry)
    yield
    if old is None:
        os.environ.pop('SKYTPU_AGENT_PID_FILE', None)
    else:
        os.environ['SKYTPU_AGENT_PID_FILE'] = old
    for line in registry.read_text().splitlines():
        try:
            pid = int(line)
        except ValueError:
            continue
        # Only kill PIDs still running OUR agent (guards pid reuse).
        try:
            with open(f'/proc/{pid}/cmdline', 'rb') as f:
                cmdline = f.read()
        except OSError:
            continue
        if b'skypilot_tpu.agent.server' in cmdline:
            try:
                os.kill(pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass


def _kill_marked_processes(marker_value: 'str | None' = None) -> int:
    """SIGKILL processes whose *inherited* environment carries a
    ``SKYTPU_TEST_SESSION_MARK``.

    /proc/<pid>/environ is frozen at exec time, so the pytest process that
    exported the variable after startup never matches itself — only
    descendants spawned after the export do.  With ``marker_value`` set,
    only that exact session's descendants are killed (teardown).  Without
    it (startup sweep), any marked process is killed IFF its owning pytest
    worker — whose pid is embedded in the marker as ``<uuid>-<ownerpid>``
    — is gone: leftovers of crashed sessions are reaped, a live long
    session (however old) is never touched."""
    import re
    import signal
    killed = 0
    for pid_s in os.listdir('/proc'):
        if not pid_s.isdigit() or int(pid_s) == os.getpid():
            continue
        try:
            with open(f'/proc/{pid_s}/environ', 'rb') as f:
                environ = f.read()
            m = re.search(rb'SKYTPU_TEST_SESSION_MARK=([0-9a-f]+)-(\d+)',
                          environ)
            if not m:
                continue
            if marker_value is not None:
                if (m.group(1) + b'-' + m.group(2)).decode() != marker_value:
                    continue
            elif os.path.exists(f'/proc/{int(m.group(2))}'):
                continue        # owner alive: live session, leave it be
            os.kill(int(pid_s), signal.SIGKILL)
            killed += 1
        except (OSError, ValueError):
            continue
    return killed


@pytest.fixture(scope='session', autouse=True)
def reap_session_descendants():
    """Kill EVERY process spawned during this test session at session end.

    The agent-PID registry above only catches agent daemons; round 4 leaked
    serve-replica HTTP servers, API servers and task children (`bash -c`
    gate-poll loops) for hours, skewing every later run on the machine.
    Every framework spawn path builds its env from os.environ, so a unique
    marker exported here is inherited by all descendants — including
    detached (start_new_session=True) ones — and can be swept from /proc
    afterwards.  Per-xdist-worker uuid, so parallel workers never reap each
    other's live processes.  On startup, marked processes whose owning
    pytest worker is DEAD are swept too (leftovers of a crashed session;
    a live long-running session's owner pid still exists, so it is never
    touched)."""
    marker_val = f'{uuid.uuid4().hex}-{os.getpid()}'
    os.environ['SKYTPU_TEST_SESSION_MARK'] = marker_val
    _kill_marked_processes()                      # crashed-session sweep
    yield
    os.environ.pop('SKYTPU_TEST_SESSION_MARK', None)
    _kill_marked_processes(marker_val)
