"""Dedicated ("controller on VM") managed-jobs mode, e2e on the local
cloud: verbs ship to the controller cluster as agent jobs, a persistent
daemon there drives recovery, and the submitting process (the "API
server") never runs a controller — so its death cannot stop recovery.

Parity: sky/jobs/server/core.py:494,:527 (controller launched on its own
cluster via jobs-controller.yaml.j2); consolidation mode remains the
default and is covered by tests/test_managed_jobs.py.
"""
import os
import signal
import time

import pytest

from skypilot_tpu import global_user_state
from skypilot_tpu.jobs import controller as controller_lib
from skypilot_tpu.jobs import controller_daemon
from skypilot_tpu.jobs import core as jobs_core
from skypilot_tpu.jobs.state import ManagedJobStatus
from skypilot_tpu.resources import Resources
from skypilot_tpu.task import Task


@pytest.fixture
def vm_mode(tmp_home, enable_all_clouds, monkeypatch):
    monkeypatch.setenv('SKYTPU_JOBS_POLL_INTERVAL', '0.25')
    config = tmp_home / '.skytpu' / 'config.yaml'
    config.parent.mkdir(parents=True, exist_ok=True)
    config.write_text(
        'jobs:\n'
        '  controller:\n'
        '    mode: vm\n'
        '    resources:\n'
        '      infra: local\n')
    from skypilot_tpu import sky_config
    sky_config.reset_cache_for_tests()
    yield tmp_home
    # Kill the daemon this test's verbs spawned (it inherited this
    # test's $HOME at exec time; the session reaper is the backstop).
    try:
        pid = int(open(controller_daemon.pid_file_path(),
                       encoding='utf-8').read())
        os.kill(pid, signal.SIGKILL)
    except (OSError, ValueError):
        pass
    sky_config.reset_cache_for_tests()
    controller_lib.stop_all_controllers()


def _local_task(run, name='vmjob'):
    t = Task(name, run=run)
    t.set_resources(Resources.from_yaml_config({'infra': 'local'}))
    return t


def _wait(job_id, statuses, timeout=300):    # ends when reached (C10)
    deadline = time.time() + timeout
    while time.time() < deadline:
        recs = {r['job_id']: r for r in jobs_core.queue(all_users=True)}
        rec = recs.get(job_id)
        if rec and ManagedJobStatus(rec['status']) in statuses:
            return rec
        time.sleep(0.5)
    raise TimeoutError(
        f'job {job_id} never reached {statuses}; queue={recs}')


@pytest.mark.e2e
def test_vm_mode_end_to_end_and_recovery(vm_mode):
    job_id = jobs_core.launch(_local_task('echo done-one'))
    # The controller cluster came up through the normal stack...
    assert global_user_state.get_cluster(
        jobs_core.JOBS_CONTROLLER_CLUSTER) is not None
    # ...and THIS process runs no controller threads (the daemon on the
    # controller cluster does): the exact decoupling dedicated mode buys.
    assert not controller_lib.live_controllers()
    _wait(job_id, (ManagedJobStatus.SUCCEEDED,))
    assert controller_daemon.daemon_alive()

    # Logs are served from the controller's snapshot, remotely.
    import io
    buf = io.StringIO()
    jobs_core.tail_logs(job_id, out=buf)
    assert 'done-one' in buf.getvalue()

    # Recovery without any local controller: a long job's cluster is
    # preempted; the DAEMON (surviving an "API server" that never held
    # a controller to begin with) recovers it to completion.
    gate = vm_mode / 'gate'
    run = (f'while [ ! -f {gate} ]; do sleep 0.1; done; echo done-two')
    job2 = jobs_core.launch(_local_task(run, name='recov'))
    rec = _wait(job2, (ManagedJobStatus.RUNNING,))
    from skypilot_tpu.provision.local import instance as local_instance
    local_instance.inject_preemption(rec['cluster_name'])
    _wait(job2, (ManagedJobStatus.RECOVERING, ManagedJobStatus.RUNNING))
    gate.write_text('go')
    final = _wait(job2, (ManagedJobStatus.SUCCEEDED,))
    assert final['recovery_count'] >= 1


@pytest.mark.e2e
def test_vm_mode_cancel(vm_mode):
    gate = vm_mode / 'never'
    job_id = jobs_core.launch(_local_task(
        f'while [ ! -f {gate} ]; do sleep 0.1; done'))
    _wait(job_id, (ManagedJobStatus.RUNNING,))
    assert jobs_core.cancel(job_id)
    _wait(job_id, (ManagedJobStatus.CANCELLED,))


# ----- serve on a dedicated controller ---------------------------------------
@pytest.fixture
def serve_vm_mode(tmp_home, enable_all_clouds, monkeypatch):
    monkeypatch.setenv('SKYTPU_JOBS_POLL_INTERVAL', '0.25')
    monkeypatch.setenv('SKYTPU_SERVE_TICK_INTERVAL', '0.25')
    config = tmp_home / '.skytpu' / 'config.yaml'
    config.parent.mkdir(parents=True, exist_ok=True)
    config.write_text(
        'serve:\n'
        '  controller:\n'
        '    mode: vm\n'
        '    resources:\n'
        '      infra: local\n')
    from skypilot_tpu import sky_config
    sky_config.reset_cache_for_tests()
    yield tmp_home
    try:
        pid = int(open(controller_daemon.pid_file_path(),
                       encoding='utf-8').read())
        os.kill(pid, signal.SIGKILL)
    except (OSError, ValueError):
        pass
    sky_config.reset_cache_for_tests()
    from skypilot_tpu.serve import controller as serve_ctl
    serve_ctl.stop_all_controllers()
    controller_lib.stop_all_controllers()


_REPLICA_RUN = (
    "python3 -c \"import http.server, os\n"
    "class H(http.server.BaseHTTPRequestHandler):\n"
    "    def do_GET(self):\n"
    "        self.send_response(200)\n"
    "        self.send_header('Content-Length', '2')\n"
    "        self.end_headers(); self.wfile.write(b'ok')\n"
    "    def log_message(self, *a): pass\n"
    "http.server.HTTPServer(('127.0.0.1', "
    "int(os.environ['SKYTPU_SERVE_REPLICA_PORT'])), H).serve_forever()\"")


@pytest.mark.e2e
def test_serve_vm_mode_end_to_end(serve_vm_mode):
    """Service controller + LB live on the dedicated controller cluster;
    this process runs NO serve controllers, yet the service comes up,
    answers through the controller-host endpoint, and tears down."""
    import urllib.request
    from skypilot_tpu import serve as serve_lib
    from skypilot_tpu.serve import controller as serve_ctl
    from skypilot_tpu.serve.serve_state import ServiceStatus
    from skypilot_tpu.task import Task
    from skypilot_tpu.resources import Resources

    t = Task('vmsvc', run=_REPLICA_RUN, service={
        'readiness_probe': {'path': '/', 'initial_delay_seconds': 30},
        'replicas': 1,
    })
    t.set_resources(Resources.from_yaml_config({'infra': 'local'}))
    result = serve_lib.up(t)
    assert 'endpoint' in result

    # No serve controller threads in THIS process — the daemon on the
    # controller cluster drives the service.
    assert not serve_ctl.live_controllers()

    deadline = time.time() + 120
    while time.time() < deadline:
        records = serve_lib.status('vmsvc')
        if records and records[0]['status'] is ServiceStatus.READY:
            break
        time.sleep(0.5)
    else:
        raise TimeoutError(f'never READY: {records}')
    assert controller_daemon.daemon_alive()

    body = urllib.request.urlopen(result['endpoint'], timeout=10).read()
    assert body == b'ok'

    serve_lib.down('vmsvc')
    deadline = time.time() + 60
    while time.time() < deadline:
        records = serve_lib.status('vmsvc')
        if not records or records[0]['status'] is ServiceStatus.SHUTDOWN:
            break
        time.sleep(0.5)
    else:
        raise TimeoutError(f'service never torn down: {records}')
