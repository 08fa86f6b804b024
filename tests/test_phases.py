"""Loop phases, TTFT's last cut and the set-up spans (ISSUE 25).

A phase (`tracing.phase`) puts what a loop thread is doing on the clock
of a profiler session's device trace and hands its seconds back; it
never enters the ring.  The per-request spans engine.prefill_wait +
engine.first_token_ride tile engine.dispatch on both engine paths, the
loop's busy + wait counters account for the loop's wall time, and
prewarm leaves one engine.setup.compile span per program.
"""
import glob
import os
import sys
import time

import pytest

from skypilot_tpu.server import metrics
from skypilot_tpu.server import tracing


@pytest.fixture(autouse=True)
def _reset():
    from skypilot_tpu.perf import compile_telemetry
    metrics.reset_for_tests()
    tracing.reset_for_tests()
    yield
    metrics.reset_for_tests()
    tracing.reset_for_tests()
    compile_telemetry.reset_for_tests()     # prewarm() arms the sentinel


@pytest.fixture(scope='module')
def tiny_engine_model():
    import jax
    from skypilot_tpu.models.llama import LLAMA_CONFIGS, Llama, init_params
    model = Llama(LLAMA_CONFIGS['tiny'])
    params = init_params(model, jax.random.PRNGKey(0))['params']
    return model, params


def _engine(tiny_engine_model, **cfg):
    from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig
    model, params = tiny_engine_model
    cfg.setdefault('n_slots', 2)
    cfg.setdefault('prefill_buckets', (8,))
    return DecodeEngine(model, params, EngineConfig(**cfg))


def _family(name, **labels):
    """Sum of a family's samples whose labels include `labels`."""
    total = 0.0
    for line in metrics.render().splitlines():
        if line.startswith(name) and all(
                f'{k}="{v}"' in line for k, v in labels.items()):
            total += float(line.rpartition(' ')[2])
    return total


def _host_event_names(trace_dir):
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, 'plugins', 'profile', '*',
                                   '*.xplane.pb'))
    assert paths, f'no trace under {trace_dir}'
    names = set()
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith('/host:'):
            for line in plane.lines:
                names.update(ev.name for ev in line.events)
    return names


# ----- the substrate ----------------------------------------------------------
def test_phase_returns_seconds_and_stays_out_of_the_ring():
    with tracing.phase('engine.loop.idle') as ph:
        time.sleep(0.02)
    assert 0.02 <= ph.seconds < 0.5
    assert tracing.recent_requests() == []


def test_phase_works_before_jax_is_imported(monkeypatch):
    """The load balancer and the API server never import jax, and a
    phase must not be what does."""
    monkeypatch.setattr(tracing, '_annotation_cls', None)
    monkeypatch.setitem(sys.modules, 'jax', None)
    with tracing.phase('engine.loop.idle') as ph:
        pass
    assert ph.seconds >= 0.0 and ph._annotation is None
    assert tracing._annotation_cls is None


def test_every_phase_and_setup_name_is_registered():
    for name in ('engine.loop.dispatch', 'engine.loop.fetch',
                 'engine.loop.emit', 'engine.loop.admit',
                 'engine.loop.idle', 'engine.setup.layouts',
                 'engine.setup.compile', 'engine.setup.prewarm',
                 'engine.prefill_wait', 'engine.first_token_ride',
                 'train.feed', 'train.dispatch', 'train.fetch',
                 'train.export', 'train.checkpoint'):
        assert name in tracing.SPAN_HELP, name
    help_ = metrics.help_registry()
    assert 'skytpu_engine_loop_busy_seconds_total' in help_
    assert 'skytpu_engine_loop_wait_seconds_total' in help_
    # The two constants of the configuration the trainer used to set at
    # every log boundary are gone from the registry.
    assert 'skytpu_train_hbm_bytes_per_token' not in help_
    assert 'skytpu_train_arith_intensity' not in help_


# ----- TTFT's last cut --------------------------------------------------------
def _parts(rid):
    durs = {}
    for e in tracing.events_for(rid):
        if e['dur_ms'] is not None:
            durs.setdefault(e['name'], []).append(e['dur_ms'])
    return durs


@pytest.mark.parametrize('path', ['step', 'step_pipelined'])
def test_prefill_wait_and_ride_tile_dispatch(tiny_engine_model, path):
    """prefill_wait + first_token_ride == dispatch for every request,
    on the synchronous and the pipelined path, short prompts and a
    chunked one; decompose()'s sum is what it was."""
    engine = _engine(tiny_engine_model, steps_per_call=2)
    step = getattr(engine, path)
    # A first request keeps a decode call in flight while the others
    # are admitted behind it.
    reqs = [engine.submit([1, 2, 3], 12, request_id=f'{path}-0')]
    for _ in range(3):
        step()
    reqs += [engine.submit([4, 5, 6, 7], 4, request_id=f'{path}-1'),
             engine.submit(list(range(1, 21)), 3,
                           request_id=f'{path}-2')]     # chunked: 20 > 8
    for _ in range(400):
        step()
        if all(r.finished_at is not None for r in reqs):
            break
    assert all(r.finished_at is not None for r in reqs)
    for r in reqs:
        durs = _parts(r.request_id)
        assert len(durs['engine.dispatch']) == 1
        assert len(durs['engine.prefill_wait']) == 1
        assert len(durs['engine.first_token_ride']) == 1
        assert (durs['engine.prefill_wait'][0] +
                durs['engine.first_token_ride'][0]) == pytest.approx(
                    durs['engine.dispatch'][0], abs=1e-3)
        s = tracing.decompose(tracing.events_for(r.request_id))
        assert s['prefill_wait_ms'] + s['first_token_ride_ms'] == \
            pytest.approx(s['dispatch_ms'], abs=1e-3)
        # The two parts are shown beside dispatch, not added again.
        assert s['decomposed_ttft_ms'] == pytest.approx(
            s['queue_wait_ms'] + s['prefill_ms'] + s['dispatch_ms'],
            abs=1e-3)
        assert abs(s['unattributed_ms']) < 0.05
    late = _parts(f'{path}-1')
    if path == 'step':
        # Nothing is ever in flight at an admission: no wait, all ride.
        assert late['engine.prefill_wait'][0] == 0.0
    else:
        # Admitted behind a call in flight: the wait ends at its fetch.
        assert late['engine.prefill_wait'][0] > 0.0
        assert late['engine.first_token_ride'][0] > 0.0
        # The engine was idle when the first request came.
        assert _parts(f'{path}-0')['engine.prefill_wait'][0] == 0.0


# ----- the loop's counters ----------------------------------------------------
def test_loop_counters_account_for_the_loop_wall_time(tiny_engine_model):
    """busy + wait{device} + wait{idle} is the loop thread's wall time
    to 2%: the phases tile an iteration, and the sums are flushed at
    the perf window's cadence and at loop exit."""
    engine = _engine(tiny_engine_model, steps_per_call=4)
    warm = engine.submit([1, 2, 3], 4)
    while warm.finished_at is None:       # compile before the clock runs
        engine.step_pipelined()
    engine.drain()
    engine._flush_loop_seconds()          # the warm-up's share
    metrics.reset_for_tests()
    engine.perf_window_s = 0.05
    t0 = time.perf_counter()
    engine.start()
    reqs = [engine.submit([1, 2, 3, i + 1], 24) for i in range(6)]
    for r in reqs:
        assert len(r.tokens()) == 24
    time.sleep(0.2)                       # some idle iterations too
    engine.stop()
    wall = time.perf_counter() - t0
    assert not engine._thread.is_alive()
    busy = _family('skytpu_engine_loop_busy_seconds_total')
    device = _family('skytpu_engine_loop_wait_seconds_total', on='device')
    idle = _family('skytpu_engine_loop_wait_seconds_total', on='idle')
    assert busy > 0 and device > 0 and idle > 0.1
    assert busy + device + idle == pytest.approx(wall, rel=0.02)
    # Nothing is left unflushed, and no phase went to the ring.
    assert engine._loop_busy_s == engine._loop_idle_s == 0.0
    assert not any(n.startswith('engine.loop.')
                   for s in tracing.recent_requests() for n in s['spans'])


# ----- on the profiler's clock ------------------------------------------------
def test_profiler_session_holds_engine_and_trainer_phases(
        tiny_engine_model, tmp_path):
    """A jax.profiler session on the CPU holds engine.loop.* and
    train.* host events; the same work without a session leaves the
    ring as it was (a phase is no ring event)."""
    import jax
    import jax.numpy as jnp
    from skypilot_tpu.models.llama import LLAMA_CONFIGS, Llama
    from skypilot_tpu.parallel.mesh import build_mesh, plan_mesh
    from skypilot_tpu.train.trainer import TrainConfig, Trainer

    engine = _engine(tiny_engine_model)
    mesh = build_mesh(plan_mesh(1), jax.devices()[:1])
    tokens = jnp.ones((2, 16), jnp.int32)
    trainer = Trainer(Llama(LLAMA_CONFIGS['tiny'], mesh), mesh,
                      jax.random.PRNGKey(0), tokens,
                      TrainConfig(warmup_steps=1, total_steps=10))

    def work(tag):
        engine.start()
        try:
            assert engine.submit([1, 2, 3], 6,
                                 request_id=f'{tag}-r').tokens()
            time.sleep(0.01)              # an idle iteration or two
        finally:
            engine.stop()
            engine._stop.clear()
        trainer.run(iter(lambda: tokens, None), 4, log_every=2,
                    log_fn=lambda m: None)

    work('warm')                          # compiles outside the session
    ring_before = {s['request_id'] for s in tracing.recent_requests()}
    work('plain')
    ring = {s['request_id']: s for s in tracing.recent_requests()}
    assert set(ring) - ring_before == {'plain-r'}
    assert not any(n.startswith(('engine.loop.', 'train.feed',
                                 'train.dispatch', 'train.fetch',
                                 'train.export'))
                   for s in ring.values() for n in s['spans'])

    jax.profiler.start_trace(str(tmp_path))
    try:
        work('traced')
    finally:
        jax.profiler.stop_trace()
    names = _host_event_names(str(tmp_path))
    for want in ('engine.loop.dispatch', 'engine.loop.fetch',
                 'engine.loop.emit', 'engine.loop.admit',
                 'engine.loop.idle', 'train.feed', 'train.dispatch',
                 'train.fetch', 'train.export'):
        assert want in names, (want, sorted(
            n for n in names if n.startswith(('engine.', 'train.'))))


# ----- set-up -----------------------------------------------------------------
def test_prewarm_records_one_compile_span_per_program():
    """The virtual-mesh prewarm path: one engine.setup.compile per
    program (2 buckets x padded sizes {1, 2} prefills, scratch, chunk,
    2 chunk inserts, decode), inside one engine.setup.prewarm."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from skypilot_tpu.inference import engine as engine_mod
    from skypilot_tpu.models.llama import LLAMA_CONFIGS, Llama, init_params
    from skypilot_tpu.parallel.mesh import build_serve_mesh
    cfg = dataclasses.replace(LLAMA_CONFIGS['tiny'], dtype=jnp.float32)
    params = init_params(Llama(cfg), jax.random.PRNGKey(0))['params']
    mesh = build_serve_mesh(2, n_heads=cfg.n_heads,
                            n_kv_heads=cfg.n_kv_heads)
    engine = engine_mod.DecodeEngine(
        Llama(cfg, mesh), params,
        engine_mod.EngineConfig(mesh=mesh, n_slots=2,
                                prefill_buckets=(8, 16)))
    engine.prewarm()
    events = tracing.events_for(engine_mod.SETUP_REQUEST_ID)
    compiles = [e for e in events if e['name'] == 'engine.setup.compile']
    shapes = [(e['attrs']['kind'], e['attrs'].get('bucket'),
               e['attrs'].get('rows')) for e in compiles]
    assert len(shapes) == 9
    assert set(shapes) == (
        {('prefill', b, n) for b in (8, 16) for n in (1, 2)} |
        {('scratch', None, None), ('chunk', 16, None),
         ('chunk_insert', 8, None), ('chunk_insert', 16, None),
         ('decode', None, None)})
    whole = [e for e in events if e['name'] == 'engine.setup.prewarm']
    assert len(whole) == 1
    assert whole[0]['attrs']['programs'] == 9
    assert whole[0]['dur_ms'] >= sum(e['dur_ms'] for e in compiles) * 0.99
    # Each dummy dispatch was a compile of its own.
    assert engine._prefill_insert._cache_size() == 4
    assert engine._chunk_insert._cache_size() == 2
    assert engine._prefill_chunk._cache_size() == 1


def test_mesh_prewarm_compiles_one_program_a_bucket_with_prefill_rows():
    """The virtual-mesh prewarm path for a model that declares
    `prefill_rows`: one prefill program a bucket, compiled for the slots'
    rows (the dummy dispatch hands it no valid row, so its loops take no
    trip), and a group through it compiles nothing more."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from skypilot_tpu.inference import engine as engine_mod
    from skypilot_tpu.models.llama import LLAMA_CONFIGS, Llama, init_params
    from skypilot_tpu.parallel.mesh import build_serve_mesh
    cfg = dataclasses.replace(LLAMA_CONFIGS['tiny'], dtype=jnp.float32)
    params = init_params(Llama(cfg), jax.random.PRNGKey(0))['params']
    mesh = build_serve_mesh(2, n_heads=cfg.n_heads,
                            n_kv_heads=cfg.n_kv_heads)
    from served_utils import declaring
    kind = declaring(Llama, prefill_rows=1)
    engine = engine_mod.DecodeEngine(
        kind(cfg, mesh), params,
        engine_mod.EngineConfig(mesh=mesh, n_slots=4,
                                prefill_buckets=(8, 16),
                                max_prompt_len=16))
    before = len(tracing.events_for(engine_mod.SETUP_REQUEST_ID))
    engine.prewarm()
    shapes = [(e['attrs']['kind'], e['attrs'].get('bucket'),
               e['attrs'].get('rows'))
              for e in tracing.events_for(
                  engine_mod.SETUP_REQUEST_ID)[before:]
              if e['name'] == 'engine.setup.compile']
    assert sorted(shapes, key=str) == sorted(
        [('prefill', 8, 4), ('prefill', 16, 4), ('decode', None, None)],
        key=str)
    assert engine._prefill_insert._cache_size() == 2
    reqs = [engine.submit([1, 2, 3], 4), engine.submit([5, 6], 4),
            engine.submit(list(range(1, 12)), 3)]
    for _ in range(100):
        engine.step_pipelined()
        if all(r.finished_at is not None for r in reqs):
            break
    assert [len(r.tokens()) for r in reqs] == [4, 4, 3]
    assert engine._prefill_insert._cache_size() == 2


def test_pinned_programs_carry_their_shape_in_their_name():
    """jit names a program after its function: the pinned prefill and
    chunk programs are named for their shapes, prefix unchanged."""
    import jax
    from skypilot_tpu.inference.engine import _named

    def prefill_insert(x):
        return x + 1

    lowered = jax.jit(_named(prefill_insert,
                             'prefill_insert_b512_n16')).lower(1.0)
    assert '@jit_prefill_insert_b512_n16' in lowered.as_text()
    assert prefill_insert.__name__ == 'prefill_insert'   # not renamed


def test_pinned_prewarm_names_each_program_for_its_shape(tiny_engine_model):
    """The TPU path, run here by calling the layout pass by hand: every
    pinned prefill and chunk program is compiled once under a name that
    says its shape, each leaves an engine.setup.compile span, and
    traffic through them adds no compile."""
    from skypilot_tpu.inference import engine as engine_mod
    engine = _engine(tiny_engine_model, prefill_buckets=(8, 16),
                     max_prompt_len=40)
    engine._optimize_layouts()
    engine.prewarm()
    names = {key: fn.as_text().split(',', 1)[0].split()[-1]
             for key, fn in {**engine._prefill_compiled,
                             **engine._chunk_compiled}.items()}
    assert names == {
        (8, 1): 'jit_prefill_insert_b8_n1',
        (8, 2): 'jit_prefill_insert_b8_n2',
        (16, 1): 'jit_prefill_insert_b16_n1',
        (16, 2): 'jit_prefill_insert_b16_n2',
        ('chunk', 16): 'jit_prefill_chunk_w16',
        ('insert', 8): 'jit_prefill_chunk_insert_b8',
        ('insert', 16): 'jit_prefill_chunk_insert_b16'}
    events = tracing.events_for(engine_mod.SETUP_REQUEST_ID)
    kinds = [e['attrs']['kind'] for e in events
             if e['name'] == 'engine.setup.compile']
    assert sorted(kinds) == sorted(
        ['decode'] + ['prefill'] * 4 + ['scratch', 'chunk'] +
        ['chunk_insert'] * 2)
    whole = [e for e in events if e['name'] == 'engine.setup.prewarm']
    assert [e['attrs']['programs'] for e in whole] == [8]   # not the decode
    reqs = [engine.submit([1, 2, 3], 4), engine.submit([5, 6], 4),
            engine.submit(list(range(1, 30)), 3)]           # chunked
    for _ in range(400):
        engine.step_pipelined()
        if all(r.finished_at is not None for r in reqs):
            break
    assert [len(r.tokens()) for r in reqs] == [4, 4, 3]
    after = [e for e in tracing.events_for(engine_mod.SETUP_REQUEST_ID)
             if e['name'] == 'engine.setup.compile']
    assert len(after) == len(kinds)
