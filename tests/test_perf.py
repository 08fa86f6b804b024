"""Device-level performance observability (skypilot_tpu/perf/):

- cost attribution: live MFU / HBM-bytes-per-token gauges computed
  host-side from the static cost model, with ZERO added device syncs
  (mesh=None and tensor=2) and zero recompiles while armed;
- XLA compile telemetry + the runtime recompile sentinel (record-only
  and SKYTPU_STRICT_RECOMPILE=1 hard-failure modes);
- on-demand profiler capture with bounded retention and shutdown
  cleanup (the /debug/profile route and its LB federation);
- the program and the benchmark counting the same model's parameters;
- the serve ready-view cache (the control plane's hottest read).
"""
import asyncio
import dataclasses
import json
import os
import pathlib
import re
import socket
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from skypilot_tpu.perf import compile_telemetry
from skypilot_tpu.perf import cost_model as cost_model_lib
from skypilot_tpu.perf import profiler as profiler_lib
from skypilot_tpu.server import metrics
from skypilot_tpu.server import tracing

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO_ROOT))      # `benchmarks` is no installed package
BENCH_CONFIGS = sorted((REPO_ROOT / 'benchmarks' / 'configs').glob('*.json'))


@pytest.fixture(autouse=True)
def _reset():
    metrics.reset_for_tests()
    tracing.reset_for_tests()
    compile_telemetry.reset_for_tests()
    yield
    metrics.reset_for_tests()
    tracing.reset_for_tests()
    compile_telemetry.reset_for_tests()


def _parse_exposition(text):
    """-> {(name, labels_str): float} for sample lines."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith('#'):
            continue
        m = re.match(r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})?\s+(\S+)$',
                     line)
        assert m is not None, f'unparseable sample line: {line!r}'
        out[(m.group(1), m.group(2) or '')] = float(m.group(3))
    return out


def _gauge(name):
    samples = _parse_exposition(metrics.render())
    vals = [v for (n, _), v in samples.items() if n == name]
    return vals[0] if vals else None


class _CountingNumpy:
    """numpy shim that counts asarray() calls — the engine's one
    device->host sync per step goes through np.asarray."""

    def __init__(self, real):
        self._real = real
        self.asarray_calls = 0

    def __getattr__(self, name):
        return getattr(self._real, name)

    def asarray(self, *args, **kwargs):
        self.asarray_calls += 1
        return self._real.asarray(*args, **kwargs)


@pytest.fixture(scope='module')
def tiny_engine_model():
    import jax
    from skypilot_tpu.models.llama import LLAMA_CONFIGS, Llama, init_params
    model = Llama(LLAMA_CONFIGS['tiny'])
    params = init_params(model, jax.random.PRNGKey(0))['params']
    return model, params


# ----- cost model (pure arithmetic) -------------------------------------------
def test_cost_model_hand_arithmetic():
    # K+V, per layer, per kv head, per head_dim element, 2 bytes each.
    cm = cost_model_lib.EngineCostModel(
        n_params=100, n_layers=2, dim=8, param_bytes=400,
        cache_bytes_per_pos=2 * 2 * 2 * 4 * 2, n_chips=1, chip='v5e')
    assert cm.decode_flops_per_token(10) == 2 * 100 + 2 * 2 * 10 * 8
    assert cm.kv_bytes_per_pos() == 2 * 2 * 2 * 4 * 2
    # weights amortized over the batch + kv history read + 1-pos write.
    assert cm.decode_hbm_bytes_per_token(10, n_active=4) == \
        400 / 4 + cm.kv_bytes_per_pos() * 10 + cm.kv_bytes_per_pos()
    assert cm.arith_intensity(10, 4) == pytest.approx(
        cm.decode_flops_per_token(10) /
        cm.decode_hbm_bytes_per_token(10, 4))
    # Roofline: min of compute-bound and bandwidth-bound token rates.
    assert cm.roofline_decode_tokens_per_s(10, 4) == pytest.approx(min(
        197e12 / cm.decode_flops_per_token(10),
        819e9 / cm.decode_hbm_bytes_per_token(10, 4)))
    assert cm.prefill_seconds(16) > 0


class _Cfg:
    n_layers, dim = 2, 8

    @staticmethod
    def num_params():
        return 100


def _leaf(shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype)


def test_cost_model_kv_dtype_width_halves_kv_bytes():
    """The cache's element width is read off its leaves, so a narrower
    page pool lands as a measured bytes/token drop: int8 pages carry
    half the payload of bf16 ones, plus their f32 scales."""
    import jax.numpy as jnp
    shape = (5, 2, 16, 4)                 # [pages, kv heads, positions, D]

    def layers(leaf):
        return {f'layer_{i}': {'attn': {'k': leaf, 'v': leaf}}
                for i in range(2)}

    def build(cache):
        return cost_model_lib.EngineCostModel.from_engine_state(
            _Cfg, [_leaf((100,), jnp.float32)], cache, chip='v5e')

    wide = build(layers(_leaf(shape, jnp.bfloat16)))
    narrow = build(layers({'data': _leaf(shape, jnp.int8),
                           'scale': _leaf(shape[:3], jnp.float32)}))
    assert wide.kv_bytes_per_pos() == 2 * 2 * 2 * 4 * 2
    assert narrow.kv_bytes_per_pos() == \
        wide.kv_bytes_per_pos() / 2 + 2 * 2 * 2 * 4
    assert wide.n_kv_layers == narrow.n_kv_layers == 2


def test_cost_model_counts_a_latent_per_position_without_heads():
    """A latent a position ([slots, positions, width], no head axis) is
    a third kind of leaf: per position like K and V, not per-slot state
    read and written whole each step.  Which leaves hold one is the
    model's to say (`latent_leaves`), whatever it calls them: unnamed,
    the same leaves count as per-slot state."""
    import jax.numpy as jnp
    cache = {f'layer_{i}': {'attn': {
        'lat': _leaf((3, 64, 32), jnp.bfloat16),
        'rot': _leaf((3, 64, 8), jnp.bfloat16)}} for i in range(2)}

    def build(**kw):
        return cost_model_lib.EngineCostModel.from_engine_state(
            _Cfg, [_leaf((100,), jnp.float32)], cache, chip='v5e', **kw)

    cm = build(latent=('lat', 'rot'))
    assert cm.kv_bytes_per_pos() == 2 * (32 + 8) * 2
    assert cm.n_kv_layers == 2 and cm.state_bytes_per_slot == 0
    n_bytes = 2 * 3 * 64 * (32 + 8) * 2
    assert cost_model_lib.cache_bytes_by_kind(cache, ('lat', 'rot')) == {
        'latent': n_bytes}
    assert build().state_bytes_per_slot == n_bytes / 3
    assert cost_model_lib.cache_bytes_by_kind(cache) == {
        'recurrent': n_bytes}


def test_cost_model_counts_a_window_layers_ring_as_a_window_of_positions():
    """A window layer's ring ([slots, kv heads, W, width], named by the
    model in `window_leaves`) is a fourth kind of leaf: a step reads
    min(context, W) positions of it, not the context and not the W that
    its own axis would give a leaf read as "positions of the context".  A
    key kept in two leaves (its unrotated part, and the rotated parts of
    two heads a row) is one layer, not one and a half.  Unnamed, the same
    leaves count as per-slot state."""
    import jax.numpy as jnp
    w, s = 8, 64
    cache = {
        'layer_0': {'attn': {        # a full layer: 2 KV heads
            'k': {'nope': _leaf((3, 2, s, 16), jnp.bfloat16),
                  'rope': _leaf((3, 1, s, 16), jnp.bfloat16)},
            'v': _leaf((3, 2, s, 16), jnp.bfloat16)}},
        **{f'layer_{i}': {'attn': {  # two window layers: 4 KV heads
            'ring_k': {'nope': _leaf((3, 4, w, 16), jnp.bfloat16),
                       'rope': _leaf((3, 2, w, 16), jnp.bfloat16)},
            'ring_v': _leaf((3, 4, w, 16), jnp.bfloat16)}}
           for i in (1, 2)}}
    names = ('ring_k', 'ring_v')

    def build(**kw):
        return cost_model_lib.EngineCostModel.from_engine_state(
            _Cfg, [_leaf((100,), jnp.float32)], cache, chip='v5e', **kw)

    cm = build(window=names)
    full_pos = 2 * (16 + 8 + 16) * 2            # bytes a position
    ring_pos = 2 * 4 * (16 + 8 + 16) * 2        # of both window layers
    assert cm.kv_bytes_per_pos() == full_pos
    assert (cm.n_kv_layers, cm.n_window_layers, cm.window_len) == (1, 2, w)
    assert cm.window_bytes_per_pos == ring_pos and \
        cm.state_bytes_per_slot == 0
    assert cost_model_lib.cache_bytes_by_kind(cache, window=names) == {
        'kv': 3 * s * full_pos, 'window': 3 * w * ring_pos}
    assert cost_model_lib.window_len(cache, names) == w
    assert cost_model_lib.window_len(cache, ()) is None
    # Past the window the rings' term stands still; under it, it grows.
    weights = cm.param_bytes / 2
    assert cm.decode_hbm_bytes_per_token(40, 2) == (
        weights + 41 * full_pos + (w + 1) * ring_pos)
    assert cm.decode_hbm_bytes_per_token(5, 2) == (
        weights + 6 * full_pos + 6 * ring_pos)
    assert cm.decode_flops_per_token(40) == (
        2.0 * cm.n_params + 2.0 * cm.dim * (40 + 2 * w))
    assert cm.decode_flops_per_token(5) == (
        2.0 * cm.n_params + 2.0 * cm.dim * (5 + 2 * 5))
    assert cm.decode_hbm_bytes_per_token(4000, 2) - \
        cm.decode_hbm_bytes_per_token(40, 2) == 3960 * full_pos
    # Unnamed: state of fixed size, read and written whole.
    assert build().state_bytes_per_slot == w * ring_pos
    assert cost_model_lib.cache_bytes_by_kind(cache) == {
        'kv': 3 * s * full_pos, 'recurrent': 3 * w * ring_pos}


def test_cost_model_reads_a_state_space_models_state_and_kv_from_its_cache():
    """Granite-4.0-H as the benchmark serves it, 64 slots of 1024
    positions, the cache as shapes: with no case of its own the cost model
    reads 36 Mamba-2 layers' float32 state and taps as per-slot state
    (76,437,504 B a slot, read and written whole a step) and 4 attention
    layers' K and V, two KV heads a row, as 8,192 B a position.  At 64
    slots and 400 positions a token then costs 100 MB of weights, 153 MB
    of state and 3 MB of K and V: the state is three fifths of it."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from benchmarks import families
    config = json.loads((REPO_ROOT / 'benchmarks' / 'configs' /
                         'granite-4.0-h-micro.json').read_text())
    family = families.load(config)
    model = family.serve_model(family.dims(config), config, jnp.bfloat16)
    params = nn.meta.unbox(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))['params']))
    slots = config['serve']['n_slots']
    step = jnp.zeros((slots, 1), jnp.int32)
    cache = jax.eval_shape(lambda p: model.apply(
        {'params': p}, step, positions=step, decode=True,
        mutable=['cache'])[1]['cache'], params)
    assert cost_model_lib.cache_bytes_by_kind(cache) == {
        'kv': 64 * 1024 * 8192, 'recurrent': 64 * 76437504}
    cm = cost_model_lib.EngineCostModel.from_engine_state(
        model.cfg, jax.tree.leaves(params), cache, chip='v5e')
    assert cm.param_bytes == 2 * 3191396096 == 2 * cm.n_params
    assert (cm.n_layers, cm.n_kv_layers, cm.n_window_layers) == (40, 4, 0)
    assert cm.kv_bytes_per_pos() == 8192
    assert cm.state_bytes_per_slot == 36 * (64 * 64 * 128 * 4 +
                                            3 * 4352 * 2) == 76437504
    a_token = cm.decode_hbm_bytes_per_token(400, slots)
    assert a_token == 2 * 3191396096 / 64 + 401 * 8192 + 2 * 76437504
    assert 0.59 < 2 * cm.state_bytes_per_slot / a_token < 0.60
    assert cm.decode_flops_per_token(400) == \
        2.0 * 3191396096 + 2.0 * 2048 * 4 * 400


def test_cost_model_reads_a_convolved_latents_kv_and_fixed_leaves():
    """ZAYA1-8B's stage as the benchmark serves it, 16 slots of 13,312
    positions, the cache as shapes: with no case of its own the cost model
    reads 20 layers' K and V of the 2 latent heads as 20,480 B a position
    (a multi-head cache at this width would be 163,840) and the three
    leaves of fixed size, two rows of taps and a half value a layer, as
    107,520 B of per-slot state read and written a step.  At 16 slots and
    10,500 positions a token then costs 586 MB of weights (every expert
    counted: the cost model does not know the routing) and 215 MB of K
    and V; the fixed leaves are 0.2 MB of it."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from benchmarks import families
    config = json.loads((REPO_ROOT / 'benchmarks' / 'configs' /
                         'zaya1-8b-pp2.json').read_text())
    family = families.load(config)
    model = family.serve_model(family.dims(config), config, jnp.bfloat16)
    params = nn.meta.unbox(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))['params']))
    slots = config['serve']['n_slots']
    step = jnp.zeros((slots, 1), jnp.int32)
    cache = jax.eval_shape(lambda p: model.apply(
        {'params': p}, step, positions=step, decode=True,
        mutable=['cache'])[1]['cache'], params)
    assert cost_model_lib.cache_bytes_by_kind(cache) == {
        'kv': 16 * 13312 * 20480, 'recurrent': 16 * 107520}
    cm = cost_model_lib.EngineCostModel.from_engine_state(
        model.cfg, jax.tree.leaves(params), cache, chip='v5e')
    assert cm.param_bytes == 2 * 4688810364 == 2 * cm.n_params
    assert (cm.n_layers, cm.n_kv_layers, cm.n_window_layers) == (20, 20, 0)
    assert cm.kv_bytes_per_pos() == 20480
    assert cm.state_bytes_per_slot == 20 * (1280 + 1280 + 128) * 2 == 107520
    a_token = cm.decode_hbm_bytes_per_token(10500, slots)
    assert a_token == 2 * 4688810364 / 16 + 10501 * 20480 + 2 * 107520
    assert 0.26 < 10501 * 20480 / a_token < 0.27


@pytest.mark.parametrize('path', BENCH_CONFIGS, ids=lambda p: p.stem)
def test_program_counts_the_parameters_the_yardstick_counts(path):
    """The model object a configuration's family hands the program
    reports the parameter count the configuration's file states: the N
    of both live MFU gauges is the benchmark's.  No weights are made."""
    from benchmarks import families
    config = json.loads(path.read_text())
    family = families.load(config)
    dims = family.dims(config)
    if 'serve' in config:
        import jax.numpy as jnp
        model = family.serve_model(dims, config, jnp.bfloat16)
    else:
        import jax
        from skypilot_tpu.parallel.mesh import build_mesh, plan_mesh
        mesh = build_mesh(plan_mesh(1), jax.devices()[:1])
        model = family.train_model(dims, config, mesh, 4096)
    assert model.cfg.num_params() == config['params_total']


# ----- live attribution: zero added syncs, zero recompiles --------------------
def test_live_gauges_agree_with_cost_model_within_5pct_zero_syncs(
        tiny_engine_model, monkeypatch):
    """Acceptance: /metrics-exported MFU and bytes/token agree with
    the cost model evaluated by hand within 5%, and the whole
    attribution path adds ZERO device syncs (asarray still exactly
    once per active step) and zero recompiles while the sentinel is
    armed."""
    import numpy as real_np
    from skypilot_tpu.inference import engine as engine_mod
    counting = _CountingNumpy(real_np)
    monkeypatch.setattr(engine_mod, 'np', counting)
    model, params = tiny_engine_model
    engine = engine_mod.DecodeEngine(
        model, params,
        engine_mod.EngineConfig(n_slots=2, steps_per_call=4,
                                prefill_buckets=(8,)))
    prompt_len, new_tokens = 8, 8
    rng = real_np.random.default_rng(0)
    prompts = [rng.integers(0, model.cfg.vocab_size, prompt_len).tolist()
               for _ in range(6)]
    # Warm the decode shape before arming (first compiles are legit).
    w = engine.submit([1, 2, 3], 2)
    while w.finished_at is None:
        engine.step()
    # Warm the FUSED 2-row admission: saturated traffic admits into
    # both free slots in one grouped prefill dispatch (_admit_free
    # groups per bucket) — a distinct program from the single-row
    # admission the first warm compiled, so it must be submitted
    # CONCURRENTLY here or it would compile inside the measured region.
    ws = [engine.submit(p, 1) for p in prompts[:2]]
    while any(w.finished_at is None for w in ws):
        engine.step()
    engine.arm_recompile_sentinel()
    compiles_before = _gauge('skytpu_engine_xla_compile_total') or 0.0

    before = counting.asarray_calls
    engine.perf_window_s = 1e9
    engine.perf_reset_window()
    reqs = [engine.submit(p, new_tokens) for p in prompts]
    t0 = time.perf_counter()
    active_steps = 0
    while any(r.finished_at is None for r in reqs):
        if engine.step() > 0:
            active_steps += 1
    wall = time.perf_counter() - t0
    engine.perf_window_s = 0.0
    engine.step()
    # Zero ADDED syncs: still exactly one asarray per active step.
    assert counting.asarray_calls - before == active_steps
    # Zero recompiles with the sentinel armed.
    assert (_gauge('skytpu_engine_xla_compile_total') or 0.0) == \
        compiles_before
    assert not tracing.events_for(compile_telemetry.SENTINEL_REQUEST_ID)

    # Gauges agree with the cost model evaluated by hand within 5%.
    rate = sum(r.emitted for r in reqs) / wall
    cm = engine.perf_cost_model
    mean_ctx = prompt_len + new_tokens / 2.0
    mfu_live = _gauge('skytpu_engine_mfu')
    bytes_live = _gauge('skytpu_engine_hbm_bytes_per_token')
    intensity_live = _gauge('skytpu_engine_arith_intensity')
    assert mfu_live and mfu_live > 0
    assert bytes_live and bytes_live > 0
    assert intensity_live and intensity_live > 0
    assert mfu_live == pytest.approx(cm.mfu(rate, mean_ctx), rel=0.05)
    assert bytes_live == pytest.approx(
        cm.decode_hbm_bytes_per_token(mean_ctx, n_active=2), rel=0.05)


def test_sharded_engine_perf_gauges_zero_syncs(monkeypatch):
    """tensor=2: same contract on the sharded engine — gauges appear,
    one sync per active step, no recompiles after warmup."""
    import jax
    import numpy as real_np
    import jax.numpy as jnp
    from skypilot_tpu.inference import engine as engine_mod
    from skypilot_tpu.models.llama import LLAMA_CONFIGS, Llama, init_params
    from skypilot_tpu.parallel.mesh import build_serve_mesh
    cfg = dataclasses.replace(LLAMA_CONFIGS['tiny'], dtype=jnp.float32)
    params = init_params(Llama(cfg), jax.random.PRNGKey(0))['params']
    mesh = build_serve_mesh(2, n_heads=cfg.n_heads,
                            n_kv_heads=cfg.n_kv_heads)
    counting = _CountingNumpy(real_np)
    monkeypatch.setattr(engine_mod, 'np', counting)
    engine = engine_mod.DecodeEngine(
        Llama(cfg, mesh), params,
        engine_mod.EngineConfig(mesh=mesh, n_slots=2, steps_per_call=3,
                                prefill_buckets=(8,)))
    assert engine.perf_cost_model is not None
    assert engine.perf_cost_model.n_chips == 2
    w = engine.submit([1, 2, 3], 2)
    while w.finished_at is None:
        engine.step()
    w = engine.submit([4, 5, 6, 7], 1)   # warm the padded admission
    while w.finished_at is None:
        engine.step()
    engine.arm_recompile_sentinel()
    compiles_before = _gauge('skytpu_engine_xla_compile_total') or 0.0
    before = counting.asarray_calls
    engine.perf_window_s = 1e9
    engine.perf_reset_window()
    req = engine.submit([1, 2, 3, 4], 6)
    active_steps = 0
    while req.finished_at is None:
        if engine.step() > 0:
            active_steps += 1
    engine.perf_window_s = 0.0
    engine.step()
    assert counting.asarray_calls - before == active_steps
    assert (_gauge('skytpu_engine_xla_compile_total') or 0.0) == \
        compiles_before
    assert (_gauge('skytpu_engine_mfu') or 0.0) > 0
    assert (_gauge('skytpu_engine_hbm_bytes_per_token') or 0.0) > 0


# ----- compile telemetry + recompile sentinel ---------------------------------
def test_compile_telemetry_counts_compiles():
    import jax
    import numpy as np
    compile_telemetry.install()
    before = _gauge('skytpu_engine_xla_compile_total') or 0.0

    @jax.jit
    def f(x):
        return x * 2 + 1

    f(np.ones((3,), np.float32)).block_until_ready()
    after = _gauge('skytpu_engine_xla_compile_total') or 0.0
    assert after == before + 1
    samples = _parse_exposition(metrics.render())
    assert samples[('skytpu_engine_xla_compile_seconds_count', '')] >= 1


def test_strict_recompile_sentinel_trips_on_unpinned_shape(monkeypatch):
    """Armed + SKYTPU_STRICT_RECOMPILE=1: a post-warmup compile (the
    runtime signature of an unpinned shape) raises INSIDE the jit call
    and leaves the perf.recompile instant event in the flight
    recorder under the fixed sentinel request id."""
    import jax
    import numpy as np
    compile_telemetry.install()

    @jax.jit
    def g(x):
        return x + 1

    g(np.ones((2, 2), np.float32))       # warmup compile, unarmed
    compile_telemetry.arm()
    monkeypatch.setenv(compile_telemetry.STRICT_ENV, '1')
    try:
        with pytest.raises(RuntimeError, match='post-warmup'):
            g(np.ones((3, 3), np.float32))   # unpinned shape: recompile
    finally:
        compile_telemetry.disarm()
    events = tracing.events_for(compile_telemetry.SENTINEL_REQUEST_ID)
    assert any(e['name'] == 'perf.recompile' for e in events)


def test_recompile_sentinel_record_only_without_strict(monkeypatch):
    import jax
    import numpy as np
    compile_telemetry.install()
    monkeypatch.delenv(compile_telemetry.STRICT_ENV, raising=False)

    @jax.jit
    def h(x):
        return x - 1

    h(np.ones((2,), np.float32))
    compile_telemetry.arm()
    h(np.ones((5,), np.float32))         # records, does not raise
    compile_telemetry.disarm()
    events = tracing.events_for(compile_telemetry.SENTINEL_REQUEST_ID)
    assert any(e['name'] == 'perf.recompile' for e in events)


# ----- profiler capture + retention -------------------------------------------
def test_profile_store_capture_retention_prune(tmp_path):
    store = profiler_lib.ProfileStore(root=str(tmp_path / 'prof'),
                                      retain=2)
    summaries = [store.capture(10.0) for _ in range(3)]
    assert all(s['artifact'] for s in summaries), summaries
    # Retention-bounded: only the newest 2 captures survive.
    assert store.captures() == ['capture-000002', 'capture-000003']
    art = store.artifact_path(summaries[-1]['artifact'])
    assert art.is_file() and art.stat().st_size > 0
    with pytest.raises(ValueError, match='escapes'):
        store.artifact_path('../outside')
    with pytest.raises(FileNotFoundError):
        store.artifact_path('capture-000001/nope.gz')
    # User-supplied root: cleanup removes our captures, keeps the dir.
    store.cleanup()
    assert store.captures() == []
    assert store.root.is_dir()


def test_profile_store_owned_tmpdir_removed_on_cleanup(monkeypatch):
    monkeypatch.delenv(profiler_lib.DIR_ENV, raising=False)
    store = profiler_lib.ProfileStore()
    store.capture(5.0)
    root = store.root
    assert root.is_dir()
    store.cleanup()                       # satellite-6 regression: the
    assert not root.exists()              # tmpdir must not leak


def test_profile_capture_busy_is_409_shaped(tmp_path):
    store = profiler_lib.ProfileStore(root=str(tmp_path), retain=1)
    assert store._lock.acquire(blocking=False)
    try:
        with pytest.raises(profiler_lib.CaptureBusy):
            store.capture(5.0)
    finally:
        store._lock.release()
    with pytest.raises(ValueError, match='positive'):
        store.capture(0)


# ----- server route + LB federation e2e ---------------------------------------
def _free_port():
    s = socket.socket()
    s.bind(('127.0.0.1', 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_app_on_thread(app):
    """Serve an aiohttp app on its own thread; -> (port, stop_fn).
    stop_fn runs the app's cleanup hooks (the shutdown path under
    test) before stopping the loop."""
    from aiohttp import web
    loop = asyncio.new_event_loop()
    started = threading.Event()
    state = {}

    def run():
        asyncio.set_event_loop(loop)

        async def start():
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, '127.0.0.1', 0)
            await site.start()
            state['port'] = site._server.sockets[0].getsockname()[1]
            state['runner'] = runner

        loop.run_until_complete(start())
        started.set()
        loop.run_forever()

    th = threading.Thread(target=run, daemon=True)
    th.start()
    assert started.wait(10)

    def stop():
        fut = asyncio.run_coroutine_threadsafe(
            state['runner'].cleanup(), loop)
        fut.result(timeout=10)
        loop.call_soon_threadsafe(loop.stop)
        th.join(timeout=5)

    return state['port'], stop


def _get_json(url, timeout=30.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read().decode())


def test_debug_profile_route_and_shutdown_cleanup(tiny_engine_model,
                                                  monkeypatch):
    monkeypatch.delenv(profiler_lib.DIR_ENV, raising=False)
    from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig
    from skypilot_tpu.inference.server import build_app
    model, params = tiny_engine_model
    engine = DecodeEngine(model, params,
                          EngineConfig(n_slots=2, prefill_buckets=(8,)))
    app = build_app(engine)
    store = app['skytpu_profile_store']
    port, stop = _run_app_on_thread(app)
    base = f'http://127.0.0.1:{port}'
    try:
        status, doc = _get_json(base + '/debug/profile?duration_ms=20')
        assert status == 200
        assert doc['artifact'] and doc['size_bytes'] > 0
        assert doc['name'] in doc['retained']
        # The artifact is downloadable while retained.
        with urllib.request.urlopen(
                f'{base}/debug/profile/artifact/{doc["artifact"]}',
                timeout=10) as resp:
            assert resp.status == 200
            assert len(resp.read()) == doc['size_bytes']
        # Malformed requests are 4xx, not 500s.
        with pytest.raises(urllib.error.HTTPError) as err:
            _get_json(base + '/debug/profile?duration_ms=banana')
        assert err.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as err:
            _get_json(base + '/debug/profile/artifact/..%2Fescape')
        assert err.value.code == 404
        root = store.root
        assert root.is_dir()
    finally:
        stop()
    # Shutdown cleanup (satellite-6 regression): nothing left on disk.
    assert not root.exists()


def test_lb_federates_debug_profile(tiny_engine_model):
    from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig
    from skypilot_tpu.inference.server import build_app
    from skypilot_tpu.serve.load_balancer import LoadBalancer
    from skypilot_tpu.serve.load_balancing_policies import RoundRobinPolicy
    model, params = tiny_engine_model
    engine = DecodeEngine(model, params,
                          EngineConfig(n_slots=2, prefill_buckets=(8,)))
    port, stop_replica = _run_app_on_thread(build_app(engine))
    replica_url = f'http://127.0.0.1:{port}'
    lb = LoadBalancer(
        'perf-svc', _free_port(), RoundRobinPolicy(),
        ready_urls_fn=lambda: [replica_url],
        ready_replicas_fn=lambda: [(3, replica_url)])
    lb.start()
    try:
        status, doc = _get_json(
            lb.endpoint + '/debug/profile?duration_ms=20')
        assert status == 200
        assert doc['service'] == 'perf-svc'
        caps = doc['captures']
        assert len(caps) == 1 and caps[0]['replica'] == '3'
        assert caps[0]['ok'] and caps[0]['artifact']
    finally:
        lb.stop()
        stop_replica()


# ----- serve ready-view cache (fleetsim hot path) -----------------------------
@pytest.fixture()
def _serve_db(tmp_path, monkeypatch):
    monkeypatch.setenv('SKYTPU_SERVE_DB', str(tmp_path / 'serve.db'))
    monkeypatch.delenv('SKYTPU_DB_URL', raising=False)
    yield


def _mini_manager():
    from skypilot_tpu import task as task_lib
    from skypilot_tpu.serve import replica_managers
    from skypilot_tpu.serve.service_spec import ServiceSpec
    spec = ServiceSpec.from_yaml_config({
        'readiness_probe': '/health',
        'replica_policy': {'min_replicas': 1},
    })
    return replica_managers.ReplicaManager('cachesvc', spec,
                                           task_lib.Task(run='x'))


def _cache_counts():
    samples = _parse_exposition(metrics.render())
    hit = samples.get(('skytpu_serve_ready_view_cache_total',
                       '{result="hit"}'), 0.0)
    miss = samples.get(('skytpu_serve_ready_view_cache_total',
                        '{result="miss"}'), 0.0)
    return hit, miss


def test_ready_view_cached_and_invalidated_on_transitions(_serve_db):
    from skypilot_tpu.serve import serve_state
    m = _mini_manager()
    serve_state.add_replica('cachesvc', 1, 'c1')
    serve_state.set_replica_endpoint('cachesvc', 1, 'http://r1', None)
    serve_state.set_replica_status('cachesvc', 1,
                                   serve_state.ReplicaStatus.READY)
    # First view re-queries; repeats inside the version+TTL window hit.
    assert m.ready_replicas() == [(1, 'http://r1', None)]
    hit0, miss0 = _cache_counts()
    assert (hit0, miss0) == (0.0, 1.0)
    assert m.num_live() == 1
    assert m.ready_urls() == ['http://r1']
    hit1, miss1 = _cache_counts()
    assert miss1 == miss0 and hit1 >= 2
    # Any state transition invalidates — the view is never stale.
    serve_state.set_replica_status('cachesvc', 1,
                                   serve_state.ReplicaStatus.NOT_READY)
    assert m.ready_replicas() == []
    _, miss2 = _cache_counts()
    assert miss2 == miss1 + 1
    # Guarded no-op transitions do NOT invalidate (rowcount 0).
    assert not serve_state.set_replica_status_if(
        'cachesvc', 1, serve_state.ReplicaStatus.READY,
        serve_state.ReplicaStatus.NOT_READY)
    assert m.ready_replicas() == []
    _, miss3 = _cache_counts()
    assert miss3 == miss2


def test_ready_view_ttl_zero_disables_cache(_serve_db, monkeypatch):
    from skypilot_tpu.serve import replica_managers, serve_state
    monkeypatch.setattr(replica_managers, '_READY_VIEW_TTL_S', 0.0)
    m = _mini_manager()
    serve_state.add_replica('cachesvc', 1, 'c1')
    m.ready_replicas()
    m.ready_replicas()
    hit, miss = _cache_counts()
    assert hit == 0.0 and miss == 2.0


def test_fleetsim_profile_reports_cache_rows(_serve_db):
    """The per-run control-plane profile folds the ready-view cache
    counter in: that the ready view is served from cache shows up in
    the run report itself."""
    from skypilot_tpu.fleetsim import profile as fleet_profile
    from skypilot_tpu.serve import serve_state
    before = fleet_profile.snapshot()
    m = _mini_manager()
    serve_state.add_replica('cachesvc', 1, 'c1')
    for _ in range(5):
        m.ready_replicas()
    rows = fleet_profile.diff(before, fleet_profile.snapshot())
    paths = {r['path']: r for r in rows}
    assert paths['cache.ready_view[hit]']['calls'] == 4
    assert paths['cache.ready_view[miss]']['calls'] == 1
