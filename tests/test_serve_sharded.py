"""Tensor-parallel decode engine: serve-mesh planning + sharded serving.

Runs against the 8-device virtual CPU mesh (conftest forces
``--xla_force_host_platform_device_count=8``), which exercises the same
pjit/NamedSharding programs that run on a real TPU slice.  The tiny
model is switched to float32 COMPUTE here: the tensor=1/2/4 engines are
separately compiled programs whose o_proj/down_proj reductions split
differently, and bf16's one-ULP fusion-order noise flips argmax on
random weights (see test_inference.py's pipelined-vs-sync note); in f32
the tiny model's greedy tokens are stable across the partitionings.

The parity model is an MHA variant (n_kv_heads == n_heads == 4) so
tensor=4 divides the KV heads; the stock GQA tiny (4q/2kv) gets its own
tensor=2 parity test plus the tensor=4 rejection test.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig
from skypilot_tpu.models.llama import LLAMA_CONFIGS, Llama, init_params
from skypilot_tpu.parallel.mesh import (MeshPlan, build_mesh,
                                        build_serve_mesh, plan_mesh,
                                        plan_serve_mesh,
                                        validate_tensor_parallel)

TINY_GQA = dataclasses.replace(LLAMA_CONFIGS['tiny'], dtype=jnp.float32)
CFG = dataclasses.replace(TINY_GQA, n_kv_heads=4)   # MHA: tensor=4 legal


@pytest.fixture(scope='module')
def params():
    return init_params(Llama(CFG), jax.random.PRNGKey(0))['params']


@pytest.fixture(scope='module')
def gqa_params():
    return init_params(Llama(TINY_GQA), jax.random.PRNGKey(0))['params']


def naive_greedy(cfg, params, prompt_ids, n_new):
    """Reference: full forward over the growing sequence each step,
    single-device model."""
    model = Llama(cfg)
    ids = list(prompt_ids)
    for _ in range(n_new):
        logits = model.apply({'params': params},
                             jnp.asarray([ids], jnp.int32))
        ids.append(int(jnp.argmax(logits[0, -1])))
    return ids[len(prompt_ids):]


def make_engine(params, tensor, **overrides):
    mesh = None
    if tensor > 1:
        mesh = build_serve_mesh(tensor, n_heads=CFG.n_heads,
                                n_kv_heads=CFG.n_kv_heads)
    kw = dict(n_slots=2, prefill_buckets=(8, 16), steps_per_call=3)
    kw.update(overrides)
    return DecodeEngine(Llama(CFG, mesh), params,
                        EngineConfig(mesh=mesh, **kw))


# ----- mesh planning ---------------------------------------------------------
def test_plan_serve_mesh_defaults():
    p = plan_serve_mesh(8)
    assert p.tensor == 8 and p.fsdp == 1 and p.num_devices == 8
    p2 = plan_serve_mesh(8, tensor=2)
    assert p2.tensor == 2 and p2.data == 4 and p2.num_devices == 8
    with pytest.raises(ValueError, match='tensor'):
        plan_serve_mesh(8, tensor=16)
    with pytest.raises(ValueError, match='tensor'):
        plan_serve_mesh(8, tensor=3)


def test_plan_serve_mesh_gqa_divisibility():
    with pytest.raises(ValueError, match='GQA'):
        plan_serve_mesh(8, tensor=4, n_heads=4, n_kv_heads=2)
    with pytest.raises(ValueError, match='n_heads'):
        validate_tensor_parallel(8, n_heads=4, n_kv_heads=8)
    validate_tensor_parallel(2, n_heads=4, n_kv_heads=2)  # divides: fine


def test_plan_serve_mesh_ignores_num_slices(monkeypatch):
    """plan_mesh defaults dcn from SKYTPU_NUM_SLICES and hard-fails on a
    mismatch; the serve plan is per-slice (the load balancer, not DCN,
    spreads traffic) so it must neither inherit nor trip on it."""
    monkeypatch.setenv('SKYTPU_NUM_SLICES', '3')
    with pytest.raises(ValueError):
        plan_mesh(8)
    p = plan_serve_mesh(8, tensor=2)
    assert p.dcn == 1 and p.tensor == 2


def test_engine_rejects_bad_gqa_mesh():
    """A mesh whose tensor degree does not divide the KV heads must be
    rejected at engine construction, not crash the loop thread."""
    cfg = TINY_GQA                       # 4 q heads over 2 kv heads
    prms = init_params(Llama(cfg), jax.random.PRNGKey(0))['params']
    mesh = build_mesh(MeshPlan(tensor=4), jax.devices()[:4])
    with pytest.raises(ValueError, match='GQA'):
        DecodeEngine(Llama(cfg, mesh), prms,
                     EngineConfig(n_slots=1, mesh=mesh))


# ----- engine parity ---------------------------------------------------------
def test_sharded_engine_matches_single_device(params):
    """Greedy tokens at tensor=2 and tensor=4 must be identical to the
    single-device engine and to the naive full-forward reference,
    including staggered mid-flight admission."""
    p1, p2 = [5, 17, 3, 42, 9], [7, 8, 9, 10, 11, 12]
    want1 = naive_greedy(CFG, params, p1, 8)
    want2 = naive_greedy(CFG, params, p2, 6)

    def run(tensor):
        engine = make_engine(params, tensor)
        r1 = engine.submit(p1, 8)
        for _ in range(2):               # stagger the second admission
            engine.step()
        r2 = engine.submit(p2, 6)
        while r1.finished_at is None or r2.finished_at is None:
            engine.step()
        return [r1.tokens(), r2.tokens()]

    assert run(1) == [want1, want2]
    assert run(2) == [want1, want2]
    assert run(4) == [want1, want2]


def test_sharded_engine_gqa(gqa_params):
    """GQA sharding (2 kv heads over tensor=2: one kv head per chip,
    two q heads attending to it) reproduces single-device greedy."""
    prms = gqa_params
    mesh = build_serve_mesh(2, n_heads=TINY_GQA.n_heads,
                            n_kv_heads=TINY_GQA.n_kv_heads)
    engine = DecodeEngine(Llama(TINY_GQA, mesh), prms,
                          EngineConfig(n_slots=2, prefill_buckets=(8,),
                                       mesh=mesh))
    prompt = [1, 2, 3]
    req = engine.submit(prompt, 6)
    while req.finished_at is None:
        engine.step()
    assert req.tokens() == naive_greedy(TINY_GQA, prms, prompt, 6)


@pytest.mark.parametrize('steps_per_call', [1, 3])
def test_contiguous_engine_gqa_pipelined_matches_naive(gqa_params,
                                                       steps_per_call):
    """The engine path, not only the op, on a GQA shape (4 q heads over
    2 kv heads, one device): prefill, insert and the pipelined decode
    loop with a staggered admission give the naive full-recompute
    greedy tokens."""
    prms = gqa_params
    engine = DecodeEngine(Llama(TINY_GQA), prms,
                          EngineConfig(n_slots=2, prefill_buckets=(8, 16),
                                       steps_per_call=steps_per_call))
    p1, p2 = [1, 2, 3], [7, 8, 9, 10, 11, 12, 13, 14, 15]
    r1 = engine.submit(p1, 9)
    for _ in range(2):
        engine.step_pipelined()
    r2 = engine.submit(p2, 6)
    for _ in range(200):
        engine.step_pipelined()
        if r1.finished_at is not None and r2.finished_at is not None:
            break
    assert r1.tokens() == naive_greedy(TINY_GQA, prms, p1, 9)
    assert r2.tokens() == naive_greedy(TINY_GQA, prms, p2, 6)


def test_sharded_engine_slot_reuse_no_kv_leak(params):
    """A slot reused after retirement must not leak the previous
    request's KV — the insert overwrites each chip's KV-head slice."""
    engine = make_engine(params, 2, n_slots=1, prefill_buckets=(8,))
    first = engine.submit([4, 4, 4, 4, 4, 4, 4, 4], 5)
    while first.finished_at is None:
        engine.step()
    prompt = [9, 1, 9]
    want = naive_greedy(CFG, params, prompt, 5)
    second = engine.submit(prompt, 5)
    while second.finished_at is None:
        engine.step()
    assert second.tokens() == want


def test_sharded_engine_pipelined_loop(params):
    """The pipelined scheduler (what `start()` runs) over the sharded
    programs: backlog through few slots, every request completes with
    exactly its token budget and two runs agree."""
    def run():
        engine = make_engine(params, 2)
        prompts = [[1, 2, 3], [7, 8, 9, 10], [4, 4, 4, 4, 4], [11, 12]]
        lens = [10, 6, 5, 7]
        reqs = [engine.submit(p, n) for p, n in zip(prompts, lens)]
        for _ in range(200):
            engine.step_pipelined()
            if all(r.finished_at is not None for r in reqs):
                break
        return [r.tokens() for r in reqs], lens

    toks, lens = run()
    for got, n in zip(toks, lens):
        assert len(got) == n
    assert run()[0] == toks


def test_sharded_engine_zero_recompiles(params):
    """The engine's core invariant must hold for sharded programs: all
    engine state is committed to fixed NamedShardings at init, so
    admit/decode/retire traffic never adds a compiled-call cache entry
    once each shape has been seen."""
    engine = make_engine(params, 2)
    engine.prewarm()             # mesh path: executes every shape
    decode_size = engine._decode._cache_size()
    prefill_size = engine._prefill_insert._cache_size()
    assert decode_size == 1
    # 2 buckets x padded group sizes {1, 2} = 4 admission shapes.
    assert prefill_size == 4

    def traffic():
        reqs = [engine.submit([9, 1, 9], 5),       # 2-burst: padded N=2
                engine.submit([2, 4, 6, 8], 4)]
        for _ in range(200):
            engine.step_pipelined()
            if all(r.finished_at is not None for r in reqs):
                break
        single = engine.submit([1, 2, 3], 2)       # solo admit: N=1
        while single.finished_at is None:
            engine.step()
        engine.drain()

    traffic()
    assert engine._decode._cache_size() == decode_size
    assert engine._prefill_insert._cache_size() == prefill_size


def test_sharded_update_params_preserves_shardings(params):
    """update_params with a HOST tree (the RL loop's case) must land the
    new weights in the same NamedShardings — no recompile, actually
    partitioned — and serve them."""
    import flax.linen as nn
    engine = make_engine(params, 2)
    req = engine.submit([5, 17, 3], 4)
    while req.finished_at is None:
        engine.step()
    req.tokens()
    engine.drain()
    size0 = engine._decode._cache_size()
    host = jax.tree.map(np.asarray,
                        jax.device_get(nn.meta.unbox(params)))
    host = jax.tree.map(lambda x: x * 1.01 if x.dtype == np.float32 else x,
                        host)
    engine.update_params(host)
    kernel = engine.params['layer_0']['attn']['q_proj']['kernel']
    assert len(kernel.sharding.device_set) == 2
    assert kernel.addressable_shards[0].data.shape[1] == CFG.n_heads // 2
    want = naive_greedy(CFG, host, [5, 17, 3], 4)
    req2 = engine.submit([5, 17, 3], 4)
    while req2.finished_at is None:
        engine.step()
    assert req2.tokens() == want
    assert engine._decode._cache_size() == size0   # no recompile


def test_sharded_engine_rl_rollout(params):
    """train/rl.py's rollout must run against a tensor-parallel engine
    unmodified (sampling at temperature > 0)."""
    from skypilot_tpu.train import rl
    mesh = build_serve_mesh(2, n_heads=CFG.n_heads,
                            n_kv_heads=CFG.n_kv_heads)
    engine = DecodeEngine(
        Llama(CFG, mesh), params,
        EngineConfig(n_slots=2, prefill_buckets=(8,), steps_per_call=3,
                     temperature=0.7, seed=1, mesh=mesh))
    tokens, adv, prompt_lens, total_lens = rl.rollout(
        engine, [[1, 2, 3], [7, 8, 9]], 4, lambda p, s: float(len(s)))
    assert tokens.shape[0] == 2 and adv.shape == (2,)
    assert (total_lens - prompt_lens).max() <= 4
    assert np.isfinite(tokens).all()


def test_load_serving_params_sharded(params, tmp_path):
    """Shard-on-load: leaves restored from an orbax checkpoint land
    directly in their mesh placement (never a full single-device tree),
    and the engine serves them with single-device-identical tokens."""
    import flax.linen as nn

    from skypilot_tpu.inference.weights import (load_serving_params,
                                                serving_shardings)
    from skypilot_tpu.train.checkpoint import CheckpointManager

    host = jax.tree.map(np.asarray,
                        jax.device_get(nn.meta.unbox(params)))
    mgr = CheckpointManager(str(tmp_path / 'ckpt'))
    mgr.save(0, host, wait=True)
    mgr.close()

    mesh = build_serve_mesh(2, n_heads=CFG.n_heads,
                            n_kv_heads=CFG.n_kv_heads)
    shardings = serving_shardings(Llama(CFG, mesh), mesh)
    restored = load_serving_params(str(tmp_path / 'ckpt'),
                                   shardings=shardings)
    kernel = restored['layer_0']['attn']['q_proj']['kernel']
    assert len(kernel.sharding.device_set) == 2
    assert kernel.addressable_shards[0].data.shape[1] == CFG.n_heads // 2
    for got, want in zip(jax.tree.leaves(restored),
                         jax.tree.leaves(host), strict=True):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))

    engine = DecodeEngine(Llama(CFG, mesh), restored,
                          EngineConfig(n_slots=1, prefill_buckets=(8,),
                                       mesh=mesh))
    prompt = [5, 17, 3]
    req = engine.submit(prompt, 4)
    while req.finished_at is None:
        engine.step()
    assert req.tokens() == naive_greedy(CFG, host, prompt, 4)


def test_service_spec_tensor_parallel_roundtrip():
    from skypilot_tpu.serve.service_spec import ServiceSpec
    spec = ServiceSpec.from_yaml_config({
        'readiness_probe': '/health',
        'replicas': 2,
        'tensor_parallel': 4,
    })
    assert spec.tensor_parallel == 4
    out = spec.to_yaml_config()
    assert out['tensor_parallel'] == 4
    again = ServiceSpec.from_yaml_config(out)
    assert again.tensor_parallel == 4
    # Default stays 1 and is omitted from the round trip.
    plain = ServiceSpec.from_yaml_config({'readiness_probe': '/'})
    assert plain.tensor_parallel == 1
    assert 'tensor_parallel' not in plain.to_yaml_config()
