"""Attention op tests: pallas kernel (interpret mode) and ring attention
against the XLA reference. Runs on the 8-device virtual CPU mesh."""
import functools

import jax
import jax.numpy as jnp
import pytest

from skypilot_tpu.ops.attention import flash_attention, mha_reference
from skypilot_tpu.ops.pallas import flash_attention as pallas_fa
from skypilot_tpu.ops.pallas.flash_attention import (flash_attention_bwd,
                                                     flash_attention_fwd)
from skypilot_tpu.parallel.mesh import build_mesh, plan_mesh
from skypilot_tpu.parallel.ring_attention import ring_attention


def _qkv(b=2, h=4, s=256, d=64, hkv=None, dtype=jnp.float32, seed=0):
    key = jax.random.PRNGKey(seed)
    kq, kk, kv = jax.random.split(key, 3)
    hkv = hkv or h
    return (jax.random.normal(kq, (b, h, s, d), dtype),
            jax.random.normal(kk, (b, hkv, s, d), dtype),
            jax.random.normal(kv, (b, hkv, s, d), dtype))


@pytest.mark.parametrize('causal', [True, False])
def test_pallas_flash_matches_reference(causal):
    q, k, v = _qkv()
    ref = mha_reference(q, k, v, causal=causal)
    out = flash_attention_fwd(q, k, v, causal=causal, block_size=128,
                              interpret=True)
    assert jnp.max(jnp.abs(ref - out)) < 5e-3  # interpret-mode MXU numerics


def test_pallas_flash_gqa():
    q, k, v = _qkv(h=4, hkv=2)
    ref = mha_reference(q, k, v, causal=True)
    out = flash_attention_fwd(q, k, v, causal=True, block_size=128,
                              interpret=True)
    assert jnp.max(jnp.abs(ref - out)) < 5e-3


def test_flash_attention_dispatch_cpu_and_grad():
    # On CPU the public entry point uses the XLA path; grads flow.
    q, k, v = _qkv(s=128)
    out = flash_attention(q, k, v, True)
    ref = mha_reference(q, k, v, causal=True)
    assert jnp.allclose(out, ref, atol=1e-5)
    g = jax.grad(lambda q: flash_attention(q, k, v, True).sum())(q)
    g_ref = jax.grad(lambda q: mha_reference(q, k, v, causal=True).sum())(q)
    assert jnp.allclose(g, g_ref, atol=1e-4)


def _bwd_against_reference(q, k, v, g, causal, backward=flash_attention_bwd):
    """(dq, dk, dv) of `backward` at blocks of 128, each held to the XLA
    reference's."""
    out, lse = flash_attention_fwd(q, k, v, causal=causal, block_size=128,
                                   interpret=True, return_residuals=True)
    grads = backward(q, k, v, out, lse, g, causal=causal, block_size=128,
                     interpret=True)
    ref_out, vjp = jax.vjp(
        lambda q_, k_, v_: mha_reference(q_, k_, v_, causal=causal), q, k, v)
    assert jnp.max(jnp.abs(out - ref_out)) < 5e-3
    for got, ref in zip(grads, vjp(g)):
        assert got.shape == ref.shape
        assert jnp.max(jnp.abs(got - ref)) < 5e-3
    return grads


def _pallas_calls(fn, *args, **kwargs) -> int:
    return str(jax.make_jaxpr(functools.partial(fn, **kwargs))(*args)).count(
        'pallas_call')


# 2 x 2 blocks, 3 x 3 (a triangle of six tiles, S not a power of two
# times the block) and one block, whose only tile is the diagonal's.
@pytest.mark.parametrize('s', [256, 384, 128])
@pytest.mark.parametrize('causal', [True, False])
def test_pallas_flash_bwd_matches_reference(causal, s):
    q, k, v = _qkv(b=1, h=2, s=s, d=64)
    g = jax.random.normal(jax.random.PRNGKey(7), q.shape, q.dtype)
    _bwd_against_reference(q, k, v, g, causal)


def test_pallas_flash_bwd_gqa_group_reduce():
    # flash_attention_bwd owns the GQA repeat AND the matching group
    # reduction — grads must come back at Hkv heads and match the
    # reference (the production _flash_bwd delegates to exactly this),
    # through the one kernel.
    q, k, v = _qkv(b=1, h=4, hkv=2, s=256, d=64)
    g = jnp.ones_like(q)
    _, dk, dv = _bwd_against_reference(q, k, v, g, True)
    assert dk.shape == k.shape and dv.shape == v.shape
    assert _pallas_calls(flash_attention_bwd.__wrapped__, q, k, v, q,
                         q[..., 0], g, block_size=128, interpret=True) == 1


@pytest.mark.parametrize('causal', [True, False])
def test_pallas_flash_bwd_falls_back_where_a_pairs_dq_does_not_fit(
        monkeypatch, causal):
    """The choice is the count's: with less VMEM than a pair's float32 dq
    (S x D x 4) the backward is the two kernels it was, and both paths
    agree with the reference and with each other."""
    s, d = 384, 64
    q, k, v = _qkv(b=1, h=2, s=s, d=d)
    g = jax.random.normal(jax.random.PRNGKey(7), q.shape, q.dtype)
    assert pallas_fa.fused_bwd_vmem_bytes(s, d, 128, 128, 4) > s * d * 4
    # (Unjitted: a cached trace would not read the budget again.)
    backward = flash_attention_bwd.__wrapped__
    kwargs = dict(causal=causal, block_size=128, interpret=True)
    assert _pallas_calls(backward, q, k, v, q, q[..., 0], g, **kwargs) == 1
    one = _bwd_against_reference(q, k, v, g, causal, backward)
    monkeypatch.setattr(pallas_fa, '_FUSED_BWD_VMEM_BUDGET', s * d * 4 - 1)
    assert _pallas_calls(backward, q, k, v, q, q[..., 0], g, **kwargs) == 2
    two = _bwd_against_reference(q, k, v, g, causal, backward)
    for a, b in zip(one, two):
        assert jnp.max(jnp.abs(a - b)) < 1e-3


def _repeat_attention(q, k, v, q_pos, k_pos):
    """The independent reference for grouped-query attention: every KV
    head repeated to its query heads, float32 throughout, the softmax
    written out so that a fully masked row comes out as zeros."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    logits = jnp.einsum('bhqd,bhkd->bhqk', q, k,
                        precision='highest') * q.shape[-1]**-0.5
    mask = q_pos[:, None, :, None] >= k_pos[:, None, None, :]
    top = jnp.max(jnp.where(mask, logits, -1e30), axis=-1, keepdims=True)
    w = jnp.where(mask, jnp.exp(jnp.where(mask, logits - top, 0.0)), 0.0)
    total = jnp.sum(w, axis=-1, keepdims=True)
    probs = w / jnp.where(total == 0.0, 1.0, total)
    return jnp.einsum('bhqk,bhkd->bhqd', probs, v, precision='highest')


def _gqa_case(case, s_k):
    """Three rows: (the keyword arguments mha_reference gets, q_pos,
    k_pos)."""
    k_pos = jnp.broadcast_to(jnp.arange(s_k)[None, :], (3, s_k))
    if case == 'prefill':          # Sq == Sk, causal from the shapes
        return {}, k_pos, k_pos
    if case == 'decode':           # one query a row, each at its own place
        q_pos = jnp.array([[3], [s_k - 1], [0]])
    elif case == 'chunk':          # a chunk of 4 at a per-row offset
        q_pos = jnp.array([[5], [0], [s_k - 4]]) + jnp.arange(4)[None, :]
    elif case == 'masked_row':     # row 1 sits before every key
        q_pos = jnp.array([[2, 3], [-2, -1], [6, 7]])
    else:
        raise ValueError(case)
    return dict(segment_positions=q_pos, kv_positions=k_pos), q_pos, k_pos


@pytest.mark.parametrize('direction', ['forward', 'grad'])
@pytest.mark.parametrize('case', ['decode', 'prefill', 'chunk', 'masked_row'])
@pytest.mark.parametrize('group', [1, 2, 8])
def test_mha_reference_grouped_matches_repeat(group, case, direction):
    """Grouped-query attention contracted over the KV heads as stored is
    the repeat formulation: same values, same gradients, for every way the
    model calls it."""
    b, h_kv, s_k, d = 3, 2, 16, 8
    kwargs, q_pos, k_pos = _gqa_case(case, s_k)
    s_q = q_pos.shape[1]
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(group), 4)
    q = jax.random.normal(kq, (b, h_kv * group, s_q, d), jnp.float32)
    k = jax.random.normal(kk, (b, h_kv, s_k, d), jnp.float32)
    v = jax.random.normal(kv, (b, h_kv, s_k, d), jnp.float32)
    got_fn = lambda q_, k_, v_: mha_reference(  # noqa: E731
        q_, k_, v_, causal=True, **kwargs)
    want_fn = lambda q_, k_, v_: _repeat_attention(  # noqa: E731
        q_, k_, v_, q_pos, k_pos)
    if direction == 'forward':
        got, want = jax.jit(got_fn)(q, k, v), jax.jit(want_fn)(q, k, v)
        assert got.shape == (b, h_kv * group, s_q, d)
        assert jnp.allclose(got, want, atol=2e-5), \
            float(jnp.max(jnp.abs(got - want)))
        if case == 'masked_row':
            assert not jnp.any(got[1])
        return
    if case == 'masked_row':
        # softmax's gradient through a row of -inf is NaN whatever the
        # head layout; the rows that see a key are what can be held.
        q, q_pos = q[::2], q_pos[::2]
        k, v, k_pos = k[::2], v[::2], k_pos[::2]
        kwargs = dict(segment_positions=q_pos, kv_positions=k_pos)
    w = jax.random.normal(kw, (q.shape[0], h_kv * group, s_q, d))
    loss = lambda fn: lambda *a: jnp.sum(fn(*a) * w)  # noqa: E731
    got = jax.jit(jax.grad(loss(got_fn), argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(loss(want_fn), argnums=(0, 1, 2)))(q, k, v)
    for g_, w_, name in zip(got, want, 'qkv'):
        assert g_.shape == w_.shape
        assert jnp.allclose(g_, w_, atol=5e-5), \
            (name, float(jnp.max(jnp.abs(g_ - w_))))


def _shapes_in(jaxpr):
    """Every intermediate's shape, through the nested jaxprs."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield tuple(getattr(var.aval, 'shape', ()))
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple))
                        else [param]):
                sub = getattr(sub, 'jaxpr', sub)
                if hasattr(sub, 'eqns'):
                    yield from _shapes_in(sub)


def test_gqa_decode_step_builds_no_expanded_kv():
    """A decode step of a GQA model holds its cache as [B, Hkv, S, D] and
    nothing of the cache's length with all the query heads."""
    from skypilot_tpu.models.llama import LLAMA_CONFIGS, Llama, init_params
    cfg = LLAMA_CONFIGS['tiny']
    assert cfg.n_heads > cfg.n_kv_heads
    model, b = Llama(cfg), 2
    variables = jax.eval_shape(
        lambda: init_params(model, jax.random.PRNGKey(0)))
    prompt = jnp.zeros((b, 4), jnp.int32)
    _, state = jax.eval_shape(
        lambda p: model.apply({'params': p}, prompt, decode=True,
                              mutable=['cache']), variables['params'])

    def step(params, cache, tokens, positions):
        return model.apply({'params': params, 'cache': cache}, tokens,
                           positions=positions, decode=True,
                           mutable=['cache'])

    jaxpr = jax.make_jaxpr(step)(
        variables['params'], state['cache'],
        jnp.zeros((b, 1), jnp.int32), jnp.full((b, 1), 4, jnp.int32))
    shapes = set(_shapes_in(jaxpr.jaxpr))
    stored = (b, cfg.n_kv_heads, cfg.max_seq_len, cfg.head_dim)
    expanded = (b, cfg.n_heads, cfg.max_seq_len, cfg.head_dim)
    assert stored in shapes         # the walk does reach the attention
    assert expanded not in shapes


def test_mha_reference_ungrouped_trace_unchanged():
    """With as many KV heads as query heads the op traces to the plain
    expression, letter for letter: no group axis of size 1 slips in."""
    def plain(q, k, v, q_pos, k_pos):
        logits = jnp.einsum('bhqd,bhkd->bhqk', q, k,
                            preferred_element_type=jnp.float32
                            ) * q.shape[-1]**-0.5
        mask = q_pos[:, None, :, None] >= k_pos[:, None, None, :]
        logits = jnp.where(mask, logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        probs = jnp.where(jnp.isnan(probs), 0.0, probs)
        out = jnp.einsum('bhqk,bhkd->bhqd', probs.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        return out.astype(q.dtype)

    def op(q, k, v, q_pos, k_pos):
        return mha_reference(q, k, v, causal=True,
                             segment_positions=q_pos, kv_positions=k_pos)

    q, k, v = _qkv(b=2, h=4, s=16, d=8, dtype=jnp.bfloat16)
    q = q[:, :, :1]
    q_pos = jnp.array([[3], [9]])
    k_pos = jnp.broadcast_to(jnp.arange(16)[None, :], (2, 16))
    args = (q, k, v, q_pos, k_pos)
    assert str(jax.make_jaxpr(op)(*args)) == str(jax.make_jaxpr(plain)(*args))


@pytest.mark.parametrize('causal', [True, False])
def test_ring_attention_exact(causal):
    mesh = build_mesh(plan_mesh(8, data=1, fsdp=8, tensor=1))
    q, k, v = _qkv(s=512)
    ref = mha_reference(q, k, v, causal=causal)
    out = ring_attention(q, k, v, mesh=mesh, causal=causal)
    assert jnp.max(jnp.abs(ref - out)) < 1e-5


def test_ring_attention_gqa_with_tensor_axis():
    mesh = build_mesh(plan_mesh(8, data=1, fsdp=4, tensor=2))
    q, k, v = _qkv(h=4, hkv=2, s=256)
    ref = mha_reference(q, k, v, causal=True)
    out = ring_attention(q, k, v, mesh=mesh, causal=True)
    assert jnp.max(jnp.abs(ref - out)) < 1e-5


def test_ring_attention_grad():
    mesh = build_mesh(plan_mesh(8, data=1, fsdp=8, tensor=1))
    q, k, v = _qkv(s=256)
    g = jax.grad(
        lambda q: ring_attention(q, k, v, mesh=mesh, causal=True).sum())(q)
    g_ref = jax.grad(lambda q: mha_reference(q, k, v, causal=True).sum())(q)
    assert jnp.max(jnp.abs(g - g_ref)) < 1e-4


# ----- the real kernels, compiled for a described (not attached) v5e ---------
# Interpret mode cannot see what the TPU compiler refuses (tiling, VMEM).
# The main-path shapes [B, Hq, Hkv, S, D]: bench-1b training at 4k and 8k,
# what model.init traces for llama2-7b, and `pretrain-4k`'s own (MHA).
_MAIN_PATH_SHAPES = [(4, 16, 8, 4096, 128), (2, 16, 8, 8192, 128),
                     (1, 32, 32, 256, 128), (4, 16, 16, 4096, 128)]


@pytest.fixture(scope='module')
def v5e_chip():
    """One device of a described v5e:2x2, with the persistent compile
    cache off: such a compile can be written to it but not read back."""
    from skypilot_tpu.parallel import validate as validate_lib
    try:
        topo = validate_lib.topology_for('tpu-v5e-4')
    except Exception:  # pylint: disable=broad-except
        pytest.skip('no libtpu topology support in this environment')
    from skypilot_tpu.utils import compile_cache
    with compile_cache.bypassed():
        yield jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize('direction', ['fwd', 'bwd'])
@pytest.mark.parametrize('shape', _MAIN_PATH_SHAPES,
                         ids=lambda s: 'x'.join(map(str, s)))
def test_pallas_flash_compiles_for_v5e(v5e_chip, shape, direction):
    b, hq, hkv, s, d = shape

    def sds(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=v5e_chip)

    q, kv = sds(b, hq, s, d), sds(b, hkv, s, d)
    if direction == 'fwd':
        lowered = flash_attention_fwd.lower(q, kv, kv, causal=True,
                                            return_residuals=True)
    else:
        lse = sds(b, hq, s, dtype=jnp.float32)
        lowered = flash_attention_bwd.lower(q, kv, kv, q, lse, q,
                                            causal=True)
    calls = [line for line in lowered.compile().as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls
    if direction == 'bwd':
        # A pair's dq fits at every one of these by the count: each tile
        # is visited by one kernel, which takes lse and delta as rows and
        # no [B x H, S, 128] float32 plane of either.
        block = min(512, s)
        assert pallas_fa.fused_bwd_vmem_bytes(
            s, d, block, block, 2) <= pallas_fa._FUSED_BWD_VMEM_BUDGET
        assert len(calls) == 1
        operands = calls[0].split('operand_layout_constraints={')[1]
        assert f'f32[{b * hq},1,{s}]' in operands
        assert f'f32[{b * hq},{s},128]' not in operands


# The decode step's attention at the serving cells' shapes [B, Hq, Hkv, S]:
# Yi-Coder (MHA), Yi-6B (8 query heads a KV head), Solar-Open2's softmax
# layer, whose 1,664 positions take the smallest block, and ZAYA1's
# compressed latent: 2 KV heads under 8 query heads over 13,312 positions.
_DECODE_SHAPES = [(16, 16, 16, 1024), (8, 32, 4, 1024), (32, 64, 8, 1664),
                  (16, 8, 2, 13312)]


@pytest.mark.parametrize('shape', _DECODE_SHAPES,
                         ids=lambda s: 'x'.join(map(str, s)))
def test_pallas_decode_attention_compiles_for_v5e(v5e_chip, shape):
    from skypilot_tpu.ops.pallas import decode_attention as pallas_da
    b, hq, hkv, s = shape

    def sds(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=v5e_chip)

    cache = sds(b, hkv, s, 128)
    compiled = pallas_da.decode_attention_fwd.lower(
        sds(b, hq, 1, 128), cache, cache, sds(b, dtype=jnp.int32)).compile()
    assert 'tpu_custom_call' in compiled.as_text()


def test_decode_program_keeps_the_cache_as_the_kernel_reads_it(
        v5e_chip, monkeypatch):
    """The engine's decode program, two layers at Yi-Coder's widths with
    the kernel in it, compiled with the layouts left to the compiler as
    `_optimize_layouts` leaves them: the cache comes out row-major
    [B, Hkv, S, D] and no temporary is as large as a cache leaf.  (With
    the row written by a scatter whose window is a position's heads the
    compiler laid the cache out position-major and copied every leaf in
    front of every kernel call.)  The kernel's scalar operands (the
    lengths and where an empty slot looks) are made once a step: every
    layer's call takes the same three."""
    import re
    import flax.linen as nn
    from jax.experimental.layout import Format, Layout
    from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig
    from skypilot_tpu.models.llama import Llama, LlamaConfig
    from skypilot_tpu.ops import attention as attn_lib
    from skypilot_tpu.ops.pallas import decode_attention as pallas_da

    # `jax.default_backend()` is the CPU here: steer the choice itself.
    monkeypatch.setattr(
        attn_lib, 'decode_kv_block',
        lambda h, d, s, dtype=jnp.bfloat16, mesh=None: pallas_da.block_len(
            h, d, s, jnp.dtype(dtype).itemsize))
    cfg = LlamaConfig(vocab_size=64000, dim=2048, n_layers=2, n_heads=16,
                      n_kv_heads=16, ffn_dim=5504,
                      max_seq_len=1024, dtype=jnp.bfloat16,
                      param_dtype=jnp.bfloat16)
    model = Llama(cfg)
    params = nn.meta.unbox(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))['params']))
    engine = DecodeEngine(model, params, EngineConfig(
        n_slots=4, steps_per_call=8, prefill_buckets=(128,)))
    auto = Format(Layout.AUTO, v5e_chip)

    def autos(tree):
        return jax.tree.map(lambda _: auto, tree)

    def shapes(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=v5e_chip), tree)

    lens = shapes(engine._lens_d)
    compiled = jax.jit(
        engine._decode_raw, donate_argnums=(1, 2, 3),
        in_shardings=(autos(params), autos(engine._cache), auto, auto, auto,
                      auto),
        out_shardings=(auto, autos(engine._cache), auto, auto)).lower(
            shapes(params), shapes(engine._cache), shapes(engine._last_d),
            lens, lens, shapes(engine._rng)).compile()
    text = compiled.as_text()
    calls = re.findall(r' custom-call\(([^)]*)\), '
                       r'custom_call_target="tpu_custom_call"', text)
    assert len(calls) == cfg.n_layers
    scalars = {tuple(call.split(', ')[:3]) for call in calls}
    assert len(scalars) == 1 and len(set(scalars.pop())) == 3
    formats, _ = compiled.input_formats
    leaf = jax.tree.leaves(engine._cache)[0]
    for fmt in jax.tree.leaves(formats[1]):
        assert fmt.layout.major_to_minor == (0, 1, 2, 3)
    assert compiled.memory_analysis().temp_size_in_bytes < leaf.nbytes
    shape = ','.join(map(str, leaf.shape))
    assert f'bf16[{shape}]' in text
    assert not re.search(rf'= bf16\[{shape}\]\S* (copy|transpose)\(', text)


def test_decode_program_keeps_the_expert_stacks_as_the_kernel_reads_them(
        v5e_chip, monkeypatch):
    """Solar-Open2's expert layer at the cell's widths (32 tokens, 40 held
    experts of 4096 x 1280), decode-shaped with the grouped kernel in it
    and every layout left to the compiler as `_optimize_layouts` leaves
    them: the stacks stay row-major as they are made (what the prefill's
    block loop reads too) and no temporary is as large as one stack."""
    from jax.experimental.layout import Format, Layout
    from skypilot_tpu.models import moe as moe_lib
    from skypilot_tpu.ops.pallas import grouped_experts as pallas_ge

    # `jax.default_backend()` is the CPU here: steer the choice itself.
    monkeypatch.setattr(
        moe_lib, 'expert_tile',
        lambda n_tokens, block, w_gate, mesh=None: pallas_ge.tile_f(
            w_gate.shape[1], w_gate.shape[2], w_gate.dtype.itemsize))
    layer = moe_lib.DroplessMoE(
        dim=4096, ffn_dim=1280, n_experts=320, held=tuple(range(40)),
        router=moe_lib.LinearRouter(top_k=8), dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((32, 1, 4096), jnp.bfloat16, sharding=v5e_chip)
    params = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0),
                           jnp.zeros(x.shape, x.dtype))['params'])
    auto = Format(Layout.AUTO, v5e_chip)
    compiled = jax.jit(
        lambda params, x: layer.apply({'params': params}, x),
        in_shardings=(jax.tree.map(lambda _: auto, params), auto),
        out_shardings=auto).lower(
            jax.tree.map(lambda p: jax.ShapeDtypeStruct(
                p.shape, p.dtype, sharding=v5e_chip), params), x).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1
    formats, _ = compiled.input_formats
    for name in ('w_gate', 'w_up', 'w_down'):
        assert formats[0][name].layout.major_to_minor == (0, 1, 2)
    assert compiled.memory_analysis().temp_size_in_bytes < \
        params['w_gate'].size * 2


def test_decode_program_keeps_the_kda_state_as_the_kernel_reads_it(
        v5e_chip, monkeypatch):
    """Solar-Open2's Kimi Delta Attention layer at the cell's shapes (32
    slots, 64 heads of 128 x 128 float32), decode-shaped with the state
    kernel in it, the cache donated and every layout left to the compiler
    as `_optimize_layouts` leaves them: one Mosaic call, the state leaf
    row-major in and out, the new state in the buffer of the old, and no
    temporary as large as the leaf (134 MB): no copy of it anywhere."""
    import re
    from jax.experimental.layout import Format, Layout
    from skypilot_tpu.models import solar_open2 as solar_lib
    from skypilot_tpu.ops.pallas import delta_rule_step as pallas_dr

    # `jax.default_backend()` is the CPU here: steer the choice itself.
    monkeypatch.setattr(
        solar_lib, 'kda_step_heads',
        lambda state, positions, mesh=None: pallas_dr.block_heads(
            *state.shape[1:]))
    layer = solar_lib.KimiDeltaAttention(solar_lib.SolarOpen2Config(
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16))
    x = jnp.zeros((32, 1, 4096), jnp.bfloat16)
    variables = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), x, True, None))
    params, cache = variables['params'], variables['cache']
    assert cache['state'].shape == (32, 64, 128, 128)
    auto = Format(Layout.AUTO, v5e_chip)

    def autos(tree):
        return jax.tree.map(lambda _: auto, tree)

    def shapes(tree):
        return jax.tree.map(lambda t: jax.ShapeDtypeStruct(
            t.shape, t.dtype, sharding=v5e_chip), tree)

    def step(params, cache, x):
        out, new = layer.apply({'params': params, 'cache': cache}, x, True,
                               None, mutable=['cache'])
        return out, new['cache']

    compiled = jax.jit(
        step, donate_argnums=(1,),
        in_shardings=(autos(params), autos(cache), auto),
        out_shardings=(auto, autos(cache))).lower(
            shapes(params), shapes(cache), shapes(x)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert 'kda_state_update' in text
    formats_in, _ = compiled.input_formats
    assert formats_in[1]['state'].layout.major_to_minor == (0, 1, 2, 3)
    assert compiled.output_formats[1]['state'].layout.major_to_minor == \
        (0, 1, 2, 3)
    leaf = cache['state'].size * 4
    assert compiled.memory_analysis().temp_size_in_bytes < leaf
    assert compiled.memory_analysis().alias_size_in_bytes >= leaf
    assert not re.search(r'= f32\[32,64,128,128\]\S* (copy|transpose)\(',
                         text)


def test_pallas_latent_decode_attention_compiles_for_v5e(v5e_chip):
    """The latent decode kernel at openPangu-Ultra-MoE's cell: 32 slots of
    4,736 positions (four tiles of 1,024 and a ragged fifth), 128 heads
    against a latent of 512 + 64."""
    from skypilot_tpu.ops.pallas import latent_decode_attention as pallas_la

    def sds(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=v5e_chip)

    compiled = pallas_la.latent_decode_attention_fwd.lower(
        sds(32, 128, 512), sds(32, 128, 64), sds(32, 4736, 512),
        sds(32, 4736, 64), sds(32, dtype=jnp.int32)).compile()
    assert 'tpu_custom_call' in compiled.as_text()


def test_pallas_flash_takes_values_narrower_than_keys_on_v5e(v5e_chip):
    """Latent attention's prefill: keys of 128 + 64, values of 128."""
    def sds(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=v5e_chip)

    compiled = flash_attention_fwd.lower(
        sds(1, 128, 4096, 192), sds(1, 128, 4096, 192),
        sds(1, 128, 4096, 128), causal=True).compile()
    assert 'tpu_custom_call' in compiled.as_text()
    assert 'bf16[128,4096,128]' in compiled.as_text()


def test_decode_program_keeps_the_latent_cache_as_the_kernel_reads_it(
        v5e_chip, monkeypatch):
    """The engine's decode program of openPangu-Ultra-MoE at the cell's
    widths (a dense and an expert layer, 8 slots of 4,736 positions) with
    both decode kernels in it and every layout left to the compiler as
    `_optimize_layouts` leaves them: the latent comes out row-major
    [B, S, width] as the kernel reads it, nothing makes a copy of a cache
    leaf, and no temporary is as large as one."""
    import re
    from jax.experimental.layout import Format, Layout
    from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig
    from skypilot_tpu.models import moe as moe_lib
    from skypilot_tpu.models.openpangu_moe import (OpenPanguMoE,
                                                   OpenPanguMoEConfig)
    from skypilot_tpu.ops import attention as attn_lib
    from skypilot_tpu.ops.pallas import grouped_experts as pallas_ge
    from skypilot_tpu.ops.pallas import latent_decode_attention as pallas_la

    # `jax.default_backend()` is the CPU here: steer the choices themselves.
    monkeypatch.setattr(
        attn_lib, 'latent_kv_block',
        lambda c_dim, s, mesh=None: pallas_la.block_len(c_dim, s))
    monkeypatch.setattr(
        moe_lib, 'expert_tile',
        lambda n_tokens, block, w_gate, mesh=None: pallas_ge.tile_f(
            w_gate.shape[1], w_gate.shape[2], w_gate.dtype.itemsize))
    cfg = OpenPanguMoEConfig(
        vocab_size=19200, n_layers=2, n_dense_layers=1,
        held_experts=tuple(range(16)), max_seq_len=4736,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    model = OpenPanguMoE(cfg)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))['params'])
    engine = DecodeEngine(model, params, EngineConfig(
        n_slots=8, steps_per_call=8, prefill_buckets=(128,)))
    auto = Format(Layout.AUTO, v5e_chip)

    def autos(tree):
        return jax.tree.map(lambda _: auto, tree)

    def shapes(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=v5e_chip), tree)

    lens = shapes(engine._lens_d)
    compiled = jax.jit(
        engine._decode_raw, donate_argnums=(1, 2, 3),
        in_shardings=(autos(params), autos(engine._cache), auto, auto, auto,
                      auto),
        out_shardings=(auto, autos(engine._cache), auto, auto)).lower(
            shapes(params), shapes(engine._cache), shapes(engine._last_d),
            lens, lens, shapes(engine._rng)).compile()
    text = compiled.as_text()
    # A latent kernel a layer, the expert kernel in the expert layer.
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert not re.search(r'= bf16\[8,4736,(512|64)\]\S* (copy|transpose)\(',
                         text)
    formats, _ = compiled.input_formats
    for fmt in jax.tree.leaves(formats[1]):
        assert fmt.layout.major_to_minor == (0, 1, 2)
    small = min(leaf.nbytes for leaf in jax.tree.leaves(engine._cache))
    assert compiled.memory_analysis().temp_size_in_bytes < small


def test_decode_program_of_blocks_keeps_the_cache_as_the_kernel_reads_it(
        v5e_chip, monkeypatch):
    """The engine's decode program for generation by blocks (SDAR-MoE at
    the cell's widths: two layers, 8 slots of 1,408 positions, passes of 4
    rows a slot) with both decode kernels in it and every layout left to
    the compiler as `_optimize_layouts` leaves them: the cache comes out
    row-major [B, Hkv, S, D] as the kernel reads it (a block's 4 rows are
    written as rows of D over (slot x head, position)), nothing makes a
    copy of a cache leaf or of an expert stack, and no temporary is as
    large as a cache leaf.  The prefill under the mask by blocks compiles
    with the flash kernel in it."""
    import re
    from jax.experimental.layout import Format, Layout
    from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig
    from skypilot_tpu.models import moe as moe_lib
    from skypilot_tpu.models.sdar_moe import SDARMoE, SDARMoEConfig
    from skypilot_tpu.ops import attention as attn_lib
    from skypilot_tpu.ops.pallas import decode_attention as pallas_da
    from skypilot_tpu.ops.pallas import flash_attention as pallas_fa
    from skypilot_tpu.ops.pallas import grouped_experts as pallas_ge

    # `jax.default_backend()` is the CPU here: steer the choices themselves.
    monkeypatch.setattr(
        attn_lib, 'decode_kv_block',
        lambda h, d, s, dtype=jnp.bfloat16, mesh=None: pallas_da.block_len(
            h, d, s, jnp.dtype(dtype).itemsize))
    monkeypatch.setattr(
        moe_lib, 'expert_tile',
        lambda n_tokens, block, w_gate, mesh=None: None
        if n_tokens > block else pallas_ge.tile_f(
            w_gate.shape[1], w_gate.shape[2], w_gate.dtype.itemsize))
    monkeypatch.setattr(
        attn_lib, '_flash_fwd_impl',
        lambda q, k, v, causal, block_size, mask_block=1:
        pallas_fa.flash_attention_fwd(q, k, v, causal=causal,
                                      block_size=block_size,
                                      mask_block=mask_block))
    cfg = SDARMoEConfig(vocab_size=8192, n_layers=2, max_seq_len=1408,
                        remasking='sequential', dtype=jnp.bfloat16,
                        param_dtype=jnp.bfloat16)
    model = SDARMoE(cfg)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))['params'])
    engine = DecodeEngine(model, params, EngineConfig(
        n_slots=8, steps_per_call=5, prefill_buckets=(1024,)))
    auto = Format(Layout.AUTO, v5e_chip)

    def autos(tree):
        return jax.tree.map(lambda _: auto, tree)

    def shapes(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=v5e_chip), tree)

    lens = shapes(engine._lens_d)
    state = (shapes(params), shapes(engine._cache), shapes(engine._last_d),
             lens)
    compiled = jax.jit(
        engine._decode_raw, donate_argnums=(1, 2, 3),
        in_shardings=(autos(params), autos(engine._cache),
                      autos(engine._last_d), auto, auto, auto),
        out_shardings=(auto, autos(engine._cache), autos(engine._last_d),
                       auto)).lower(
                           *state, lens, shapes(engine._rng)).compile()
    text = compiled.as_text()
    # A decode-attention kernel and an expert kernel a layer.
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    assert not re.search(r'= bf16\[8,4,1408,128\]\S* (copy|transpose)\(',
                         text)
    assert not re.search(r'= bf16\[128,(2048,768|768,2048)\]\S* '
                         r'(copy|transpose)\(', text)
    formats, _ = compiled.input_formats
    for fmt in jax.tree.leaves(formats[1]):
        assert fmt.layout.major_to_minor == (0, 1, 2, 3)
    leaf = jax.tree.leaves(engine._cache)[0]
    assert compiled.memory_analysis().temp_size_in_bytes < leaf.nbytes
    # The prefill of a group: the flash kernel under the mask by blocks in
    # the first layer (the last layer's attention feeds logits that a
    # prefill of blocks does not read, and is not computed).
    toks = jax.ShapeDtypeStruct((4, 1024), jnp.int32, sharding=v5e_chip)
    vec = jax.ShapeDtypeStruct((4,), jnp.int32, sharding=v5e_chip)
    prefill = jax.jit(engine._prefill_raw, donate_argnums=(1, 2, 3)).lower(
        *state, toks, vec, vec, vec, shapes(engine._rng)).compile()
    assert prefill.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize('vocab, layers', [(64000, 8), (8000, 4)],
                         ids=['fullest-at-the-loss', 'fullest-in-backward'])
def test_train_step_keeps_the_flash_output_and_fits_as_counted(
        v5e_chip, monkeypatch, vocab, layers):
    """The WHOLE training step at `pretrain-4k`'s shape (Yi-Coder's widths,
    8 layers, 4 x 4,096 tokens, float32 state) compiled for the described
    v5e.  With nothing kept the backward pass runs the flash forward kernel
    a second time in every layer; with `out` + `lse` kept it is in the
    program once a layer, beside the one backward kernel: two custom
    calls a layer.  The head and the loss go by chunks of rows, so
    no buffer has the whole logits' shape and the step's temporaries with
    nothing kept are the gradients and a block's working set (7.02 GB
    with the logits whole).  And the bytes the trainer counts for the
    step's temporaries (`step_temporary_bytes` with the plan) are not
    under the compiler's own: with nothing kept, with `out` + `lse`, and
    with what `activation_budget` chooses under the v5e's limit, which
    then compiles (a count under the compiler's is an OOM on the chip);
    at the cell's vocabulary and, where the names "at the loss" and "in
    backward" come from, at an eighth of it and half the depth, which
    with the logits whole was fullest in a block's backward pass."""
    import dataclasses
    import re
    import numpy as np
    from skypilot_tpu.models import llama as llama_lib
    from skypilot_tpu.parallel import validate as validate_lib
    from skypilot_tpu.parallel.mesh import build_mesh, plan_mesh
    from skypilot_tpu.train import trainer as trainer_lib

    # `jax.default_backend()` is the CPU here: steer the kernel's choice.
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    (device,) = v5e_chip.device_set
    mesh = build_mesh(plan_mesh(1, fsdp=1), np.array([device]))
    rows, seq = 4, 4096
    cfg = llama_lib.LlamaConfig(
        vocab_size=vocab, dim=2048, n_layers=layers, n_heads=16,
        n_kv_heads=16,
        ffn_dim=5504, max_seq_len=seq, attention_impl='flash')
    tokens = jax.ShapeDtypeStruct((rows, seq), jnp.int32)
    out_lse = layers * rows * seq * 16 * (128 * 2 + 4)
    params_bytes = 4 * cfg.num_params()

    def compiled_step(keep_bytes):
        c = dataclasses.replace(cfg, remat_keep_bytes=keep_bytes)
        state, shardings = validate_lib._abstract_state(
            llama_lib.Llama(c, mesh), mesh, tokens)
        compiled = trainer_lib.make_sharded_train_step(
            mesh, shardings).lower(state, tokens).compile()
        text = compiled.as_text()
        kernels = [line for line in text.splitlines()
                   if 'custom_call_target="tpu_custom_call"' in line]
        forward = sum('flash_attention_fwd)' in line for line in kernels)
        # The backward is one kernel a layer (it was two), so a step
        # that keeps `out` + `lse` has two custom calls a layer.
        assert sum('flash_attention_bwd)' in line
                   for line in kernels) == cfg.n_layers
        assert len(kernels) == forward + cfg.n_layers
        # (An eighth of the vocabulary is one chunk, and whole.)
        assert vocab == 8000 or not re.search(
            rf'f32\[({rows * seq}|{rows},{seq}),{vocab}\]', text)
        counted = trainer_lib.step_temporary_bytes(
            c, mesh, rows, seq, params_bytes,
            llama_lib.keep_plan(c, mesh, rows, seq))
        return forward, compiled.memory_analysis().temp_size_in_bytes, counted

    forward, temporaries, counted = compiled_step(out_lse)
    assert forward == cfg.n_layers
    assert temporaries <= counted
    if vocab == 64000:
        forward, temporaries, counted = compiled_step(0)
        assert forward == 2 * cfg.n_layers
        # 2.67 GB of float32 gradients, which all exist before the
        # first is applied, and a block's working set.
        assert params_bytes < temporaries <= counted
        assert temporaries < 3.5e9
    # What the trainer chooses on the chip: the state is the parameters,
    # Adam's two moments and two counts.
    limit = 16909336064
    budget = trainer_lib.activation_budget(
        cfg, mesh, rows, seq, limit, 3 * params_bytes + 64, params_bytes)
    assert budget > 2 * out_lse
    forward, temporaries, counted = compiled_step(budget)
    assert forward == cfg.n_layers
    assert temporaries <= counted <= 1.1 * temporaries
    assert 3 * params_bytes + counted <= limit * 31 / 32


# ----- a window, a sink, keys wider than values (models/mimo_v2.py) ----------
@pytest.mark.parametrize('hkv', [2, 4])
def test_the_decode_kernel_takes_a_key_in_two_leaves_and_a_sink(hkv):
    """The decode kernel (interpret mode) at MiMo-V2's head sizes, a key of
    128 unrotated + 64 rotated values (the 64 of two KV heads a row of the
    second leaf) against a value of 128, with a sink a query head, against
    the XLA path: over a context cache bounded by the length, and over a
    ring of 128 bounded by min(length, 128); an empty slot gives zeros;
    without a sink the denominator is the plain one."""
    from skypilot_tpu.ops import attention as attn_lib
    from skypilot_tpu.ops.pallas import decode_attention as pallas_da
    b, hq, s, dn, dr, dv = 3, 8, 256, 128, 64, 128
    keys = jax.random.split(jax.random.PRNGKey(13), 6)
    q = jax.random.normal(keys[0], (b, hq, 1, dn), jnp.float32)
    q_rope = jax.random.normal(keys[1], (b, hq, 1, dr), jnp.float32)
    k = jax.random.normal(keys[2], (b, hkv, s, dn), jnp.float32)
    k_rope = attn_lib.pack_rope_keys(
        jax.random.normal(keys[3], (b, hkv, s, dr), jnp.float32))
    v = jax.random.normal(keys[4], (b, hkv, s, dv), jnp.float32)
    sink = jax.random.normal(keys[5], (hq,), jnp.float32)
    assert k_rope.shape == (b, hkv // 2, s, 2 * dr)
    lengths = jnp.asarray([200, 0, 12], jnp.int32)
    scale = (dn + dr) ** -0.5
    for more in (dict(sink=sink), dict(sink=None)):
        more.update(q_rope=q_rope, k_rope=k_rope, scale=scale)
        want = attn_lib.decode_attention(q, k, v, lengths, **more)  # XLA
        got = pallas_da.decode_attention_fwd(q, k, v, lengths, block=128,
                                             interpret=True, **more)
        assert got.shape == (b, hq, 1, dv)
        assert jnp.max(jnp.abs(got - want)) < 5e-3
        assert not jnp.any(got[1])
    # By hand for slot 2, head 5 (KV head 5 // group): 12 positions and
    # the sink's term in the denominator.
    kv_head = 5 // (hq // hkv)
    key_rows = jnp.concatenate(
        [k[2, kv_head, :12],
         attn_lib.unpack_rope_keys(k_rope)[2, kv_head, :12]], axis=-1)
    a = jnp.concatenate([q[2, 5, 0], q_rope[2, 5, 0]]) @ key_rows.T * scale
    e = jnp.exp(a - a.max())
    by_hand = (e / (e.sum() + jnp.exp(sink[5] - a.max()))) @ v[2, kv_head,
                                                               :12]
    got = pallas_da.decode_attention_fwd(
        q, k, v, lengths, block=128, interpret=True, q_rope=q_rope,
        k_rope=k_rope, sink=sink, scale=scale)
    assert jnp.max(jnp.abs(got[2, 5, 0] - by_hand)) < 5e-3
    # A ring of 128: every row is read once the sequence has passed it.
    ring = lambda t: t[:, :, :128]  # noqa: E731
    bound = jnp.minimum(jnp.asarray([700, 90, 128], jnp.int32), 128)
    got = pallas_da.decode_attention_fwd(
        q, ring(k), ring(v), bound, interpret=True, q_rope=q_rope,
        k_rope=ring(k_rope), sink=sink, scale=scale)
    want = attn_lib.decode_attention(q, ring(k), ring(v), bound,
                                     q_rope=q_rope, k_rope=ring(k_rope),
                                     sink=sink, scale=scale)
    assert jnp.max(jnp.abs(got - want)) < 5e-3


@pytest.mark.parametrize('window, block', [(128, 128), (128, 256), (96, 128),
                                           (300, 128)])
def test_the_flash_kernel_takes_a_window_and_a_sink(window, block):
    """The flash kernel (interpret mode) under a sliding window with a
    sink a head, keys of 192 against values of 128, against
    `mha_reference`; and the window alone.  The grid's last axis is the
    band's K blocks, not the sequence's."""
    keys = jax.random.split(jax.random.PRNGKey(17), 4)
    b, hq, hkv, s = 1, 4, 2, 512
    q = jax.random.normal(keys[0], (b, hq, s, 192), jnp.float32)
    k = jax.random.normal(keys[1], (b, hkv, s, 192), jnp.float32)
    v = jax.random.normal(keys[2], (b, hkv, s, 128), jnp.float32)
    sink = jax.random.normal(keys[3], (hq,), jnp.float32)
    for more in (dict(sink=sink), {}):
        want = mha_reference(q, k, v, causal=True, window=window, **more)
        got = flash_attention_fwd(q, k, v, causal=True, block_size=block,
                                  interpret=True, window=window, **more)
        assert got.shape == (b, hq, s, 128)
        assert jnp.max(jnp.abs(got - want)) < 2e-3
    whole = mha_reference(q, k, v, causal=True)
    assert jnp.max(jnp.abs(whole - want)) > 1e-2
    text = flash_attention_fwd.lower(q, k, v, causal=True, block_size=block,
                                     interpret=True, window=window).as_text()
    full = flash_attention_fwd.lower(q, k, v, causal=True, block_size=block,
                                     interpret=True).as_text()
    assert text != full


def test_without_a_window_or_a_sink_every_program_is_the_one_it_was():
    """Yi's and SDAR's decode and prefill attention, and the causal flash
    kernel with its residuals: `window` 0, `sink` None and the decode
    kernel's further operands left out lower to the same text as with
    them given at those values, in the kernels and in the XLA paths (the
    parent's text itself was compared by hand: PERF.md section 6, PR 41)."""
    from skypilot_tpu.ops import attention as attn_lib
    from skypilot_tpu.ops.pallas import decode_attention as pallas_da
    f32 = jnp.float32
    q = jax.ShapeDtypeStruct((2, 8, 256, 128), f32)
    kv = jax.ShapeDtypeStruct((2, 2, 256, 128), f32)

    def flash(**kw):
        return flash_attention_fwd.lower(q, kv, kv, causal=True,
                                         block_size=128, interpret=True,
                                         **kw).as_text()

    assert flash(window=0, sink=None) == flash()
    assert flash(window=0, sink=None, mask_block=4) == flash(mask_block=4)
    assert flash(window=0, return_residuals=True) == flash(
        return_residuals=True)
    assert flash(window=128) != flash()

    def on_mesh(**kw):
        return jax.jit(lambda q, k, v: attn_lib.flash_attention_on_mesh(
            q, k, v, None, causal=True, **kw)).lower(q, kv, kv).as_text()

    assert on_mesh(window=0, sink=None) == on_mesh()
    assert on_mesh(window=0, mask_block=4) == on_mesh(mask_block=4)

    def ref(**kw):
        return jax.jit(lambda q, k, v: mha_reference(
            q, k, v, causal=True, **kw)).lower(q, kv, kv).as_text()

    assert ref(window=0, sink=None) == ref()
    assert ref(window=64) != ref()
    cache = jax.ShapeDtypeStruct((4, 2, 256, 128), f32)
    lengths = jax.ShapeDtypeStruct((4,), jnp.int32)
    for rows in (1, 4):         # a token a slot; a block's 4 rows (SDAR)
        row = jax.ShapeDtypeStruct((4, 8, rows, 128), f32)
        assert pallas_da.decode_attention_fwd.lower(
            row, cache, cache, lengths, block=128, interpret=True,
            q_rope=None, k_rope=None, sink=None, scale=None).as_text() == \
            pallas_da.decode_attention_fwd.lower(
                row, cache, cache, lengths, block=128,
                interpret=True).as_text()
        assert jax.jit(lambda q, k, v, n: attn_lib.decode_attention(
            q, k, v, n, None, q_rope=None, k_rope=None, sink=None,
            scale=None)).lower(row, cache, cache, lengths).as_text() == \
            jax.jit(lambda q, k, v, n: attn_lib.decode_attention(
                q, k, v, n)).lower(row, cache, cache, lengths).as_text()


def test_mimo_kernels_compile_for_v5e(v5e_chip):
    """At the cell's shapes (32 slots, 64 query heads, keys of 128 + 64,
    values of 128): the decode kernel over a full layer's 4 KV heads and
    9,216 positions, and over a window layer's 8 KV heads and ring of 128
    with its sinks; the flash kernel over a row of 8,192 positions under
    the window of 128 with its sinks."""
    from skypilot_tpu.ops.pallas import decode_attention as pallas_da

    def sds(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=v5e_chip)

    for hkv, kept, sink in ((4, 9216, None),
                            (8, 128, sds(64, dtype=jnp.float32))):
        compiled = pallas_da.decode_attention_fwd.lower(
            sds(32, 64, 1, 128), sds(32, hkv, kept, 128),
            sds(32, hkv, kept, 128), sds(32, dtype=jnp.int32),
            q_rope=sds(32, 64, 1, 64), k_rope=sds(32, hkv // 2, kept, 128),
            sink=sink, scale=192 ** -0.5).compile()
        assert 'tpu_custom_call' in compiled.as_text()
    compiled = flash_attention_fwd.lower(
        sds(1, 64, 8192, 192), sds(1, 8, 8192, 192), sds(1, 8, 8192, 128),
        causal=True, window=128, sink=sds(64, dtype=jnp.float32)).compile()
    assert 'tpu_custom_call' in compiled.as_text()


def test_decode_program_keeps_both_kinds_of_cache_as_the_kernel_reads_them(
        v5e_chip, monkeypatch):
    """The engine's decode program of MiMo-V2 at the cell's widths (the
    dense full layer and two window expert layers, 8 slots of 9,216
    positions) with both decode kernels in it and every layout left to the
    compiler as `_optimize_layouts` leaves them: the full layer's K and V
    and the rings come out row-major [B, Hkv, positions, 128] as the
    kernel reads them (the rows of a step are written as rows of 128 over
    (slot x head, position)), nothing makes a copy of a context leaf, and
    no temporary is as large as one.  The prefill of a row compiles with
    the flash kernel in every layer, the window layers' over their band."""
    import re
    from jax.experimental.layout import Format, Layout
    from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig
    from skypilot_tpu.models import moe as moe_lib
    from skypilot_tpu.models.mimo_v2 import MiMoV2, MiMoV2Config
    from skypilot_tpu.ops import attention as attn_lib
    from skypilot_tpu.ops.pallas import decode_attention as pallas_da
    from skypilot_tpu.ops.pallas import grouped_experts as pallas_ge

    # `jax.default_backend()` is the CPU here: steer the choices themselves.
    monkeypatch.setattr(
        attn_lib, 'decode_kv_block',
        lambda h, d, s, dtype=jnp.bfloat16, mesh=None: pallas_da.block_len(
            h, d, s, jnp.dtype(dtype).itemsize))
    monkeypatch.setattr(
        moe_lib, 'expert_tile',
        lambda n_tokens, block, w_gate, mesh=None: None
        if n_tokens > block else pallas_ge.tile_f(
            w_gate.shape[1], w_gate.shape[2], w_gate.dtype.itemsize))
    real_on_mesh = attn_lib.flash_attention_on_mesh

    def on_mesh(q, k, v, mesh, causal=True, mask_block=1, window=0,
                sink=None):
        return flash_attention_fwd(q, k, v, causal=causal, window=window,
                                   sink=sink)

    monkeypatch.setattr(attn_lib, 'flash_attention_on_mesh', on_mesh)
    del real_on_mesh
    cfg = MiMoV2Config(
        vocab_size=19072, n_layers=3, layer_pattern=(0, 1, 1),
        held_experts=tuple(range(16)), max_seq_len=9216,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    model = MiMoV2(cfg)
    assert model.served().decode_kv_block == 512
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))['params'])
    engine = DecodeEngine(model, params, EngineConfig(
        n_slots=8, steps_per_call=8, prefill_buckets=(8192,)))
    auto = Format(Layout.AUTO, v5e_chip)

    def autos(tree):
        return jax.tree.map(lambda _: auto, tree)

    def shapes(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=v5e_chip), tree)

    lens = shapes(engine._lens_d)
    state = (shapes(params), shapes(engine._cache), shapes(engine._last_d),
             lens)
    compiled = jax.jit(
        engine._decode_raw, donate_argnums=(1, 2, 3),
        in_shardings=(autos(params), autos(engine._cache), auto, auto, auto,
                      auto),
        out_shardings=(auto, autos(engine._cache), auto, auto)).lower(
            *state, lens, shapes(engine._rng)).compile()
    text = compiled.as_text()
    # A decode-attention kernel a layer, an expert kernel in the two
    # expert layers.
    assert text.count('custom_call_target="tpu_custom_call"') == 5
    assert not re.search(r'= bf16\[8,[24],9216,128\]\S* (copy|transpose)\(',
                         text)
    formats, _ = compiled.input_formats
    for fmt in jax.tree.leaves(formats[1]):
        assert fmt.layout.major_to_minor == (0, 1, 2, 3)
    context = engine._cache['layer_0']['attn']['v']
    assert context.shape == (8, 4, 9216, 128)
    assert compiled.memory_analysis().temp_size_in_bytes < context.nbytes
    # The prefill of a group, a row at a time: a flash kernel a layer.
    toks = jax.ShapeDtypeStruct((4, 8192), jnp.int32, sharding=v5e_chip)
    vec = jax.ShapeDtypeStruct((4,), jnp.int32, sharding=v5e_chip)
    prefill = jax.jit(engine._prefill_raw, donate_argnums=(1, 2, 3)).lower(
        *state, toks, vec, vec, vec, shapes(engine._rng)).compile()
    assert prefill.as_text().count(
        'custom_call_target="tpu_custom_call"') == 3


def _granite_at_published_widths(n_slots, rows=None, vocab=8192):
    """Granite-4.0-H at its published widths in three layers (Mamba-2,
    attention, Mamba-2), abstract weights, an engine built for its raw
    programs, and the cache of `n_slots` slots as shapes."""
    import flax.linen as nn
    from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig
    from skypilot_tpu.models import granite_hybrid as granite_lib
    cfg = granite_lib.GraniteHybridConfig(
        vocab_size=vocab, n_layers=3, attention_layers=(1,),
        max_seq_len=1024, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    kind = granite_lib.GraniteHybrid
    if rows is not None:
        from served_utils import declaring
        kind = declaring(kind, prefill_rows=rows)
    model = kind(cfg)
    params = nn.meta.unbox(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))['params']))
    engine = DecodeEngine(model, params, EngineConfig(
        n_slots=2, steps_per_call=8, prefill_buckets=(512,)))
    cache = jax.eval_shape(lambda p: engine._make_cache(p, n_slots), params)
    return engine, params, cache


def test_granite_kernels_compile_for_v5e(v5e_chip):
    """The state kernel at the cell's shapes (64 slots of 32 pairs of
    heads, a state of 128 by 128 lanes a pair), and the flash kernel at a
    head of 64 (32 query heads over 8 KV heads, 512 positions)."""
    from skypilot_tpu.ops.pallas import ssm_state_update as pallas_ssm

    def sds(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=v5e_chip)

    assert pallas_ssm.block_groups(32, 128, 128) == 16
    row = sds(64, 32, 128)
    compiled = pallas_ssm.ssm_state_update_fwd.lower(
        sds(64, 32, 128, 128), row, row, row, sds(64, 128),
        sds(64, 128)).compile()
    assert 'ssm_state_update' in compiled.as_text()
    q = sds(8, 32, 512, 64, dtype=jnp.bfloat16)
    kv = sds(8, 8, 512, 64, dtype=jnp.bfloat16)
    assert 'tpu_custom_call' in flash_attention_fwd.lower(
        q, kv, kv, causal=True).compile().as_text()


def test_decode_program_keeps_the_ssm_state_as_the_kernel_reads_it(
        v5e_chip, monkeypatch):
    """Granite-4.0-H's whole decode program (three layers at the published
    widths, 16 slots, 8 steps a call) with the state kernel and the decode
    attention kernel in it, the cache donated and every layout left to the
    compiler as `_optimize_layouts` leaves them: a Mosaic call a layer,
    every state leaf and K/V leaf row-major in and out, the new cache in
    the buffers of the old, and no temporary as large as a state leaf (33
    MB at 16 slots): no copy of one anywhere."""
    import re
    from jax.experimental.layout import Format, Layout
    from skypilot_tpu.ops import attention as attn_lib
    from skypilot_tpu.ops.pallas import decode_attention as pallas_da

    n_slots = 16
    engine, params, cache = _granite_at_published_widths(n_slots)
    # `jax.default_backend()` is the CPU here: steer the choices (once the
    # engine is built: it would lay its arrays out for a chip).
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    assert cache['layer_0']['mamba']['state'].shape == (16, 32, 128, 128)
    assert cache['layer_1']['attn']['k'].shape == (16, 4, 1024, 128)
    assert attn_lib.decode_kv_block(4, 128, 1024) == \
        pallas_da.block_len(4, 128, 1024)
    auto = Format(Layout.AUTO, v5e_chip)

    def autos(tree):
        return jax.tree.map(lambda _: auto, tree)

    def shapes(tree):
        return jax.tree.map(lambda t: jax.ShapeDtypeStruct(
            t.shape, t.dtype, sharding=v5e_chip), tree)

    vec = jax.ShapeDtypeStruct((n_slots,), jnp.int32, sharding=v5e_chip)
    compiled = jax.jit(
        engine._decode_raw, donate_argnums=(1, 2, 3),
        in_shardings=(autos(params), autos(cache), auto, auto, auto, auto),
        out_shardings=(auto, autos(cache), auto, auto)).lower(
            shapes(params), shapes(cache), vec, vec, vec,
            shapes(engine._rng)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert 'ssm_state_update' in text
    formats_in, _ = compiled.input_formats
    for layer in ('layer_0', 'layer_2'):
        for formats in (formats_in[1], compiled.output_formats[1]):
            assert formats[layer]['mamba']['state'].layout.major_to_minor \
                == (0, 1, 2, 3)
    for leaf in ('k', 'v'):
        assert formats_in[1]['layer_1']['attn'][leaf].layout.major_to_minor \
            == (0, 1, 2, 3)
    leaf = 16 * 32 * 128 * 128 * 4
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < leaf
    assert memory.alias_size_in_bytes >= 2 * leaf
    assert not re.search(r'= f32\[16,32,128,128\]\S* (copy|transpose)\(',
                         text)
    assert not re.search(r'= bf16\[16,4,1024,128\]\S* (copy|transpose)\(',
                         text)


def test_a_prefill_holds_one_groups_caches_beside_the_cache(v5e_chip,
                                                            monkeypatch):
    """A prefill of 32 rows of 512 into a cache of 32 slots (three layers
    at Granite-4.0-H's published widths), compiled for the chip: with
    `prefill_rows` 4 its temporaries are those of 4 rows, not of 32.  A
    row's caches are 6.3 MB (two states of 2 MB, the taps, 2 MB of K and
    V over 1024 positions); the rows in one pass hold 32 of them and
    every activation 8 times over."""
    n = 32
    engines = {rows: _granite_at_published_widths(n, rows)
               for rows in (4, n)}
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')

    def temporaries(rows):
        engine, params, cache = engines[rows]

        def shapes(tree):
            return jax.tree.map(lambda t: jax.ShapeDtypeStruct(
                t.shape, t.dtype, sharding=v5e_chip), tree)

        vec = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=v5e_chip)
        toks = jax.ShapeDtypeStruct((n, 512), jnp.int32, sharding=v5e_chip)
        memory = jax.jit(engine._prefill_raw, donate_argnums=(1, 2, 3)).lower(
            shapes(params), shapes(cache), vec, vec, toks, vec, vec, vec,
            shapes(engine._rng)).compile().memory_analysis()
        row = sum(leaf.size * leaf.dtype.itemsize
                  for leaf in jax.tree.leaves(cache)) // n
        assert memory.alias_size_in_bytes >= n * row     # inserted in place
        return memory.temp_size_in_bytes, row

    (by_four, row), (whole, _) = temporaries(4), temporaries(n)
    assert 6_000_000 < row < 7_000_000
    assert whole - by_four > (n - 4) * row
    assert by_four < whole / 4


def test_the_one_prefill_program_keeps_the_cache_where_decode_pinned_it(
        v5e_chip, monkeypatch):
    """The WHOLE prefill program of a model with `prefill_rows` (three
    layers at Granite-4.0-H's published widths, 16 slots, a bucket of 512),
    compiled for the chip as `_prefill_for` compiles it: the length of its
    arrays is the slots', the rows it runs a value it reads, two loops (8
    rows at a time, then a row at a time) that carry the donated cache,
    which comes in and goes out in the layouts the decode program chose.
    No state or K/V leaf is copied or transposed anywhere, each body
    scatters its rows in place, and the program's temporaries are at most
    those of the parent's program of 16 rows (two groups of 8 under a
    `lax.scan`: 340,414,464 bytes, a scratch compile of commit 551dff7),
    so the one program costs a start no more memory than the largest of
    the five it stands for."""
    import re
    from jax.experimental.layout import Format, Layout

    n_slots = 16
    engine, params, cache = _granite_at_published_widths(n_slots)
    assert engine.model.served().prefill_rows == 8
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    auto = Format(Layout.AUTO, v5e_chip)

    def autos(tree):
        return jax.tree.map(lambda _: auto, tree)

    def shapes(tree):
        return jax.tree.map(lambda t: jax.ShapeDtypeStruct(
            t.shape, t.dtype, sharding=v5e_chip), tree)

    vec = jax.ShapeDtypeStruct((n_slots,), jnp.int32, sharding=v5e_chip)
    state = (shapes(params), shapes(cache), vec, vec)
    decode = jax.jit(
        engine._decode_raw, donate_argnums=(1, 2, 3),
        in_shardings=(autos(params), autos(cache), auto, auto, auto, auto),
        out_shardings=(auto, autos(cache), auto, auto)).lower(
            *state, vec, shapes(engine._rng)).compile()
    pinned, _ = decode.input_formats
    toks = jax.ShapeDtypeStruct((n_slots, 512), jnp.int32, sharding=v5e_chip)
    prefill = jax.jit(
        engine._prefill_raw, donate_argnums=(1, 2, 3),
        in_shardings=(*pinned[:4], None, None, None, None, None),
        out_shardings=tuple(pinned[1:4])).lower(
            *state, toks, vec, vec, vec, shapes(engine._rng)).compile()
    text = prefill.as_text()
    # A flash kernel a body (the one attention layer), and the two loops.
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    for leaf in (r'f32\[16,32,128,128\]', r'bf16\[16,4,1024,128\]'):
        assert not re.search(rf'= {leaf}\S* (copy|transpose)\(', text)
    formats_in, _ = prefill.input_formats
    for got in (formats_in[1], prefill.output_formats[0]):
        for want, fmt in zip(jax.tree.leaves(pinned[1]),
                             jax.tree.leaves(got)):
            assert fmt.layout.major_to_minor == want.layout.major_to_minor
    memory = prefill.memory_analysis()
    row = sum(leaf.size * leaf.dtype.itemsize
              for leaf in jax.tree.leaves(cache)) // n_slots
    assert memory.alias_size_in_bytes >= n_slots * row   # inserted in place
    assert memory.temp_size_in_bytes <= 340_414_464

