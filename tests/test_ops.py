"""Attention op tests: pallas kernel (interpret mode) and ring attention
against the XLA reference. Runs on the 8-device virtual CPU mesh."""
import jax
import jax.numpy as jnp
import pytest

from skypilot_tpu.ops.attention import flash_attention, mha_reference
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


@pytest.mark.parametrize('causal', [True, False])
def test_pallas_flash_bwd_matches_reference(causal):
    q, k, v = _qkv(b=1, h=2, s=256, d=64)
    out, lse = flash_attention_fwd(q, k, v, causal=causal, block_size=128,
                                   interpret=True, return_residuals=True)
    g = jax.random.normal(jax.random.PRNGKey(7), out.shape, out.dtype)
    dq, dk, dv = flash_attention_bwd(q, k, v, out, lse, g, causal=causal,
                                     block_size=128, interpret=True)
    ref_out, vjp = jax.vjp(
        lambda q_, k_, v_: mha_reference(q_, k_, v_, causal=causal), q, k, v)
    dq_ref, dk_ref, dv_ref = vjp(g)
    assert jnp.max(jnp.abs(out - ref_out)) < 5e-3
    assert jnp.max(jnp.abs(dq - dq_ref)) < 5e-3
    assert jnp.max(jnp.abs(dk - dk_ref)) < 5e-3
    assert jnp.max(jnp.abs(dv - dv_ref)) < 5e-3


def test_pallas_flash_bwd_gqa_group_reduce():
    # flash_attention_bwd owns the GQA repeat AND the matching group
    # reduction — grads must come back at Hkv heads and match the
    # reference (the production _flash_bwd delegates to exactly this).
    q, k, v = _qkv(b=1, h=4, hkv=2, s=256, d=64)
    out, lse = flash_attention_fwd(q, k, v, causal=True, block_size=128,
                                   interpret=True, return_residuals=True)
    g = jnp.ones_like(out)
    dq, dk, dv = flash_attention_bwd(
        q, k, v, out, lse, g, causal=True, block_size=128, interpret=True)
    assert dk.shape == k.shape and dv.shape == v.shape
    _, vjp = jax.vjp(
        lambda q_, k_, v_: mha_reference(q_, k_, v_, causal=True), q, k, v)
    dq_ref, dk_ref, dv_ref = vjp(g)
    assert jnp.max(jnp.abs(dq - dq_ref)) < 5e-3
    assert jnp.max(jnp.abs(dk - dk_ref)) < 5e-3
    assert jnp.max(jnp.abs(dv - dv_ref)) < 5e-3


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
# and what model.init traces for llama2-7b.
_MAIN_PATH_SHAPES = [(4, 16, 8, 4096, 128), (2, 16, 8, 8192, 128),
                     (1, 32, 32, 256, 128)]


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
    assert 'tpu_custom_call' in lowered.compile().as_text()
