"""The decode step's attention (`ops/attention.py decode_attention`): the
Pallas kernel under the interpreter against `mha_reference` with the
positions mask, and the XLA path it takes off the TPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.ops import attention as attn_lib
from skypilot_tpu.ops.pallas import decode_attention as pallas_da

S, D, BLOCK = 64, 128, 16
# A slot's length: empty, one row, one under / at / over a block edge, one
# under the last edge, and the whole (clamped) slot.
LENGTHS = [0, 1, BLOCK - 1, BLOCK, BLOCK + 1, S - 1, S]


def _masked_reference(q, k, v, lengths):
    b, _, s, _ = k.shape
    return attn_lib.mha_reference(
        q, k, v, causal=True, segment_positions=(lengths - 1)[:, None],
        kv_positions=jnp.broadcast_to(jnp.arange(s)[None, :], (b, s)))


def _inputs(hq, hkv, lengths, seed=0):
    b = len(lengths)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kq, (b, hq, 1, D), jnp.bfloat16),
            jax.random.normal(kk, (b, hkv, S, D), jnp.bfloat16),
            jax.random.normal(kv, (b, hkv, S, D), jnp.bfloat16),
            jnp.asarray(lengths, jnp.int32))


@pytest.mark.parametrize('length', LENGTHS)
@pytest.mark.parametrize('heads', [(16, 16), (32, 4), (64, 8)],
                         ids=lambda h: f'{h[0]}on{h[1]}')
def test_kernel_matches_masked_reference(heads, length):
    # The slot under test between a full and an empty neighbour: a tile
    # index clamped for one slot must not leak into the next.
    q, k, v, lengths = _inputs(*heads, [S, length, 0, length])
    out = pallas_da.decode_attention_fwd(q, k, v, lengths, block=BLOCK,
                                         interpret=True)
    ref = _masked_reference(q, k, v, lengths)
    assert out.shape == q.shape and out.dtype == q.dtype
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    assert np.isfinite(out).all()
    # bf16 probabilities and outputs: a rounding step of values near 1.
    np.testing.assert_allclose(out, ref, atol=2e-2, rtol=2e-2)
    assert (out[2] == 0).all()           # length 0: zeros, not NaN


# Slots that hold no request (a length of zero) in front of, between
# and behind live ones, and nothing but them.
EMPTY_SLOTS = {
    'front': [0, 0, BLOCK + 1, S],
    'between': [S, 0, 0, 1, 0, BLOCK],
    'behind': [BLOCK + 1, 3, 0, 0],
    'mixed': [0, S - 1, 0, BLOCK, 0],
    'all': [0, 0, 0],
}


@pytest.mark.parametrize('lengths', EMPTY_SLOTS.values(), ids=EMPTY_SLOTS)
@pytest.mark.parametrize('heads', [(16, 16), (32, 4)],
                         ids=lambda h: f'{h[0]}on{h[1]}')
def test_empty_slots_give_zeros_and_leave_the_live_rows_alone(heads, lengths):
    q, k, v, lengths = _inputs(*heads, lengths)
    # What a retired request left in an empty slot would show if read.
    empty = (lengths == 0)[:, None, None, None]
    k = jnp.where(empty, 1e4, k).astype(k.dtype)
    v = jnp.where(empty, -1e4, v).astype(v.dtype)
    out = pallas_da.decode_attention_fwd(q, k, v, lengths, block=BLOCK,
                                         interpret=True)
    ref = _masked_reference(q, k, v, lengths)
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(out[live], ref[live], atol=2e-2, rtol=2e-2)
    assert (out[~live] == 0).all() and (ref[~live] == 0).all()


@pytest.mark.parametrize('lengths', EMPTY_SLOTS.values(), ids=EMPTY_SLOTS)
def test_an_empty_slot_asks_for_the_tile_already_there(lengths):
    """The index map over the whole grid, in the order the pipeline walks
    it (a tile is fetched when its index differs from the step before):
    a live slot asks for its own tiles up to its last live one, an empty
    slot for what the step before it asked for, at every `j`, so the call
    fetches the live slots' tiles and nothing else (one tile where no
    slot is live)."""
    lengths = np.asarray(lengths, np.int32)
    slot, tile = (np.asarray(a) for a in pallas_da.resident_tiles(
        jnp.asarray(lengths), BLOCK))
    n_blocks = S // BLOCK
    walk = [tuple(int(x) for x in pallas_da._kv_index(i, j, lengths, slot,
                                                       tile))
            for i in range(len(lengths)) for j in range(n_blocks)]
    for step, index in enumerate(walk):
        i, j = divmod(step, n_blocks)
        if lengths[i]:
            assert index == (i, 0, min(j, -(-lengths[i] // BLOCK) - 1), 0)
        elif step:
            assert index == walk[step - 1]
    fetches = 1 + sum(a != b for a, b in zip(walk, walk[1:]))
    assert fetches == max(int((-(-lengths // BLOCK)).sum()), 1)


def test_kernel_reads_nothing_past_the_length():
    """Positions at and past a slot's length do not reach the result:
    what a retired request left there (here values that would swamp the
    softmax if read) leaves it as it was."""
    q, k, v, lengths = _inputs(32, 4, [1, BLOCK, BLOCK + 3, S - 1])
    out = pallas_da.decode_attention_fwd(q, k, v, lengths, block=BLOCK,
                                         interpret=True)
    past = jnp.arange(S)[None, None, :, None] >= lengths[:, None, None, None]
    k_bad = jnp.where(past, 1e4, k).astype(k.dtype)
    v_bad = jnp.where(past, -1e4, v).astype(v.dtype)
    out_bad = pallas_da.decode_attention_fwd(q, k_bad, v_bad, lengths,
                                             block=BLOCK, interpret=True)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(out_bad, np.float32))


@pytest.mark.parametrize('shape,block', [
    ((16, 128, 1024), 256),      # Yi-Coder: a tile of 1 MB
    ((4, 128, 1024), 512),       # Yi-6B: capped, half a slot
    ((8, 128, 1664), 128),       # 1,664 positions divide by 128 only
    ((8, 128, 4096), 512),
    ((32, 128, 2048), 128),
    ((16, 64, 1024), None),      # head size not a multiple of the lanes
    ((16, 128, 1000), None),     # no power of two >= 128 divides it
    ((128, 128, 1024), None),    # 128 positions of 128 heads are 4 MB
])
def test_block_len_follows_the_shapes(shape, block):
    assert pallas_da.block_len(*shape) == block


def test_off_the_tpu_it_is_the_masked_reference_to_the_bit():
    assert attn_lib.decode_kv_block(16, D, 1024) is None     # the CPU
    q, k, v, lengths = _inputs(32, 4, LENGTHS)
    out = attn_lib.decode_attention(q, k, v, lengths)
    # What `_decode_attend` called before: the mask by positions.
    positions = (lengths - 1)[:, None]
    ref = attn_lib.mha_reference(
        q, k, v, causal=True, segment_positions=positions,
        kv_positions=jnp.broadcast_to(jnp.arange(S)[None, :],
                                      (len(LENGTHS), S)))
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(ref, np.float32))
    assert np.isfinite(np.asarray(out, np.float32)).all()


def test_a_mesh_of_several_devices_reads_through_xla(monkeypatch):
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    assert attn_lib.decode_kv_block(16, D, 1024) == 256
    assert attn_lib.decode_kv_block(16, D, 1000) is None
    devices = np.array(jax.devices()[:1])
    one = jax.sharding.Mesh(devices, ('tensor',))
    assert attn_lib.decode_kv_block(16, D, 1024, mesh=one) == 256

    class Four:          # a mesh as the test sees it: its size decides
        size = 4
    assert attn_lib.decode_kv_block(16, D, 1024, mesh=Four()) is None
