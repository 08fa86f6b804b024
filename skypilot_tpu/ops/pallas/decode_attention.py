"""Pallas TPU kernel for the decode step's attention: one query row a
head against a slot's cached K and V, read up to the slot's length.

The XLA path (`mha_reference` under the positions mask) reads every
position of every slot's cache leaf whatever the slot holds; a decode
step is bound by the bytes it reads, and a serving batch holds a fraction
of `n_slots x max_seq_len`.  Here the lengths are scalar-prefetched and
the grid is (slot, KV block): a tile is all KV heads of one block of
positions, `[Hkv, block, D]` of K and of V.  The tile's block index is
clamped to the slot's last live block, so the steps past a slot's length
ask for the tile already there (the pipeline fetches a tile only when its
index changes) and compute nothing (`pl.when`).  A length of zero is a
slot that holds no request: its steps ask for the tile that is resident
when the grid reaches it (`resident_tiles`: the last live tile of the
nearest live slot before it), so it fetches nothing, computes nothing and
returns zeros; a batch of empty slots fetches one tile in all.  Online
softmax in float32 scratch, as in `flash_attention.py`; bf16 in, float32
accumulation, bf16 out.

GQA: the `group` query heads of a KV head are `group` query rows of it
(`mha_reference`'s fold); K and V are contracted as they are stored.  The
rows are padded to the sublane tile, so MHA's one row a head is 8 rows of
which one is read.  A pass over a block (generation by diffusion over
blocks: R query rows a slot, all of which read the positions below the one
length, the block's own rows included) folds its R rows in beside them:
`group * R` rows a KV head, the same tiles, the same bound.

Keys wider than values (a key of 192 beside a value of 128: 128 values
that are not rotated and 64 that are): the key's first 128 lanes are the
leaf `k_cache` as ever, and the other 64 come as a second leaf, `k_rope`
`[B, Hkv / 2, S, 128]`, in which a row holds the 64 of TWO KV heads side by
side (heads 2i and 2i + 1 in lanes 0-63 and 64-127).  Every leaf is then a
whole number of 128-lane tiles, a position costs 128 + 64 + 128 values a
head in HBM and not the 384 that a padded key of 192 would, and the row a
step writes is a whole row of each leaf.  The query's 64 come laid out to
match (`q_rope`: zeros in the other head's lanes), so a pair's score is one
product over 128 lanes against the pair's tile; the value is as wide as
the key's first leaf.  A `sink` is one logit a query head that joins the
softmax's denominator and brings no value (it is added when a slot's last
tile is done); a slot of length zero still returns zeros.  A ring of W
positions (a window layer's cache, position p at p % W) needs nothing of
its own here: softmax does not care for order, so its caller bounds it by
min(length, W).  Without `k_rope` and `sink` the program is the one it
was.

Operand layout: a Mosaic call fixes its operands' layouts, so a program
that holds this kernel keeps the cache row-major `[B, Hkv, S, D]` with D
on the lanes and positions on the sublanes (where XLA alone chose
position-major for the decode program).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_TILE_BYTES = 1 << 20     # of K (and of V) a grid step; twice double-buffered
_MAX_BLOCK = 512          # a slot half full should not read all of itself
_MIN_BLOCK = 128
_Q_ROWS = 8               # the float32 sublane tile


def block_len(n_kv_heads: int, head_dim: int, seq_len: int,
              itemsize: int = 2) -> Optional[int]:
    """Positions in a tile: the largest power of two that divides
    `seq_len`, keeps `[Hkv, block, D]` within about a megabyte and is at
    most `_MAX_BLOCK`; None where the tiling cannot take the shapes (the
    caller then reads the cache through XLA)."""
    if head_dim % 128:
        return None
    block = _MAX_BLOCK
    while block >= _MIN_BLOCK:
        if (seq_len % block == 0 and
                n_kv_heads * block * head_dim * itemsize <= _TILE_BYTES):
            return block
        block //= 2
    return None


def resident_tiles(lengths: jax.Array, block: int):
    """(slot, tile) [B] each: where a slot's grid steps look once they have
    nothing of their own left to read.  A live slot's are its own last
    live tile.  An empty slot's (length zero) are those of the nearest
    live slot before it, which is what the pipeline holds when the grid
    gets there; empty slots in front of the first live one look at that
    slot's first tile, which its own first step then finds fetched.  With
    every slot empty that is slot 0's first tile, the one fetch a call
    cannot do without.  (A chip that splits the slot axis over two cores
    starts the second half with nothing resident: one more fetch there.)"""
    index = jnp.arange(lengths.shape[0], dtype=jnp.int32)
    live = lengths > 0
    before = jax.lax.cummax(jnp.where(live, index, -1))
    slot = jnp.where(before >= 0, before, jnp.argmax(live)).astype(jnp.int32)
    last = jnp.maximum(pl.cdiv(lengths, block) - 1, 0)
    return slot, jnp.where(before >= 0, last[slot], 0).astype(jnp.int32)


def _kv_index(i, j, lens, slot, tile):
    """The K (and V) block of grid step (i, j): the slot's own tile j up
    to its last live one, and only that one (`resident_tiles`) of an
    empty slot."""
    own = jnp.where(lens[i] > 0, j, tile[i])
    return (slot[i], 0, jnp.minimum(own, tile[i]), 0)


def _kernel(lens_ref, slot_ref, tile_ref, q_ref, k_ref, v_ref, *rest,
            scale: float, block: int, rope: bool = False,
            sink: bool = False):
    del slot_ref, tile_ref               # the index maps' operands
    rest = list(rest)
    qr_ref, kr_ref = (rest.pop(0), rest.pop(0)) if rope else (None, None)
    sink_ref = rest.pop(0) if sink else None
    o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    length = lens_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j * block < length)
    def _compute():
        q = q_ref[0]                                   # (Hkv, rows, D)
        k = k_ref[0]                                   # (Hkv, block, D)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)        # (Hkv, rows, block)
        if rope:
            # A pair of KV heads a batch entry: (Hkv / 2, 2 x rows, 128)
            # against the pair's tile, the first `rows` the even head's.
            s = s + jax.lax.dot_general(
                qr_ref[0], kr_ref[0], (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32).reshape(s.shape)
        s = s * scale
        k_pos = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(k_pos < length, s, _NEG_INF)
        m_prev = m_scr[:]                              # (Hkv, rows, 128)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, :, :1])
        correction = jnp.exp(m_prev[:, :, :1] - m_new[:, :, :1])
        l_scr[:] = l_scr[:] * correction + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * correction + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)        # (Hkv, rows, D)
        m_scr[:] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        # An empty slot keeps l = 0: zeros, not NaN.
        l = l_scr[:, :, :1]
        whole = l
        if sink:
            whole = l + jnp.exp(sink_ref[:, :, :1] - m_scr[:, :, :1])
        o_ref[0] = (acc_scr[:] / jnp.where(l == 0.0, 1.0, whole)).astype(
            o_ref.dtype)


def pack_rope_queries(q_rope: jax.Array) -> jax.Array:
    """q_rope [B, Hkv, rows, R] -> [B, Hkv / 2, 2 x rows, 2 x R]: a pair of
    KV heads' query rows one under the other, each with its R values in
    its own head's lanes of the pair's `k_rope` row and zeros in the other
    head's."""
    b, hkv, rows, r = q_rope.shape
    pairs = q_rope.reshape(b, hkv // 2, 2, rows, r)
    zeros = jnp.zeros_like(pairs[:, :, 0])
    return jnp.concatenate([
        jnp.concatenate([pairs[:, :, 0], zeros], axis=-1),
        jnp.concatenate([zeros, pairs[:, :, 1]], axis=-1)], axis=2)


@functools.partial(jax.jit,
                   static_argnames=('block', 'interpret', 'scale'))
def decode_attention_fwd(q: jax.Array, k_cache: jax.Array,
                         v_cache: jax.Array, lengths: jax.Array,
                         block: Optional[int] = None,
                         interpret: bool = False,
                         q_rope: Optional[jax.Array] = None,
                         k_rope: Optional[jax.Array] = None,
                         sink: Optional[jax.Array] = None,
                         scale: Optional[float] = None) -> jax.Array:
    """q [B, Hq, R, D] against k/v [B, Hkv, S, D], positions
    `< lengths[b]` for every one of the R rows -> [B, Hq, R, D]; zeros
    where `lengths[b]` is zero.  `block` defaults to `block_len`'s.  With
    `k_rope` [B, Hkv / 2, S, 2 x Dr] (two KV heads' further Dr key values
    a row) and `q_rope` [B, Hq, R, Dr] the key is D + Dr wide, the value
    still D; `sink` [Hq] float32 is a logit a head in the softmax's
    denominator; `scale` defaults to D ** -0.5."""
    b, hq, s_q, d = q.shape
    _, hkv, s, _ = k_cache.shape
    if block is None:
        block = block_len(hkv, d, s, k_cache.dtype.itemsize)
    if block is None or s % block:
        raise ValueError(f'no KV block for Hkv={hkv} D={d} S={s}')
    group = hq // hkv * s_q
    rows = -(-group // _Q_ROWS) * _Q_ROWS

    def kv_rows(t):
        # Query head h reads kv head h // group: [B, Hkv, group (x R), .],
        # padded with rows that are computed and never read.
        return jnp.pad(t.reshape(b, hkv, group, t.shape[-1]),
                       ((0, 0), (0, 0), (0, rows - group), (0, 0)))

    q = kv_rows(q)
    n_blocks = s // block

    lengths = lengths.astype(jnp.int32)
    row_spec = pl.BlockSpec((1, hkv, rows, d), lambda i, j, *_: (i, 0, 0, 0))
    kv_spec = pl.BlockSpec((1, hkv, block, d), _kv_index)
    operands, in_specs, options = [q, k_cache, v_cache], [row_spec, kv_spec,
                                                          kv_spec], {}
    if k_rope is not None:
        wide = k_rope.shape[-1]
        operands += [pack_rope_queries(kv_rows(q_rope)), k_rope]
        in_specs += [
            pl.BlockSpec((1, hkv // 2, 2 * rows, wide),
                         lambda i, j, *_: (i, 0, 0, 0)),
            pl.BlockSpec((1, hkv // 2, block, wide), _kv_index)]
        options['rope'] = True
    if sink is not None:
        # A head's logit on each of its rows, lane-broadcast.
        per_row = jnp.repeat(sink.astype(jnp.float32).reshape(hkv, -1), s_q,
                             axis=1)
        operands.append(jnp.broadcast_to(jnp.pad(
            per_row, ((0, 0), (0, rows - group)))[:, :, None],
            (hkv, rows, 128)))
        in_specs.append(pl.BlockSpec((hkv, rows, 128),
                                     lambda i, j, *_: (0, 0, 0)))
        options['sink'] = True
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale or d**-0.5, block=block,
                          **options),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, n_blocks),
            in_specs=in_specs,
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM((hkv, rows, 128), jnp.float32),
                pltpu.VMEM((hkv, rows, 128), jnp.float32),
                pltpu.VMEM((hkv, rows, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary')),
        name='decode_attention',
        interpret=interpret,
    )(lengths, *resident_tiles(lengths, block), *operands)
    return out[:, :, :group].reshape(b, hq, s_q, d)
