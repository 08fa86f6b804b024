"""Pallas TPU kernel for the decode step of latent attention (MLA, weight
absorbed): every query head of a slot against ONE shared key a position,
the cached latent `c_kv` [C] beside the rotated `k_pe` [R], of which the
latent is also the value.

    s[h, t] = q_lat[h] . c_kv[t] + q_pe[h] . k_pe[t]      (scale in q)
    o_lat[h] = sum_t softmax_t(s[h, :]) c_kv[t]

Fed to `decode_attention.py` as K = V the latent would be read twice; here
a tile `[block, C]` of it is fetched once and used for the scores and for
the weighted sum.  The lengths are scalar-prefetched and the grid is
(slot, block of positions), as in `decode_attention.py`: a tile's index is
clamped to the slot's last live block, so the steps past a slot's length
ask for the tile already there (no fetch) and compute nothing; a slot of
length zero computes nothing and returns zeros.  A block that lies whole
below the length is multiplied unmasked; the one that straddles it masks
its scores and zeroes its dead rows of the latent (they may be the
ragged end of the array: `block` need not divide the positions).  Online
softmax with float32 running max, sum and accumulator; bf16 in and out.

By count a position costs H x (C + R + C) x 2 operations for (C + R)
values read: 242 FLOP/B at 128 heads, 512 + 64 wide, bf16; a v5e's ridge
is 240.

Operand layout: a Mosaic call fixes its operands' layouts, so a program
that holds this kernel keeps the cache row-major `[B, S, C]` and
`[B, S, R]` with the width on the lanes and positions on the sublanes.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128
# Positions a tile ([1024, 512] bf16 is 1 MiB).  Alone on a v5e at 32 slots
# of 2,048-4,608 positions a call took 531, 419 and 373 us with tiles of
# 256, 512 and 1,024 (PERF.md section 6, PR 35): fewer grid steps outweigh
# what a longer tile fetches past a slot's length.
_MAX_BLOCK = 1024
_Q_ROWS = 16              # the bf16 sublane tile


def block_len(latent_dim: int, seq_len: int) -> Optional[int]:
    """Positions in a tile: `_MAX_BLOCK`, or all of a shorter cache; None
    where the tiling cannot take the shapes (the caller then reads the
    cache through XLA).  The last tile of a slot may be ragged."""
    if latent_dim % _LANES or seq_len % _LANES:
        return None
    return min(_MAX_BLOCK, seq_len)


def _kernel(lens_ref, ql_ref, qp_ref, c_ref, pe_ref, o_ref, m_scr, l_scr,
            acc_scr, *, block: int):
    b = pl.program_id(0)
    j = pl.program_id(1)
    length = lens_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def step(masked: bool):
        c = c_ref[0]                                   # (block, C)
        if masked:
            row = j * block + jax.lax.broadcasted_iota(jnp.int32, c.shape, 0)
            c = jnp.where(row < length, c, jnp.zeros_like(c))
        contract_last = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(ql_ref[0], c, contract_last,
                                 preferred_element_type=jnp.float32) +
             jax.lax.dot_general(qp_ref[0], pe_ref[0], contract_last,
                                 preferred_element_type=jnp.float32))
        if masked:                                     # (H, block)
            k_pos = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                         1)
            s = jnp.where(k_pos < length, s, _NEG_INF)
        m_prev = m_scr[:]                              # (H, 128)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])
        correction = jnp.exp(m_prev[:, :1] - m_new[:, :1])
        l_scr[:] = l_scr[:] * correction + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * correction + jnp.dot(
            p.astype(c.dtype), c, preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    whole = (j + 1) * block <= length
    pl.when(whole)(lambda: step(False))
    pl.when((j * block < length) & jnp.logical_not(whole))(
        lambda: step(True))

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        # A slot of length zero keeps l = 0: zeros, not NaN.
        l = l_scr[:, :1]
        o_ref[0] = (acc_scr[:] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=('block', 'interpret'))
def latent_decode_attention_fwd(q_lat: jax.Array, q_pe: jax.Array,
                                c_kv: jax.Array, k_pe: jax.Array,
                                lengths: jax.Array,
                                block: Optional[int] = None,
                                interpret: bool = False) -> jax.Array:
    """q_lat [B, H, C] and q_pe [B, H, R] (the softmax scale already in
    them) against c_kv [B, S, C] and k_pe [B, S, R], positions
    `< lengths[b]` -> o_lat [B, H, C].  `block` defaults to
    `block_len`'s."""
    b, h, c_dim = q_lat.shape
    _, s, r_dim = k_pe.shape
    if block is None:
        block = block_len(c_dim, s)
    if block is None or block % _Q_ROWS:
        raise ValueError(f'no block of positions for C={c_dim} S={s}')
    rows = -(-h // _Q_ROWS) * _Q_ROWS
    pad = ((0, 0), (0, rows - h), (0, 0))       # rows computed, never read
    q_lat = jnp.pad(q_lat.astype(c_kv.dtype), pad)
    q_pe = jnp.pad(q_pe.astype(k_pe.dtype), pad)
    n_blocks = pl.cdiv(s, block)

    def at(i, j, lens):
        last = jnp.maximum(pl.cdiv(lens[i], block) - 1, 0)
        return (i, jnp.minimum(j, last), 0)

    slot = lambda i, j, lens: (i, 0, 0)                 # noqa: E731
    out = pl.pallas_call(
        functools.partial(_kernel, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n_blocks),
            in_specs=[
                pl.BlockSpec((1, rows, c_dim), slot),
                pl.BlockSpec((1, rows, r_dim), slot),
                pl.BlockSpec((1, block, c_dim), at),
                pl.BlockSpec((1, block, r_dim), at),
            ],
            out_specs=pl.BlockSpec((1, rows, c_dim), slot),
            scratch_shapes=[
                pltpu.VMEM((rows, _LANES), jnp.float32),
                pltpu.VMEM((rows, _LANES), jnp.float32),
                pltpu.VMEM((rows, c_dim), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, rows, c_dim), c_kv.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary')),
        name='latent_decode_attention',
        interpret=interpret,
    )(lengths.astype(jnp.int32), q_lat, q_pe, c_kv, k_pe)
    return out[:, :h]
