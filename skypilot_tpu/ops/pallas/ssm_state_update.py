"""Pallas TPU kernel for a decode step of a Mamba-2 layer's state: every
head's state tile is fetched once, updated in VMEM and written once, in
place.

Per head, with S in R^{P x N} float32, a scalar decay `dA = exp(dt A)`
and a scalar step `dt` a head, `x` in R^P the head's input, `B` and `C` in
R^N shared by the heads of a group:

    S_new = dA * S + (dt x) B^T
    y     = S_new C + D x

(`models/granite_hybrid.py ssm_step`, which stays the reference and the
fallback).  A step is bound by the bytes of the state (read once, written
once: 2 MB a slot and layer at 64 heads of 64 x 128).

**The state's layout.**  `y` sums over N.  With N on a tile's lanes that
is a reduction across lanes a row of the tile, and it costs more than the
bytes: a kernel over `[B, H, P, N]` tiles took 907 us a call where a plain
copy in place takes 426 (PERF.md section 6, PR 43).  So the leaf is kept
with N on the SUBLANES and the channels on the lanes, `[B, H / pack, N,
pack * P]`: `pack` heads side by side fill a row of 128 lanes (two heads
of 64), lane `i * P + p` of group `g` is channel `p` of head `g * pack +
i`.  The sum over N is then a sum of a tile's vregs and one fold of 8
sublanes, `dA`, `dt x` and `D x` are lane vectors (ROWS: one value a
channel, broadcast over sublanes for nothing) and `y` leaves as a row;
only `B` and `C` are columns, the same for every head, broadcast over the
lanes once a grid step.

The grid is (slot, block of groups), a block's tiles `[groups, N, 128]`
come in and go out through the pipeline's double buffers, and the output
state aliases the input: the engine donates the cache and carries it
through a scan of steps, so the new state lands in the buffer the old one
was read from.  Float32 on the vector unit throughout.

A row with `dt = 0` (padding, an empty slot: `dA = 1`, `dt x = 0`) leaves
its state bit for bit: `1 * S + B * 0`.

Operand layout: a Mosaic call fixes its operands' layouts, so a program
that holds this kernel keeps the state row-major `[B, H / pack, N, 128]`,
as the engine's cache leaf is made.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8
# Of state a grid step (in and out, each double-buffered: four times this).
_TILE_BYTES = 1 << 20
_VMEM_SLACK = 8 << 20     # the unrolled groups' temporaries


def block_groups(n_groups: int, n: int, lanes: int) -> Optional[int]:
    """Groups of heads in a grid step for a state `[B, n_groups, n,
    lanes]`: the largest divisor of `n_groups` that is a whole number of
    sublane tiles (or all of them) and whose state tiles stay within
    `_TILE_BYTES`; None where the tiling cannot take the shapes (the
    caller then updates through XLA)."""
    if lanes != _LANES or n % _SUBLANES:
        return None
    fits = [g for g in range(1, n_groups + 1)
            if _divides(g, n_groups) and g * n * lanes * 4 <= _TILE_BYTES]
    return max(fits) if fits else None


def _divides(groups: int, n_groups: int) -> bool:
    """Whether blocks of `groups` tile the rows and `y` ([B, n_groups,
    lanes]: the groups lie on its sublanes)."""
    return n_groups % groups == 0 and (groups % _SUBLANES == 0 or
                                       groups == n_groups)


def _kernel(rows_ref, cols_ref, s_ref, y_ref, s_out_ref, *, groups: int):
    n, lanes = s_ref.shape[2:]
    # B and C: a value a sublane row, the same for every head.
    b_all = jnp.broadcast_to(cols_ref[0, :, 0:1], (n, lanes))
    c_all = jnp.broadcast_to(cols_ref[0, :, 1:2], (n, lanes))
    for g in range(groups):
        def row(q, g=g):              # [1, lanes]: one value a channel
            return rows_ref[0, q, g:g + 1, :]
        new = row(0) * s_ref[0, g] + b_all * row(1)             # [N, lanes]
        s_out_ref[0, g] = new
        y_ref[0, g:g + 1, :] = jnp.sum(new * c_all, axis=0,
                                       keepdims=True) + row(2)


@functools.partial(jax.jit, static_argnames=('groups', 'interpret'))
def ssm_state_update_fwd(state: jax.Array, decay: jax.Array, dtx: jax.Array,
                         dx: jax.Array, b: jax.Array, c: jax.Array,
                         groups: Optional[int] = None,
                         interpret: bool = False):
    """`ssm_step` for state [B, G, N, L] f32 (L = 128 lanes: `pack` heads'
    channels side by side); decay, dtx, dx [B, G, L] (`exp(dt A)`, `dt x`
    and `D x` a channel); b, c [B, N], all float32.  Returns (y [B, G, L],
    the new state in the buffer of the old).  `groups` defaults to
    `block_groups`'s."""
    n_slots, n_groups, n, lanes = state.shape
    largest = block_groups(n_groups, n, lanes)
    groups = groups or largest
    if (largest is None or not _divides(groups, n_groups) or
            state.dtype != jnp.float32):
        raise ValueError(f'no block of groups for state {state.shape} '
                         f'{state.dtype}')
    f32 = jnp.float32
    rows = jnp.stack([t.astype(f32) for t in (decay, dtx, dx)], axis=1)
    cols = jnp.stack([b.astype(f32), c.astype(f32)], axis=-1)   # [B, N, 2]
    tile = groups * n * lanes * 4
    y, new = pl.pallas_call(
        functools.partial(_kernel, groups=groups),
        grid=(n_slots, n_groups // groups),
        in_specs=[
            pl.BlockSpec((1, 3, groups, lanes), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec((1, n, 2), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, groups, n, lanes), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, groups, lanes), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, groups, n, lanes), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((n_slots, n_groups, lanes), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel'),
            vmem_limit_bytes=4 * tile + _VMEM_SLACK),
        name='ssm_state_update',
        interpret=interpret,
    )(rows, cols, state)
    return y, new
