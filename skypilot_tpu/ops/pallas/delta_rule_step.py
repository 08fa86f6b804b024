"""Pallas TPU kernel for a decode step of the gated delta rule: every
head's state tile is fetched once, updated in VMEM and written once, in
place.

Per head, with S in R^{dk x dv} float32 (`models/solar_open2.py
delta_rule_step`, which stays the reference and the fallback):

    decayed = exp(a)[:, None] * S
    u       = v - k^T decayed
    S_new   = decayed + (beta * k)[:, None] * u[None, :]
    o       = (q^T S_new) / sqrt(dk)

A step is bound by the bytes of the state (read once, written once); XLA
runs the function as dependent sweeps of the state in HBM, because it
cannot hold a head's tile between the reduction for `u` and the update
that needs it.  Here the grid is (slot, block of heads), a block's tiles
`[heads, dk, dv]` come in and go out through the pipeline's double
buffers, and the output state aliases the input: the engine donates the
cache and carries it through a scan of steps, so the new state lands in
the buffer the old one was read from.

Float32 on the vector unit throughout.  dk lies on a tile's sublanes and dv
on its lanes, so the two reductions over dk are sums of a tile's vregs and
then of 8 sublanes, `v`, `u` and `o` are lane vectors broadcast over
sublanes, and `exp(a)`, `k`, `beta * k` and `q` enter as COLUMNS (one value
a sublane row, broadcast over lanes).  The caller-side wrapper hands the
columns over already transposed, `[B, H / heads, dk, 4 * heads]` with the
operand and the head on the lanes (a few MB that XLA transposes in front of
the call), so the kernel's only cross-lane work is the broadcast of a
column it picks by a static lane index.

A row with `a = 0, beta = 0` (padding, an empty slot) leaves its state bit
for bit: `1 * S + 0 * u`.

Operand layout: a Mosaic call fixes its operands' layouts, so a program
that holds this kernel keeps the state row-major `[B, H, dk, dv]`, as the
engine's cache leaf is made.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_COLUMNS = 4              # exp(a), k, beta * k, q
# Of state a grid step (in and out, each double-buffered: four times this).
# The heads of a tile are unrolled: 16 of 64 KB ran at the time of a plain
# copy through the same pipeline (0.49 ms for 134 MB read and written), 8
# paid 0.15 ms more for twice the grid steps and 32 paid 0.10 ms more for
# the unrolled code (PERF.md section 6, PR 38).
_TILE_BYTES = 1 << 20
_VMEM_SLACK = 8 << 20     # the unrolled heads' temporaries


def block_heads(n_heads: int, dk: int, dv: int) -> Optional[int]:
    """Heads in a grid step: the largest divisor of `n_heads` whose four
    columns a head fit the 128 lanes and whose state tiles stay within
    `_TILE_BYTES`; None where the tiling cannot take the shapes (the
    caller then updates through XLA)."""
    if dk % _LANES or dv % _LANES:
        return None
    fits = [h for h in range(1, min(n_heads, _LANES // _COLUMNS) + 1)
            if n_heads % h == 0 and h * dk * dv * 4 <= _TILE_BYTES]
    return max(fits) if fits else None


def _kernel(cols_ref, rows_ref, s_ref, o_ref, s_out_ref, *, heads: int,
            scale: float):
    for h in range(heads):
        def col(p, h=h):              # [dk, 1]: one value a sublane row
            lane = p * heads + h
            return cols_ref[0, 0, :, lane:lane + 1]
        decayed = col(0) * s_ref[0, h]                          # [dk, dv]
        v = rows_ref[0, 0, h:h + 1, :]                          # [1, dv]
        u = v - jnp.sum(col(1) * decayed, axis=0, keepdims=True)
        new = decayed + col(2) * u
        s_out_ref[0, h] = new
        o_ref[0, 0, h:h + 1, :] = jnp.sum(col(3) * new, axis=0,
                                          keepdims=True) * scale


@functools.partial(jax.jit, static_argnames=('heads', 'interpret'))
def delta_rule_step_fwd(state: jax.Array, q: jax.Array, k: jax.Array,
                        v: jax.Array, a: jax.Array, beta: jax.Array,
                        heads: Optional[int] = None,
                        interpret: bool = False):
    """`delta_rule_step` for state [B, H, dk, dv] f32; q, k, a [B, H, dk];
    v [B, H, dv]; beta [B, H], all float32.  Returns (o [B, H, dv], the
    new state in the buffer of the old).  `heads` defaults to
    `block_heads`'s."""
    b, n_heads, dk, dv = state.shape
    largest = block_heads(n_heads, dk, dv)
    heads = heads or largest
    if (largest is None or n_heads % heads or _COLUMNS * heads > _LANES or
            state.dtype != jnp.float32):
        raise ValueError(f'no head block for state {state.shape} '
                         f'{state.dtype}')
    n_blocks = n_heads // heads
    f32 = jnp.float32
    q, k, v, a, beta = (t.astype(f32) for t in (q, k, v, a, beta))
    # [B, 4, H, dk] -> [B, H / heads, dk, 4 * heads]: lane p * heads + h of
    # block j is operand p of head j * heads + h.
    cols = jnp.stack([jnp.exp(a), k, beta[..., None] * k, q], axis=1)
    cols = cols.reshape(b, _COLUMNS, n_blocks, heads, dk)
    cols = cols.transpose(0, 2, 4, 1, 3).reshape(
        b, n_blocks, dk, _COLUMNS * heads)
    rows = v.reshape(b, n_blocks, heads, dv)
    tile = heads * dk * dv * 4
    o, new = pl.pallas_call(
        functools.partial(_kernel, heads=heads, scale=dk ** -0.5),
        grid=(b, n_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, dk, _COLUMNS * heads),
                         lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, heads, dv), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, heads, dk, dv), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, heads, dv), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, heads, dk, dv), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, n_blocks, heads, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel'),
            vmem_limit_bytes=4 * tile + _VMEM_SLACK),
        name='kda_state_update',
        interpret=interpret,
    )(cols, rows, state)
    return o.reshape(b, n_heads, dv), new
