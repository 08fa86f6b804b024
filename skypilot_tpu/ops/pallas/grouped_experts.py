"""Pallas TPU kernel for a decode step's expert layer: the few tokens of
a step against the held experts they reach, each expert read once.

With T tokens no more than one block of the expert loop, every reached
expert has one block and it holds, at most, every token.  Nothing is
sorted, gathered or scattered:

    out[T, D] = sum over reached experts e of
                (c_e * silu(x W_gate_e) * (x W_up_e)) W_down_e

where `c` [n_held, T] is each token's routing weight for e, zero where
the token did not choose it.  Such a step is bound by the bytes of the
weights it reads, so the kernel's one job is to keep that stream running:
the reached experts' rows of the stacks, compacted to the front, and their
count are scalar-prefetched, the grid is (n_held, F / tile_f) and the
weight tiles are addressed through the table, so the pipeline fetches
expert j+1's first tile while expert j's last is multiplied.  A grid step
past the count asks for the tile already there (no fetch) and computes
nothing (`pl.when`); an expert nobody chose is never read, and a step
that reaches none fetches one tile.

gate, up, SiLU, the weighting and the down product are fused over an F
tile; bf16 in, float32 products and a float32 sum in VMEM, written once.

Operand layout: a Mosaic call fixes its operands' layouts, so a program
that holds this kernel keeps the stacks row-major, `[n_held, D, F]` with F
on the lanes and `[n_held, F, D]` with D on the lanes, as they are made.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_ROWS = 16                # the bf16 sublane tile
# Of each of the three weight tiles a grid step.  A trip costs the same at
# every width (41-42 us for 31.5 MB); what a wider tile saves is grid
# steps, live and past the count: Solar-Open2's `tpot_p50_ms` read 7.71
# with a whole expert a step (1,280 columns, 63 MB of tiles in a v5e's
# 128 MiB of VMEM) and 7.78 with 256 columns (PERF.md section 6, PR 34).
_TILE_BYTES = 10 << 20
_VMEM_SLACK = 8 << 20     # products and what the compiler keeps besides


def tile_f(dim: int, ffn_dim: int, itemsize: int = 2) -> Optional[int]:
    """Columns of F in a grid step: the largest multiple of 128 that
    divides `ffn_dim` and keeps a `[D, tile]` weight tile within
    `_TILE_BYTES`; None where the tiling cannot take the shapes (the
    caller then multiplies through the block loop)."""
    if dim % _LANES or ffn_dim % _LANES:
        return None
    fits = [t for t in range(_LANES, ffn_dim + 1, _LANES)
            if ffn_dim % t == 0 and dim * t * itemsize <= _TILE_BYTES]
    return max(fits) if fits else None


def _kernel(rows_ref, count_ref, x_ref, c_ref, gate_ref, up_ref, down_ref,
            o_ref):
    del rows_ref                                  # read by the index maps
    j = pl.program_id(0)
    f = pl.program_id(1)

    # The output's one block stays in VMEM over the whole grid: it is the
    # float32 sum, written back once after the last step.
    @pl.when((j == 0) & (f == 0))
    def _init():
        o_ref[:] = jnp.zeros_like(o_ref)

    @pl.when(j < count_ref[0])
    def _compute():
        x = x_ref[:]                                        # (T, D)
        gate = jnp.dot(x, gate_ref[0], preferred_element_type=jnp.float32)
        up = jnp.dot(x, up_ref[0], preferred_element_type=jnp.float32)
        h = jax.nn.silu(gate) * up * c_ref[0][:, :1]        # (T, tile) f32
        o_ref[:] += jnp.dot(h.astype(down_ref.dtype), down_ref[0],
                            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=('tile', 'interpret'))
def grouped_experts_fwd(x: jax.Array, c: jax.Array, rows: jax.Array,
                        count: jax.Array, w_gate: jax.Array,
                        w_up: jax.Array, w_down: jax.Array,
                        tile: Optional[int] = None,
                        interpret: bool = False) -> jax.Array:
    """x [T, D] against the stacks w_gate / w_up [n_held, D, F] and w_down
    [n_held, F, D] -> [T, D] float32.  `c` [n_held, T] float32: token t's
    weight for the expert of row e, zero where it did not choose it.
    `rows` [n_held] int32: the rows to multiply, first `count` (a scalar)
    of them; what stands behind is not read.  `tile` defaults to
    `tile_f`'s."""
    t, d = x.shape
    n_held, _, ffn = w_gate.shape
    if tile is None:
        tile = tile_f(d, ffn, w_gate.dtype.itemsize)
    if tile is None or ffn % tile or tile % _LANES or d % _LANES:
        raise ValueError(f'no F tile for D={d} F={ffn}')
    n_f = ffn // tile
    t_pad = -(-t // _ROWS) * _ROWS
    x = jnp.pad(x.astype(w_gate.dtype), ((0, t_pad - t), (0, 0)))
    # A token's weight on every lane of its row: the kernel reads a
    # column of it against the (T, tile) product.
    c = jnp.broadcast_to(
        jnp.pad(c.astype(jnp.float32), ((0, 0), (0, t_pad - t)))[..., None],
        (n_held, t_pad, _LANES))
    count = jnp.reshape(count, (1,)).astype(jnp.int32)

    def at(j, f, rows, count):
        """(stack row, F tile) of grid step (j, f); past the count, the
        last tile that was fetched, again."""
        live = j < count[0]
        last = jnp.maximum(count[0] - 1, 0)
        return rows[jnp.where(live, j, last)], jnp.where(live, f, n_f - 1)

    def up_index(*step):
        row, f_tile = at(*step)
        return row, 0, f_tile

    def down_index(*step):
        row, f_tile = at(*step)
        return row, f_tile, 0

    whole = lambda *step: (0, 0)                        # noqa: E731
    itemsize = w_gate.dtype.itemsize
    vmem = (2 * 3 * d * tile * itemsize +               # the weight tiles
            2 * t_pad * d * itemsize +                  # x
            2 * t_pad * _LANES * 4 +                    # c
            2 * t_pad * d * 4 +                         # the sum
            _VMEM_SLACK)
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_held, n_f),
            in_specs=[
                pl.BlockSpec((t_pad, d), whole),
                pl.BlockSpec((1, t_pad, _LANES),
                             lambda *step: (at(*step)[0], 0, 0)),
                pl.BlockSpec((1, d, tile), up_index),
                pl.BlockSpec((1, d, tile), up_index),
                pl.BlockSpec((1, tile, d), down_index),
            ],
            out_specs=pl.BlockSpec((t_pad, d), whole)),
        out_shape=jax.ShapeDtypeStruct((t_pad, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary', 'arbitrary'),
            vmem_limit_bytes=vmem),
        name='grouped_experts',
        interpret=interpret,
    )(rows.astype(jnp.int32), count, x, c, w_gate, w_up, w_down)
    return out[:t]
