"""Pallas TPU flash-attention kernels (forward + backward).

Forward: canonical TPU pattern — 3D grid (batch*heads, q_blocks, k_blocks)
with the k dimension innermost; Mosaic iterates the last grid axis
sequentially on the core, so VMEM scratch (running max `m`, denominator
`l`, accumulator `acc`) persists across k steps of one q block.  Causal
blocks strictly above the diagonal are skipped with `pl.when` (no MXU work
issued).  With `return_residuals=True` the kernel also emits the row
logsumexp, stored lane-broadcast as (bh, S, 128) f32 (the TPU layout
convention for per-row scalars) and compacted to (bh, S) outside.

Backward: flash-style recompute from (q, k, v, lse, delta), so nothing
O(S^2) ever lands in HBM.  `delta = rowsum(dO * O)` is the standard
softmax-backward correction and is computed in XLA (O(S*D), fuses into
the surrounding graph).  One kernel visits each (k block, q block) tile
once and makes everything the tile owes: five products and one
exponential.
  - Grid (bh, live tile): the tiles on or under the diagonal, a k
    block's together and its q blocks ascending, from a scalar-prefetched
    table that the index maps read, so that no grid step is spent (and
    no block fetched) above the diagonal.  Only the tiles the diagonal
    crosses run the mask.
  - A tile is computed turned, keys on the sublanes and queries on the
    lanes (s^T = k q^T): lse and delta arrive as (1, block_q) rows that
    broadcast down the sublanes, 2 KB a block each, and no lane-broadcast
    plane of either exists.
  - dk and dv accumulate in (block_k, D) float32 scratch across a k
    block's q sweep; dq accumulates (over k blocks ascending) into a
    float32 scratch of the pair's WHOLE dq, (S, D), resident for all of
    the pair's steps and written out once when the pair ends.
Where a pair's dq does not fit in VMEM (`fused_bwd_vmem_bytes` against
`_FUSED_BWD_VMEM_BUDGET`: a sequence several times 8,192) the backward is
the two kernels it was, chosen from the shapes alone:
  - dq:    grid (bh, q_blocks, k_blocks), k innermost, dq accumulates in
           VMEM scratch across the k sweep of one q block.
  - dk/dv: grid (bh, k_blocks, q_blocks), q innermost, dk/dv accumulate
           across the q sweep of one k block.
Each of the two makes every tile for itself (seven products and two
exponentials a tile) and takes lse and delta as (bh, S, 128) planes.

Sizing: q/k/v blocks live in VMEM ((block, D) each); with block=512 and
D=128 in bf16 that is 128 KB per operand, two buffers each.  A v5e core
has 128 MiB of VMEM and hands a kernel 16 MiB unless it says what it
needs: the forward and the two-kernel backward stay far under that; the
one-kernel backward counts what it holds (`fused_bwd_vmem_bytes`: 12 MiB
at S = 4,096, 16 at 8,192, 40 at 32,768, of which the pair's dq is 32)
and asks for that.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _band(qi, block_q: int, block_k: int, window: int):
    """(first, last) K block that q block `qi` sees under a causal window
    of `window` positions (i sees j iff i - window < j <= i)."""
    first = jnp.maximum(qi * block_q - (window - 1), 0) // block_k
    return first, (qi * block_q + block_q - 1) // block_k


def _fa_kernel(q_ref, k_ref, v_ref, *rest,
               scale: float, causal: bool, block_q: int, block_k: int,
               with_lse: bool = False, mask_block: int = 1,
               window: int = 0, sink: bool = False):
    rest = list(rest)
    sink_ref = rest.pop(0) if sink else None
    o_ref = rest.pop(0)
    if with_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        lse_ref = None
        m_scr, l_scr, acc_scr = rest
    qi = pl.program_id(1)
    step = kj = pl.program_id(2)
    n_k = pl.num_programs(2)
    if window:
        # The grid's last axis counts the K blocks of a q block's band,
        # not all of them: step `step` is block first + step (the index
        # map fetches that one, and the band's last one again past its
        # end).
        first, last = _band(qi, block_q, block_k, window)
        kj = first + step

    @pl.when(step == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Causal: whole block above the diagonal contributes nothing.  Under
    # the mask by blocks (`mask_block` > 1: position i sees j iff
    # j // mask_block <= i // mask_block) the diagonal is that of the
    # mask's blocks; at 1 the program is the causal one, as it was.
    if mask_block > 1:
        diag_ok = (not causal) or (
            (kj * block_k) // mask_block <=
            (qi * block_q + block_q - 1) // mask_block)
    elif window:
        diag_ok = kj <= last
    else:
        diag_ok = (not causal) or (kj * block_k <= qi * block_q + block_q - 1)

    @pl.when(diag_ok)
    def _compute():
        q = q_ref[0]                                   # (block_q, D)
        k = k_ref[0]                                   # (block_k, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        if causal:
            q_pos = (qi * block_q +
                     jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
            k_pos = (kj * block_k +
                     jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
            if mask_block > 1:
                q_pos, k_pos = q_pos // mask_block, k_pos // mask_block
            seen = q_pos >= k_pos
            if window:
                seen = seen & (q_pos - k_pos < window)
            s = jnp.where(seen, s, _NEG_INF)
        m_prev = m_scr[:]                              # (bq, 128)
        m_cur = jnp.max(s, axis=-1, keepdims=True)     # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)             # broadcast → (bq,128)
        p = jnp.exp(s - m_new[:, :1])                  # (bq, bk)
        correction = jnp.exp(m_prev[:, :1] - m_new[:, :1])  # (bq, 1)
        l_scr[:] = l_scr[:] * correction + jnp.sum(
            p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * correction + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    @pl.when(step == n_k - 1)
    def _finalize():
        # Rows with an all-masked history keep l=0; emit 0 instead of NaN.
        l = l_scr[:, :1]
        if sink:
            # The head's sink logit joins the denominator, with no value.
            l = l + jnp.exp(sink_ref[0][:, :1] - m_scr[:, :1])
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        if lse_ref is not None:
            # lse = m + log(l); +inf for all-masked rows so the backward's
            # exp(s - lse) underflows to exactly 0 there.
            lse = jnp.where(l == 0.0, jnp.inf, m_scr[:, :1] + jnp.log(safe_l))
            lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


@functools.partial(jax.jit,
                   static_argnames=('causal', 'block_size', 'interpret',
                                    'return_residuals', 'mask_block',
                                    'window'))
def flash_attention_fwd(q: jax.Array,
                        k: jax.Array,
                        v: jax.Array,
                        causal: bool = True,
                        block_size: int = 512,
                        interpret: bool = False,
                        return_residuals: bool = False,
                        mask_block: int = 1,
                        window: int = 0,
                        sink=None):
    """q [B,Hq,S,D], k [B,Hkv,S,D], v [B,Hkv,S,Dv] → [B,Hq,S,Dv].  GQA via
    head repeat (broadcast, fused by XLA before the kernel).  Dv may
    differ from D (latent attention's prefill: keys of 192, values of
    128); the backward kernels take Dv = D only.  With
    `return_residuals=True` also returns the row logsumexp [B,Hq,S] f32
    for the backward kernels.  `mask_block` B > 1 (with `causal`) is the
    mask by blocks of generation by diffusion over blocks: position i sees
    j iff j // B <= i // B, both ways inside a block and causal from block
    to block; the backward kernels are causal only.  `window` W > 0 (with
    `causal`) is a sliding window: i sees j iff i - W < j <= i, and only
    the K blocks of a q block's band are fetched or multiplied, O(S x W)
    work.  `sink` [Hq] float32 is a logit a head that joins the softmax's
    denominator and brings no value.  Both are forward only."""
    b, hq, s, d = q.shape
    dv = v.shape[-1]
    hkv = k.shape[1]
    if hkv != hq:
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
    scale = d**-0.5
    block_q = min(block_size, s)
    block_k = min(block_size, s)
    if s % block_q or s % block_k:
        raise ValueError(f'seq len {s} must divide block size {block_q}')
    q3 = q.reshape(b * hq, s, d)
    k3 = k.reshape(b * hq, s, d)
    v3 = v.reshape(b * hq, s, dv)
    grid = (b * hq, s // block_q, s // block_k)
    options = {'mask_block': mask_block} if mask_block > 1 else {}
    kv_block = lambda bh, qi, kj: (bh, kj, 0)  # noqa: E731
    operands = [q3, k3, v3]
    if window:
        if not causal or mask_block > 1 or return_residuals:
            raise ValueError('a window is causal, forward only')
        # The most K blocks a q block's band holds.
        n_band = max((qi * block_q + block_q - 1) // block_k -
                     max(qi * block_q - (window - 1), 0) // block_k + 1
                     for qi in range(grid[1]))
        grid = grid[:2] + (n_band,)
        options['window'] = window

        def kv_block(bh, qi, kj):
            first, last = _band(qi, block_q, block_k, window)
            return (bh, jnp.minimum(first + kj, last), 0)
    if sink is not None:
        operands.append(jnp.broadcast_to(
            jnp.tile(sink.astype(jnp.float32), b)[:, None, None],
            (b * hq, 1, 128)))
        options['sink'] = True
    kernel = functools.partial(_fa_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               with_lse=return_residuals, **options)
    out_specs = pl.BlockSpec((1, block_q, dv), lambda bh, qi, kj: (bh, qi, 0))
    out_shape = jax.ShapeDtypeStruct((b * hq, s, dv), q.dtype)
    if return_residuals:
        out_specs = [
            out_specs,
            pl.BlockSpec((1, block_q, 128), lambda bh, qi, kj: (bh, qi, 0)),
        ]
        out_shape = [
            out_shape,
            jax.ShapeDtypeStruct((b * hq, s, 128), jnp.float32),
        ]
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_block),
            pl.BlockSpec((1, block_k, dv), kv_block),
        ] + ([pl.BlockSpec((1, 1, 128), lambda bh, qi, kj: (bh, 0, 0))]
             if sink is not None else []),
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),     # denominator l
            pltpu.VMEM((block_q, dv), jnp.float32),    # output accumulator
        ],
        cost_estimate=pl.CostEstimate(
            flops=(2 * b * hq * s * min(s, window) * (d + dv) if window else
                   2 * b * hq * s * s * (d + dv) // (2 if causal else 1)),
            bytes_accessed=(q3.size + k3.size + v3.size) * q.dtype.itemsize,
            transcendentals=b * hq * s * (min(s, window) if window else s),
        ),
        interpret=interpret,
    )(*operands)
    if return_residuals:
        o, lse = out
        return o.reshape(b, hq, s, dv), lse[:, :, 0].reshape(b, hq, s)
    return out.reshape(b, hq, s, dv)


# ----- backward ---------------------------------------------------------------


def _recompute_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    qi, kj, *, scale, causal, block_q, block_k):
    """Shared backward recompute: p = softmax tile from saved lse, and
    ds = p * (dO·V^T - delta) * scale.  Both bwd kernels consume these;
    keeping the mask/scale arithmetic in one place keeps dq consistent
    with dk/dv by construction."""
    q = q_ref[0]                                   # (bq, D)
    k = k_ref[0]                                   # (bk, D)
    v = v_ref[0]                                   # (bk, D)
    do = do_ref[0]                                 # (bq, D)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (bq, bk)
    if causal:
        q_pos = (qi * block_q +
                 jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
        k_pos = (kj * block_k +
                 jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
    p = jnp.exp(s - lse_ref[0][:, :1])             # (bq, bk)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # (bq, bk)
    ds = p * (dp - delta_ref[0][:, :1]) * scale    # (bq, bk)
    return p, ds


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dq_scr, *, scale: float, causal: bool,
                      block_q: int, block_k: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    diag_ok = (not causal) or (kj * block_k <= qi * block_q + block_q - 1)

    @pl.when(diag_ok)
    def _compute():
        _, ds = _recompute_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                delta_ref, qi, kj, scale=scale,
                                causal=causal, block_q=block_q,
                                block_k=block_k)
        k = k_ref[0]
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bq, D)

    @pl.when(kj == n_k - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                       causal: bool, block_q: int, block_k: int):
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    # Causal: a q block strictly before the k block attends to none of it.
    diag_ok = (not causal) or (qi * block_q + block_q - 1 >= kj * block_k)

    @pl.when(diag_ok)
    def _compute():
        p, ds = _recompute_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                delta_ref, qi, kj, scale=scale,
                                causal=causal, block_q=block_q,
                                block_k=block_k)
        q = q_ref[0]
        do = do_ref[0]
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bk, D)
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bk, D)

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


# What the one-kernel backward may ask of VMEM (a v5e core has 128 MiB;
# a kernel gets 16 MiB unless it says what it needs), and what the
# compiler holds at once for a visit, in float32 tiles: s^T, p^T, dp^T,
# ds^T, and at half a tile each p^T and ds^T in the compute type and ds
# turned for dq's product.
_FUSED_BWD_VMEM_BUDGET = 48 << 20
_TILE_TEMPORARIES = 6


def fused_bwd_vmem_bytes(s: int, d: int, block_q: int, block_k: int,
                         itemsize: int) -> int:
    """The VMEM `_fa_bwd_kernel` holds for one (batch, head) pair: the
    pair's whole dq as a float32 sum beside its output block's two
    buffers, the blocks' two buffers each (q, dO; k, v, dk, dv), the two
    statistics (a row pads to 8 sublanes), the dk and dv sums, and the
    tile's temporaries."""
    return (s * d * (4 + 2 * itemsize) +
            2 * (2 * block_q + 4 * block_k) * d * itemsize +
            2 * 2 * 8 * block_q * 4 +
            2 * block_k * d * 4 +
            _TILE_TEMPORARIES * block_q * block_k * 4)


def _live_tiles(n_q: int, n_k: int, block_q: int, block_k: int,
                causal: bool):
    """(k block, q block) of every tile in which some query sees some
    key, a k block's tiles together and its q blocks ascending: the order
    the grid visits them in."""
    return [(kj, qi) for kj in range(n_k) for qi in range(n_q)
            if not causal or kj * block_k <= qi * block_q + block_q - 1]


def _fa_bwd_kernel(kj_ref, qi_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr,
                   *, scale: float, causal: bool, block_q: int, block_k: int):
    """One visit of tile (kj, qi): everything the tile owes.  The tile is
    computed turned, keys on the sublanes and queries on the lanes, so
    that a query's lse and delta are a row's lanes."""
    t = pl.program_id(1)
    n_t = pl.num_programs(1)
    kj, qi = kj_ref[t], qi_ref[t]
    n_q = dq_scr.shape[0] // block_q
    first_q = (kj * block_k) // block_q if causal else 0

    @pl.when(t == 0)
    def _init_pair():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(qi == first_q)
    def _init_sweep():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def visit(masked: bool):
        q = q_ref[0]                                   # (bq, D)
        k = k_ref[0]                                   # (bk, D)
        do = do_ref[0]                                 # (bq, D)
        st = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bk, bq)
        if masked:
            ahead = (jax.lax.broadcasted_iota(jnp.int32, st.shape, 1) -
                     jax.lax.broadcasted_iota(jnp.int32, st.shape, 0))
            st = jnp.where(ahead >= kj * block_k - qi * block_q, st,
                           _NEG_INF)
        pt = jnp.exp(st - lse_ref[0])                  # (bk, bq)
        dpt = jax.lax.dot_general(
            v_ref[0], do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bk, bq)
        dst = (pt * (dpt - delta_ref[0]) * scale).astype(q.dtype)
        dv_scr[:] += jax.lax.dot_general(
            pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bk, D)
        dk_scr[:] += jax.lax.dot_general(
            dst, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bk, D)
        rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
        dq_scr[rows, :] += jax.lax.dot_general(
            dst, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bq, D)

    if causal:
        # Only a tile the diagonal crosses has anything to mask: one whose
        # last key lies past its first query.
        crossed = kj * block_k + block_k - 1 > qi * block_q
        pl.when(crossed)(lambda: visit(True))
        pl.when(jnp.logical_not(crossed))(lambda: visit(False))
    else:
        visit(False)

    @pl.when(qi == n_q - 1)
    def _end_sweep():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    @pl.when(t == n_t - 1)
    def _end_pair():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_one_kernel(q3, k3, v3, do3, lse, delta, *, scale, causal, block_q,
                    block_k, interpret):
    """Each live tile once: grid (pair, live tile), the tiles' blocks from
    a table the index maps read, so that no step is spent above the
    diagonal.  lse and delta [B x H, S] go in as rows."""
    bh, s, d = q3.shape
    tiles = _live_tiles(s // block_q, s // block_k, block_q, block_k, causal)
    kj_of = jnp.asarray([kj for kj, _ in tiles], jnp.int32)
    qi_of = jnp.asarray([qi for _, qi in tiles], jnp.int32)
    q_spec = pl.BlockSpec((1, block_q, d),
                          lambda p, t, kj_of, qi_of: (p, qi_of[t], 0))
    kv_spec = pl.BlockSpec((1, block_k, d),
                           lambda p, t, kj_of, qi_of: (p, kj_of[t], 0))
    row_spec = pl.BlockSpec((1, 1, block_q),
                            lambda p, t, kj_of, qi_of: (p, 0, qi_of[t]))
    pair_spec = pl.BlockSpec((1, s, d), lambda p, t, kj_of, qi_of: (p, 0, 0))
    live = len(tiles) * block_q * block_k
    return pl.pallas_call(
        functools.partial(_fa_bwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, len(tiles)),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
            out_specs=[pair_spec, kv_spec, kv_spec],
            scratch_shapes=[pltpu.VMEM((s, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), q3.dtype),
                   jax.ShapeDtypeStruct((bh, s, d), k3.dtype),
                   jax.ShapeDtypeStruct((bh, s, d), v3.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary'),
            vmem_limit_bytes=fused_bwd_vmem_bytes(
                s, d, block_q, block_k, q3.dtype.itemsize)),
        cost_estimate=pl.CostEstimate(
            flops=5 * 2 * bh * live * d,
            bytes_accessed=(7 * q3.size * q3.dtype.itemsize + 8 * bh * s),
            transcendentals=bh * live),
        interpret=interpret,
    )(kj_of, qi_of, q3, k3, v3, do3, lse.reshape(bh, 1, s),
      delta.reshape(bh, 1, s))


def _bwd_two_kernels(q3, k3, v3, do3, lse, delta, *, scale, causal, block_q,
                     block_k, interpret):
    """A pair's dq does not fit in VMEM: a dq kernel and a dk/dv kernel,
    each making every tile for itself, the statistics lane-broadcast to
    the (bh, S, 128) scalar-row convention."""
    bh, s, d = q3.shape
    delta3 = jnp.broadcast_to(delta[:, :, None], (bh, s, 128))
    lse3 = jnp.broadcast_to(lse[:, :, None], (bh, s, 128))

    q_spec = pl.BlockSpec((1, block_q, d), lambda bh_, i, j: (bh_, i, 0))
    row_spec = pl.BlockSpec((1, block_q, 128), lambda bh_, i, j: (bh_, i, 0))
    flops = 5 * bh * s * s * d // (2 if causal else 1)
    io_bytes = (q3.size * 4 + do3.size * 2) * q3.dtype.itemsize

    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(bh, s // block_q, s // block_k),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, block_k, d), lambda bh_, qi, kj: (bh_, kj, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh_, qi, kj: (bh_, kj, 0)),
            q_spec,
            row_spec,
            row_spec,
        ],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q3.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=3 * flops // 5, bytes_accessed=io_bytes,
            transcendentals=bh * s * s),
        interpret=interpret,
    )(q3, k3, v3, do3, lse3, delta3)

    # dk/dv sweep: q innermost so the (bk, D) accumulators persist.
    kv_spec = pl.BlockSpec((1, block_k, d), lambda bh_, kj, qi: (bh_, kj, 0))
    q_spec_t = pl.BlockSpec((1, block_q, d), lambda bh_, kj, qi: (bh_, qi, 0))
    row_spec_t = pl.BlockSpec((1, block_q, 128),
                              lambda bh_, kj, qi: (bh_, qi, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(bh, s // block_k, s // block_q),
        in_specs=[q_spec_t, kv_spec, kv_spec, q_spec_t, row_spec_t,
                  row_spec_t],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), k3.dtype),
                   jax.ShapeDtypeStruct((bh, s, d), v3.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=2 * flops // 5, bytes_accessed=io_bytes,
            transcendentals=bh * s * s),
        interpret=interpret,
    )(q3, k3, v3, do3, lse3, delta3)
    return dq, dk, dv


@functools.partial(jax.jit,
                   static_argnames=('causal', 'block_size', 'interpret'))
def flash_attention_bwd(q: jax.Array,
                        k: jax.Array,
                        v: jax.Array,
                        out: jax.Array,
                        lse: jax.Array,
                        g: jax.Array,
                        causal: bool = True,
                        block_size: int = 512,
                        interpret: bool = False):
    """Flash backward.  q/out/g [B,Hq,S,D], k/v [B,Hkv,S,D],
    lse [B,Hq,S] f32.  Returns (dq, dk, dv) with dk/dv at Hkv heads —
    GQA grads are group-reduced here, mirroring the repeat this function
    performs on the way in.  One kernel where a pair's dq fits in VMEM by
    `fused_bwd_vmem_bytes`, two where it does not: the shapes decide.
    """
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    k_dtype, v_dtype = k.dtype, v.dtype
    if hkv != hq:
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
    scale = d**-0.5
    block_q = min(block_size, s)
    block_k = min(block_size, s)
    if s % block_q or s % block_k:
        raise ValueError(f'seq len {s} must divide block size {block_q}')
    bh = b * hq
    q3 = q.reshape(bh, s, d)
    do3 = g.reshape(bh, s, d)
    # delta = rowsum(dO * O): the softmax-backward correction term.  O(S*D)
    # in XLA.
    delta = jnp.sum(do3.astype(jnp.float32) *
                    out.reshape(bh, s, d).astype(jnp.float32), axis=-1)
    fits = fused_bwd_vmem_bytes(s, d, block_q, block_k,
                                q.dtype.itemsize) <= _FUSED_BWD_VMEM_BUDGET
    dq, dk, dv = (_bwd_one_kernel if fits else _bwd_two_kernels)(
        q3, k.reshape(bh, s, d), v.reshape(bh, s, d), do3,
        lse.reshape(bh, s), delta, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret)
    dq = dq.reshape(b, hq, s, d)
    dk = dk.reshape(b, hq, s, d)
    dv = dv.reshape(b, hq, s, d)
    if hkv != hq:
        # jnp.repeat(axis=1) laid heads out [h0,h0,...,h1,h1,...]; the
        # (hkv, group) reshape matches that layout exactly.
        group = hq // hkv
        dk = dk.reshape(b, hkv, group, s, d).sum(axis=2).astype(k_dtype)
        dv = dv.reshape(b, hkv, group, s, d).sum(axis=2).astype(v_dtype)
    return dq, dk, dv
