"""Attention ops with a Pallas TPU fast path.

`mha_reference` is the XLA implementation (always correct, runs anywhere,
fuses well).  `flash_attention` dispatches to the Pallas online-softmax
kernel on TPU (`ops/pallas/flash_attention.py`) and falls back to the
reference elsewhere.  Backward of the Pallas path is the Pallas flash
backward (chunked recompute from saved logsumexp: O(S) memory, trades
FLOPs for HBM — the right trade on TPU where attention bwd is
bandwidth-bound; nothing O(S^2) is ever materialized in HBM).

Shapes: q [B, Hq, Sq, D], k/v [B, Hkv, Sk, D]; grouped-query attention is
expressed by Hq = G * Hkv (query heads grouped over kv heads).  The
reference folds the G query heads of a kv head into its query rows and
contracts against K and V as they are stored; it never repeats them.

`decode_attention` is the decode step's attention: one query row a head
against a slot's cache leaves, positions below the slot's length.  On one
TPU device, where `decode_kv_block` finds a tiling for the cache's shape
(head size a multiple of 128, `max_seq_len` a multiple of 128), it is the
Pallas kernel of `ops/pallas/decode_attention.py`, which fetches a slot's
K and V up to its length and nothing of an empty slot (a length of zero).
Elsewhere (the CPU, a mesh of several devices, other shapes) it is
`mha_reference` under the positions mask, which reads every position of
every slot.  Both give an empty slot zeros.  The choice hangs on the
backend and on shapes, as
`flash_attention`'s does.  `mha_reference` itself was left alone: prefill,
a chunk against a cache, the paged gather, verify and training keep the
programs they had, and the kernel's tests have their ground truth.

Two things a window layer brings (models/mimo_v2.py) ride the same three
functions, off by default so that every other program is the one it was:
a `window` W (position i sees j iff i - W < j <= i; the flash kernel
fetches and multiplies only the tiles of the band) and a `sink`, one
learned logit a query head that joins the softmax's denominator and
brings no value.  The decode step of such a model has keys wider than
values, in two leaves (`decode_attention`'s `k_rope`), and keeps a window
layer's cache as a ring of W positions that it bounds by min(length, W).

`latent_decode_attention` is the same step for latent attention (MLA with
the up-projection absorbed into the query): every head against one shared
key a position, the cached latent beside its rotated part, of which the
latent is also the value.  It is engaged the same way (`latent_kv_block`:
the backend, the mesh's size, the shapes) and is the kernel of
`ops/pallas/latent_decode_attention.py`, which fetches a tile of the latent
once for the scores and the weighted sum; elsewhere it is
`latent_attention_reference`, plain `jnp` over every position.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P


def mha_reference(q: jax.Array,
                  k: jax.Array,
                  v: jax.Array,
                  causal: bool = True,
                  scale: Optional[float] = None,
                  segment_positions: Optional[jax.Array] = None,
                  kv_positions: Optional[jax.Array] = None,
                  mask_block: int = 1,
                  window: int = 0,
                  sink: Optional[jax.Array] = None) -> jax.Array:
    """XLA multi-head attention (numerically the ground truth for the
    Pallas kernel's tests).

    segment_positions/kv_positions: optional absolute positions
    [B, Sq] / [B, Sk] for causal masking when q/k are *shards* of a longer
    sequence (ring attention uses this).  `mask_block` B > 1 makes the
    causal mask one by blocks: position i sees j iff j // B <= i // B.
    `window` W > 0 narrows the causal mask to i - W < j <= i.  `sink`
    [Hq] is a logit a query head in the softmax's denominator: it takes
    weight and gives no value.
    """
    orig_dtype = q.dtype
    scale = scale if scale is not None else q.shape[-1]**-0.5
    b, h_q, s_q, d = q.shape
    h_kv = k.shape[1]
    group = h_q // h_kv
    if group > 1:
        # GQA: query head h reads kv head h // group (the order jnp.repeat
        # over axis 1 gives), so the `group` query heads of one kv head
        # are `group * Sq` query rows of it, contracted against K and V as
        # they are stored: nothing [B, Hq, Sk, D] is ever built.
        q = q.reshape(b, h_kv, group * s_q, d)
    logits = jnp.einsum('bhqd,bhkd->bhqk', q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        if segment_positions is None:
            q_pos = jnp.arange(s_q)[None, :]
            k_pos = jnp.arange(k.shape[2])[None, :]
        else:
            q_pos = segment_positions
            k_pos = (kv_positions if kv_positions is not None
                     else segment_positions)
        if mask_block > 1:
            q_pos, k_pos = q_pos // mask_block, k_pos // mask_block
        mask = q_pos[:, None, :, None] >= k_pos[:, None, None, :]
        if window:
            mask &= (q_pos[:, None, :, None] - k_pos[:, None, None, :]
                     < window)
        if group > 1:
            # The mask is tiled, not the positions: comparing `group`
            # copies of the positions read 1% slower in Yi-6B's decode.
            mask = jnp.tile(mask, (1, 1, group, 1))
        logits = jnp.where(mask, logits, -jnp.inf)
    if sink is not None:
        # One more column a row, the head's own logit, dropped again
        # after the softmax: rows (g, i) of a kv head are query head g's.
        column = jnp.repeat(sink.astype(jnp.float32).reshape(h_kv, group),
                            s_q, axis=1)[None, :, :, None]
        logits = jnp.concatenate([logits, jnp.broadcast_to(
            column, logits.shape[:3] + (1,))], axis=-1)
    probs = jax.nn.softmax(logits, axis=-1)
    if sink is not None:
        probs = probs[..., :-1]
    # Fully-masked rows (possible for ring-attention shards) produce NaN
    # from softmax(-inf row); zero them so the combine step can ignore them.
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)
    out = jnp.einsum('bhqk,bhkd->bhqd', probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    if group > 1:
        out = out.reshape(b, h_q, s_q, v.shape[-1])
    return out.astype(orig_dtype)


def decode_kv_block(n_kv_heads: int, head_dim: int, seq_len: int,
                    dtype=jnp.bfloat16,
                    mesh: Optional[Mesh] = None) -> Optional[int]:
    """The positions one tile of `decode_attention`'s kernel covers for a
    cache `[B, n_kv_heads, seq_len, head_dim]`, or None where it reads the
    whole cache through XLA: off the TPU, under a mesh of several devices
    (XLA cannot partition a Mosaic call), or for shapes the kernel's
    tiling cannot take."""
    if jax.default_backend() != 'tpu':
        return None
    if mesh is not None and mesh.size > 1:
        return None
    from skypilot_tpu.ops.pallas import decode_attention as pallas_da
    return pallas_da.block_len(n_kv_heads, head_dim, seq_len,
                               jnp.dtype(dtype).itemsize)


def unpack_rope_keys(k_rope: jax.Array) -> jax.Array:
    """The leaf `k_rope` [B, Hkv / 2, S, 2 x R] (two KV heads' rotated
    values a row, `ops/pallas/decode_attention.py`) as [B, Hkv, S, R]."""
    b, pairs, s, wide = k_rope.shape
    return k_rope.reshape(b, pairs, s, 2, wide // 2).transpose(
        0, 1, 3, 2, 4).reshape(b, 2 * pairs, s, wide // 2)


def pack_rope_keys(k_rope: jax.Array) -> jax.Array:
    """[B, Hkv, S, R] -> the leaf's [B, Hkv / 2, S, 2 x R]."""
    b, h, s, r = k_rope.shape
    return k_rope.reshape(b, h // 2, 2, s, r).transpose(
        0, 1, 3, 2, 4).reshape(b, h // 2, s, 2 * r)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     lengths: jax.Array,
                     mesh: Optional[Mesh] = None,
                     q_rope: Optional[jax.Array] = None,
                     k_rope: Optional[jax.Array] = None,
                     sink: Optional[jax.Array] = None,
                     scale: Optional[float] = None) -> jax.Array:
    """The decode step's attention: q [B, Hq, R, D] against the cache
    leaves [B, Hkv, S, D] as they are stored, over the positions
    `< lengths[b]` (the rows written this step included).  R is 1 for a
    step of one token a slot, and a block's length for a pass over a
    block, whose R rows all read the same positions (inside a block
    nothing is masked).  A length of zero is a slot that holds no
    request: zeros.  A key wider than its value comes in two leaves:
    `k_rope` [B, Hkv / 2, S, 2 x Dr] holds its further Dr values, two KV
    heads' a row, and `q_rope` [B, Hq, R, Dr] the query's; `sink` [Hq]
    and `scale` (default D ** -0.5) as in `mha_reference`.  A ring of W
    positions is a cache of S = W whose caller passes min(length, W)."""
    b, h_kv, s, d = k_cache.shape
    block = decode_kv_block(h_kv, d, s, k_cache.dtype, mesh)
    if block is not None:
        from skypilot_tpu.ops.pallas import decode_attention as pallas_da
        return pallas_da.decode_attention_fwd(
            q, k_cache, v_cache, lengths, block=block, q_rope=q_rope,
            k_rope=k_rope, sink=sink, scale=scale)
    q_pos = (lengths - 1)[:, None]
    if q.shape[2] > 1:
        q_pos = jnp.broadcast_to(q_pos, (b, q.shape[2]))
    if k_rope is not None:
        q = jnp.concatenate([q, q_rope], axis=-1)
        k_cache = jnp.concatenate([k_cache, unpack_rope_keys(k_rope)],
                                  axis=-1)
    return mha_reference(
        q, k_cache, v_cache, causal=True, scale=scale, segment_positions=q_pos,
        kv_positions=jnp.broadcast_to(jnp.arange(s)[None, :], (b, s)),
        sink=sink)


def latent_kv_block(latent_dim: int, seq_len: int,
                    mesh: Optional[Mesh] = None) -> Optional[int]:
    """The positions one tile of `latent_decode_attention`'s kernel covers
    for a cache `[B, seq_len, latent_dim]`, or None where it reads the
    whole cache through XLA (as `decode_kv_block`)."""
    if jax.default_backend() != 'tpu':
        return None
    if mesh is not None and mesh.size > 1:
        return None
    from skypilot_tpu.ops.pallas import latent_decode_attention as pallas_la
    return pallas_la.block_len(latent_dim, seq_len)


def latent_attention_reference(q_lat: jax.Array, q_pe: jax.Array,
                               c_kv: jax.Array, k_pe: jax.Array,
                               lengths: jax.Array) -> jax.Array:
    """XLA latent attention over every position of every slot, masked to
    `< lengths[b]` (the ground truth of the kernel's tests).  A slot of
    length zero gives zeros."""
    scores = (jnp.einsum('bhc,bsc->bhs', q_lat, c_kv,
                         preferred_element_type=jnp.float32) +
              jnp.einsum('bhr,bsr->bhs', q_pe, k_pe,
                         preferred_element_type=jnp.float32))
    live = jnp.arange(c_kv.shape[1])[None, :] < lengths[:, None]
    probs = jax.nn.softmax(jnp.where(live[:, None, :], scores, -jnp.inf),
                           axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)
    return jnp.einsum('bhs,bsc->bhc', probs.astype(c_kv.dtype), c_kv,
                      preferred_element_type=jnp.float32).astype(c_kv.dtype)


def latent_decode_attention(q_lat: jax.Array, q_pe: jax.Array,
                            c_kv: jax.Array, k_pe: jax.Array,
                            lengths: jax.Array,
                            mesh: Optional[Mesh] = None) -> jax.Array:
    """The decode step of latent attention: q_lat [B, H, C] and q_pe
    [B, H, R], the softmax scale already in them, against the cache
    leaves c_kv [B, S, C] and k_pe [B, S, R] as they are stored, over the
    positions `< lengths[b]` (the row written this step included) ->
    o_lat [B, H, C], the weighted sum of the latent a head."""
    q_lat, q_pe = q_lat.astype(c_kv.dtype), q_pe.astype(k_pe.dtype)
    block = latent_kv_block(c_kv.shape[2], c_kv.shape[1], mesh)
    if block is not None:
        from skypilot_tpu.ops.pallas import latent_decode_attention as \
            pallas_la
        return pallas_la.latent_decode_attention_fwd(
            q_lat, q_pe, c_kv, k_pe, lengths, block=block)
    return latent_attention_reference(q_lat, q_pe, c_kv, k_pe, lengths)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q: jax.Array,
                    k: jax.Array,
                    v: jax.Array,
                    causal: bool = True,
                    block_size: int = 512,
                    mask_block: int = 1) -> jax.Array:
    """Flash attention: Pallas kernel on TPU, XLA reference elsewhere.
    `mask_block` B > 1 is the mask by blocks (`mha_reference`); at 1 the
    causal program as it was."""
    return _flash_fwd_impl(q, k, v, causal, block_size, mask_block)


def _flash_fwd_impl(q, k, v, causal, block_size, mask_block=1):
    if jax.default_backend() == 'tpu':
        from skypilot_tpu.ops.pallas import flash_attention as pallas_fa
        return pallas_fa.flash_attention_fwd(q, k, v, causal=causal,
                                             block_size=block_size,
                                             mask_block=mask_block)
    return mha_reference(q, k, v, causal=causal, mask_block=mask_block)


def _flash_fwd(q, k, v, causal, block_size, mask_block=1):
    # `out` and `lse` are named for a checkpoint around the caller
    # (models/llama.py keep_plan): a policy that keeps both keeps the
    # kernel out of the backward pass; either alone does not, the kernel
    # makes them together.  Outside a checkpoint a name is the identity.
    if jax.default_backend() == 'tpu':
        from skypilot_tpu.ops.pallas import flash_attention as pallas_fa
        out, lse = pallas_fa.flash_attention_fwd(
            q, k, v, causal=causal, block_size=block_size,
            return_residuals=True, mask_block=mask_block)
        out = checkpoint_name(out, 'attn_out')
        return out, (q, k, v, out, checkpoint_name(lse, 'attn_lse'))
    out = mha_reference(q, k, v, causal=causal, mask_block=mask_block)
    return checkpoint_name(out, 'attn_out'), (q, k, v, None, None)


def _flash_bwd(causal, block_size, mask_block, residuals, g):
    q, k, v, out, lse = residuals
    if out is None:
        # XLA path (non-TPU): recompute under vjp; XLA fuses this into a
        # bandwidth-friendly bwd.
        _, vjp_fn = jax.vjp(
            lambda q_, k_, v_: mha_reference(q_, k_, v_, causal=causal,
                                             mask_block=mask_block),
            q, k, v)
        return vjp_fn(g)
    if mask_block > 1:
        raise NotImplementedError(
            'the Pallas backward kernels are causal only: the mask by '
            'blocks is a serving path (no model trains under it here)')
    from skypilot_tpu.ops.pallas import flash_attention as pallas_fa
    # flash_attention_bwd returns dk/dv already group-reduced to Hkv heads.
    return pallas_fa.flash_attention_bwd(
        q, k, v, out, lse, g, causal=causal, block_size=block_size)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_on_mesh(q: jax.Array, k: jax.Array, v: jax.Array,
                            mesh: Optional[Mesh],
                            causal: bool = True,
                            mask_block: int = 1,
                            window: int = 0,
                            sink: Optional[jax.Array] = None) -> jax.Array:
    """`flash_attention` inside a program partitioned over `mesh`.

    XLA cannot partition a Mosaic kernel ("wrap the call in a
    shard_map"), so each device runs it on the rows it already holds:
    batch and heads over the axes the sharding rules give them — the
    layout the model's activations have.  A dimension its axes do not
    divide stays whole: every device then repeats that work, the answer
    is the same.

    With a `window` or a `sink` (`mha_reference`) it is the forward kernel
    alone, a serving path: on one TPU device the Pallas kernel, elsewhere
    (the CPU, a mesh of several devices) the XLA reference, which XLA can
    partition.
    """
    if window or sink is not None:
        if jax.default_backend() == 'tpu' and (mesh is None or
                                               mesh.size == 1):
            from skypilot_tpu.ops.pallas import flash_attention as pallas_fa
            return pallas_fa.flash_attention_fwd(
                q, k, v, causal=causal, window=window, sink=sink)
        return mha_reference(q, k, v, causal=causal, window=window,
                             sink=sink)
    if mesh is None or mesh.size == 1:
        return flash_attention(q, k, v, causal, 512, mask_block)
    return _flash_attention_sharded(q, k, v, mesh=mesh, causal=causal,
                                    mask_block=mask_block)


# Jitted like ring_attention, so that an eager caller (model.init under a
# mesh) compiles the shard_map once and not once a layer.
@functools.partial(jax.jit, static_argnames=('mesh', 'causal', 'mask_block'))
def _flash_attention_sharded(q, k, v, mesh, causal, mask_block=1):
    from skypilot_tpu.parallel import sharding as sharding_lib
    rules = dict(sharding_lib.DEFAULT_RULES)
    batch_axes = tuple(a for a in rules['batch'] if a in mesh.shape)
    head_axis = rules['heads']
    n_batch = math.prod(mesh.shape[a] for a in batch_axes)
    n_heads = mesh.shape.get(head_axis, 1)
    spec = P(batch_axes if q.shape[0] % n_batch == 0 else None,
             head_axis if (q.shape[1] % n_heads == 0 and
                           k.shape[1] % n_heads == 0) else None)
    return jax.shard_map(
        lambda q_, k_, v_: flash_attention(q_, k_, v_, causal, 512,
                                           mask_block),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)(q, k, v)
