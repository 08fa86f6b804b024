"""The output head and the next-token loss, a chunk of rows at a time.

`lm_loss` is handed the logits whole: `[B, S, V]` in float32 beside their
gradient in the compute type, 6 B a logit, alive when everything a block
kept for the backward pass is alive too (6.29 GB of one v5e at 4 x 4,096
tokens over 64,000 words).  `chunked_lm_loss` takes the hidden state in
front of the head and the head's weights instead, walks the sequence in
chunks and makes, in ONE visit of a chunk, its logits, its loss terms and
its gradients.  The loss is a scalar, so its gradients are linear in the
cotangent: the forward pass stores them and the backward rule scales
them, and no product of the head runs twice.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from skypilot_tpu.parallel.sharding import BATCH_AXES

# What one chunk's logits and their gradient may take on a device.  Of the
# order of one block's working set; larger chunks visit the head's float32
# gradient less often (it is read and written once a chunk).
_CHUNK_BYTES = 800 * 2 ** 20


class LossChunks(NamedTuple):
    positions: int      # of a row, in one chunk; divides the sequence
    shards: int         # the devices the batch's rows divide over
    logit_bytes: int    # one chunk's logits and gradient on one device


def loss_chunks(mesh, batch: int, seq: int, vocab: int,
                act_bytes: int) -> LossChunks:
    """How `chunked_lm_loss` walks a [batch, seq] step: the largest
    divisor of `seq` whose chunk of logits stays under `_CHUNK_BYTES` on
    one device, the float32 logits beside their gradient in the compute
    type.  Rows divide over the mesh's batch axes and the vocabulary
    over 'tensor', where they divide at all.  A step whose logits fit
    is one chunk."""
    mesh_shape = dict(mesh.shape) if mesh is not None else {}
    shards = math.prod(mesh_shape.get(a, 1) for a in BATCH_AXES)
    if batch % shards:
        shards = 1
    tp = mesh_shape.get('tensor', 1)
    words = vocab // tp if vocab % tp == 0 else vocab
    per_position = batch // shards * words * (4 + act_bytes)
    positions = max((c for c in range(1, seq + 1)
                     if seq % c == 0 and c * per_position <= _CHUNK_BYTES),
                    default=1)
    return LossChunks(positions, shards, positions * per_position)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def chunked_lm_loss(hidden: jax.Array, head: jax.Array, tokens: jax.Array,
                    chunk: int, tied: bool = False,
                    shards: int = 1) -> jax.Array:
    """`lm_loss(head(hidden), tokens)` without the logits whole.

    hidden [B, S, D] is the state after the final norm, in the compute
    type; `head` the head's kernel [D, V] or, `tied`, the embedding table
    [V, D]; tokens [B, S]; `chunk` divides S.  The product runs in the
    hidden state's type with a float32 result; logsumexp, loss and the
    logits' gradient in float32, the gradient cast to the compute type
    before its two products; the head's gradient summed over the chunks
    in float32.  The last position of a row has no target and takes
    weight zero.  `shards` divides B: the head's gradient is summed a
    group of rows apart and the groups added after the last chunk, so
    that rows on different devices meet in one reduction a step and not
    one a chunk."""
    return _forward(hidden, head, tokens, chunk, tied, shards)[0]


def _forward(hidden, head, tokens, chunk, tied, shards):
    b, s, d = hidden.shape
    if s % chunk or b % shards:
        raise ValueError(f'{chunk} positions a chunk, {shards} groups of '
                         f'rows: they do not divide {hidden.shape}')
    dtype = hidden.dtype
    n = s // chunk
    # 'v' is the vocabulary's axis of the head as the parameter tree
    # holds it.
    w = 'vd' if tied else 'dv'
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)], axis=1)
    weight = (jnp.arange(s) < s - 1).astype(jnp.float32) / (b * (s - 1))
    head_c = head.astype(dtype)

    def by_chunks(x):           # [B, S, ...] -> [n, B, chunk, ...]
        return jnp.moveaxis(x.reshape(b, n, chunk, *x.shape[2:]), 1, 0)

    def by_groups(x):           # [B, ...] -> [shards, B / shards, ...]
        return x.reshape(shards, b // shards, *x.shape[1:])

    def visit(carry, xs):
        loss, d_head = carry
        h, t, wt = xs           # [B, c, D], [B, c], [c]
        logits = jnp.einsum(f'bcd,{w}->bcv', h, head_c,
                            preferred_element_type=jnp.float32)
        top = logits.max(axis=-1, keepdims=True)
        shifted = logits - top
        lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True))
        hit = jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 2) == t[..., None]
        at_target = jnp.sum(jnp.where(hit, shifted, 0.0), axis=-1)
        loss = loss + jnp.sum((lse[..., 0] - at_target) * wt)
        d_logits = ((jnp.exp(shifted - lse) - hit) *
                    wt[:, None]).astype(dtype)
        d_h = jnp.einsum(f'bcv,{w}->bcd', d_logits, head_c,
                         preferred_element_type=dtype)
        d_head = d_head + jnp.einsum(
            f'gbcd,gbcv->g{w}', by_groups(h), by_groups(d_logits),
            preferred_element_type=jnp.float32)
        return (loss, d_head), d_h

    (loss, d_head), d_hidden = jax.lax.scan(
        visit, (jnp.zeros((), jnp.float32),
                jnp.zeros((shards, *head.shape), jnp.float32)),
        (by_chunks(hidden), by_chunks(targets), weight.reshape(n, chunk)))
    d_hidden = jnp.moveaxis(d_hidden, 0, 1).reshape(b, s, d)
    return loss, (d_hidden, d_head.sum(axis=0).astype(head.dtype))


def _forward_rule(hidden, head, tokens, chunk, tied, shards):
    loss, gradients = _forward(hidden, head, tokens, chunk, tied, shards)
    return loss, (gradients, tokens)


def _backward_rule(chunk, tied, shards, residuals, g):
    del chunk, tied, shards
    (d_hidden, d_head), tokens = residuals
    # Tokens are integers: their cotangent has no values.
    return ((g * d_hidden).astype(d_hidden.dtype),
            (g * d_head).astype(d_head.dtype),
            np.zeros(tokens.shape, jax.dtypes.float0))


chunked_lm_loss.defvjp(_forward_rule, _backward_rule)
