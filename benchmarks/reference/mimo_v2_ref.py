"""The MiMo-V2 forward pass in plain `jax.numpy`.

float32 arithmetic throughout, `jax.default_matmul_precision("highest")`
set by the caller, no cache, no ring, no kernels: a window layer is the
full [S, S] score matrix under a band mask.  It imports nothing of the
program.

It has to fit BESIDE what the harness still holds of the engine, so one
layer's weights are held at a time and in the type they are served in (a
bfloat16 weight is cast where it is multiplied, which is exact), a row of
the batch goes through a layer at a time, its attention a block of heads
at a time (the scores of 2 heads over 8,704 positions are 0.6 GB) and the
dense layer's 16,384 columns a block at a time.

What is computed (`model_type` `mimo_v2`), with N an RMSNorm:

* blocks `h = x + Attn_i(N1(x))`, `y = h + FFN_i(N2(h))`, a final norm,
  an untied head;
* Attn of either kind: `heads` query heads and `kv` KV heads (query head h
  reads KV head h // (heads / kv)), queries and keys of `qk` of which the
  first `rope` are rotated (the two halves of the `rope` paired, theta of
  the layer's kind) and the rest are not, values of `v` multiplied by
  `value_scale`, scores `q . k / sqrt(qk)`;
* a FULL layer (pattern 0): causal softmax over the whole sequence;
* a WINDOW layer (pattern 1): position i sees j iff i - window < j <= i,
  and the head's sink logit `s_h` joins the denominator and gives no
  value: `p_j = exp(a_j - m) / (exp(s_h - m) + sum_j exp(a_j - m))`;
* FFN of the leading dense layers: SwiGLU;
* FFN of the others: `scores = sigmoid(x W_r)` over ALL experts; the
  `top_k` with the largest `scores + b` (b the correction bias) are
  chosen, their weights are their `scores` over the chosen ones' sum; a
  loop over the experts HELD, each over every token under its weight;
  what an expert held elsewhere would add is left out, as in the program;
  no shared expert.

`matmul` is the one hook, as in `llama_ref`: the control puts a W8A8
product in its place.  The router, the norms, the rotation, the scores
and the weighted sum are not products of the hook: they stay float32 in
the control too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference.llama_ref import (MATMULS, plain_matmul, rms_norm,
                                            rope)
from benchmarks.reference.openpangu_moe_ref import dense_ffn
from benchmarks.reference.solar_open2_ref import head_logits, swiglu

_HEAD_BLOCK = 2       # heads whose [S, S] scores are live at once


def partly_rotated(x, n, theta):
    """x [1, H, S, qk]: the first `n` of each head rotated."""
    return jnp.concatenate([rope(x[..., :n], theta), x[..., n:]], axis=-1)


def mixed_attention(w, x, *, window, theta, rotated, value_scale, matmul):
    """x [1, S, hidden], one row -> [1, S, hidden].  `window` 0 is a full
    layer; a window layer's weights hold `sink` [H]."""
    q = partly_rotated(matmul('bsd,dhk->bhsk', x, w['q_proj']['kernel']),
                       rotated, theta)
    k = partly_rotated(matmul('bsd,dhk->bhsk', x, w['k_proj']['kernel']),
                       rotated, theta)
    v = matmul('bsd,dhk->bhsk', x, w['v_proj']['kernel']) * value_scale
    h, s = q.shape[1], q.shape[2]
    group = h // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = (j <= i) & ((i - j < window) if window else True)
    sink = w['sink'].astype(jnp.float32) if window else jnp.zeros((h,))

    def heads(args):
        q_h, k_h, v_h, sink_h = args                       # [hb, S, .]
        scores = jnp.einsum('hqd,hkd->hqk', q_h, k_h) * q_h.shape[-1] ** -0.5
        scores = jnp.where(seen, scores, -jnp.inf)
        if window:
            # The sink: one more term of the softmax, with no value.
            scores = jnp.concatenate([scores, jnp.broadcast_to(
                sink_h[:, None, None], scores.shape[:2] + (1,))], axis=-1)
        p = jax.nn.softmax(scores, axis=-1)[..., :s]
        return jnp.einsum('hqk,hkd->hqd', p, v_h)

    hb = _HEAD_BLOCK if h % _HEAD_BLOCK == 0 else h
    blocks = lambda t: t.reshape((h // hb, hb) + t.shape[1:])  # noqa: E731
    out = jax.lax.map(heads, (blocks(q[0]), blocks(k[0]), blocks(v[0]),
                              blocks(sink)))
    out = out.reshape((1, h) + out.shape[2:])              # [1, H, S, v]
    return matmul('bhsk,hkd->bsd', out, w['o_proj']['kernel'])


def expert_layer(w, h, *, held, top_k, matmul):
    """h [1, S, hidden] -> the held experts' part of the layer."""
    x = h[0]
    scores = jax.nn.sigmoid(jnp.einsum('td,de->te', x,
                                       w['router'].astype(jnp.float32)))
    biased = scores + w['correction_bias'].astype(jnp.float32)
    kth = jnp.sort(biased, axis=-1)[:, -top_k][:, None]
    weight = jnp.where(biased >= kth, scores, 0.0)         # [T, E]
    weight = weight / jnp.sum(weight, axis=-1, keepdims=True)

    def add_expert(i, out):
        y = swiglu(x, w['w_gate'][i], w['w_up'][i], w['w_down'][i], matmul)
        return out + jnp.take(weight, jnp.asarray(held)[i],
                              axis=1)[:, None] * y

    return jax.lax.fori_loop(0, len(held), add_expert,
                             jnp.zeros(x.shape, jnp.float32))[None]


def layer_forward(w, x, *, window, theta, rotated, value_scale, dense: bool,
                  eps, held, top_k, matmul=plain_matmul):
    """One block over one row.  x [1, S, hidden] float32; `w` one layer of
    the tree the family's `layer_weights` makes, in the type it is served
    in: a weight is cast to float32 where it is multiplied."""
    def mm(spec, a, b):
        return matmul(spec, a, b.astype(jnp.float32))

    h = rms_norm(x, w['attn_norm']['scale'].astype(jnp.float32), eps)
    x = x + mixed_attention(w['attn'], h, window=window, theta=theta,
                            rotated=rotated, value_scale=value_scale,
                            matmul=mm)
    h = rms_norm(x, w['ffn_norm']['scale'].astype(jnp.float32), eps)
    if dense:
        return x + dense_ffn(w['mlp'], h[0], mm)[None]
    return x + expert_layer(w['moe'], h, held=held, top_k=top_k, matmul=mm)


class LayerwiseModel:
    """Forward pass, layer by layer, with the weights made again from the
    seed for each layer (`make_layer(i)` in the type the weights are served
    in, `make_outer()` float32).  Holds one layer at a time."""

    def __init__(self, dims, make_layer, make_outer, precision='float32'):
        self.dims = dims
        self._make_layer = make_layer
        self._make_outer = make_outer
        mm = MATMULS[precision]
        self._layers = {
            (windowed, dense): jax.jit(functools.partial(
                layer_forward, window=dims.window if windowed else 0,
                theta=dims.window_theta if windowed else dims.rope_theta,
                rotated=dims.rope, value_scale=dims.value_scale, dense=dense,
                eps=dims.eps, held=dims.held_ids, top_k=dims.top_k,
                matmul=mm))
            for windowed in (True, False) for dense in (True, False)}
        self._head = jax.jit(functools.partial(
            head_logits, eps=dims.eps, matmul=mm))

    def hidden(self, tokens):
        """Final hidden states [B, S, hidden] for tokens [B, S]."""
        dims = self.dims
        with jax.default_matmul_precision('highest'):
            table = self._make_outer()['embed']['embedding']
            rows = [table[tokens[r:r + 1]] for r in range(tokens.shape[0])]
            del table
            for i in range(dims.layers):
                w = self._make_layer(i)
                layer = self._layers[bool(dims.pattern[i]),
                                     i < dims.dense_layers]
                rows = [layer(w, row) for row in rows]
                # Before the next layer's weights are made: two layers do
                # not fit beside the engine.
                jax.block_until_ready(rows)
                del w
        return jnp.concatenate(rows)

    def logits_at(self, hidden_rows):
        """The output head over rows [B, T, hidden] picked from `hidden`."""
        with jax.default_matmul_precision('highest'):
            return self._head(self._make_outer(), hidden_rows)
