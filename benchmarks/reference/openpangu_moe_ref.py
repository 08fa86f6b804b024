"""The openPangu-Ultra-MoE forward pass in plain `jax.numpy`.

float32 arithmetic throughout, `jax.default_matmul_precision("highest")`
set by the caller, no cache, no absorbed products, no kernels.  It imports
nothing of the program.

It has to fit BESIDE the engine: `harness/serve.py` still holds the engine
(its `submit`) while the reference runs, and this configuration's engine
is 10.8 GB of a chip's 16.9.  So one layer's weights are held at a time and
in the type they are served in (an expert layer is 2 GB in bfloat16, 4 in
float32; a bfloat16 weight is cast where it is multiplied, which is
exact), a row of the batch goes through a layer at a time, its attention
a block of heads at a time (the scores of 4 heads over 4,608 positions
are 340 MB) and a dense layer's 18,432 columns a block at a time.

What is computed (`model_type` `pangu_ultra_moe`), with N an RMSNorm:

* blocks `h = x + N2(Attn(N1(x)))`, `y = h + N4(FFN(N3(h)))`
  (`sandwich_norm`), a final norm, an untied head;
* Attn is multi-head latent attention in its textbook, expanded form
  (arXiv:2405.04434, section 2.1): `c_q = N_q(W_qa x)`, `q_h = W_qb,h c_q
  = [q_nope_h | q_pe_h]`; `[c_kv | k_pe] = W_kva x`, `c_kv = N_kv(c_kv)`;
  `[k_nope_h | v_h] = W_kvb,h c_kv`; RoPE (the two halves of the rotated
  part, theta from the configuration) on `q_pe_h` and on the one `k_pe`
  that every head shares; scores `(q_nope_h . k_nope_h + q_pe_h . k_pe) /
  sqrt(nope + rope)`, causal softmax over materialised scores,
  `W_o concat_h(sum p v_h)`.  Keys and values are expanded per head for
  every position: nothing is absorbed into the query;
* FFN of the leading dense layers: SwiGLU;
* FFN of the others: `solar_open2_ref.expert_layer`, the same expert layer
  (sigmoid scores over ALL experts, the `top_k` largest, normalised,
  times the scaling; a loop over the experts HELD, each over every token
  under its mask, plus the shared expert; what an expert held elsewhere
  would add is left out, as in the program).

`matmul` is the one hook, as in `llama_ref`: the control puts a W8A8
product in its place.  The router, the norms, the rotation, the scores
and the weighted sum are not products of the hook: they stay float32 in
the control too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference.llama_ref import (MATMULS, attention,
                                            plain_matmul, rms_norm, rope)
from benchmarks.reference.solar_open2_ref import (expert_layer, head_logits,
                                                  swiglu)

_HEAD_BLOCK = 4       # heads whose [S, S] scores are live at once
_FFN_BLOCK = 2048     # columns of a dense layer multiplied at once


def latent_attention(w, x, *, rank, nope, theta, eps, matmul):
    """x [1, S, hidden], one row -> [1, S, hidden].  The latent and the
    queries' bottleneck are made once; keys, values and queries are
    expanded from them per head, `_HEAD_BLOCK` heads at a time, and the
    scores of those heads materialised whole."""
    c_q = rms_norm(matmul('bsd,dr->bsr', x, w['q_a']['kernel']),
                   w['q_norm']['scale'], eps)
    kv = matmul('bsd,dr->bsr', x, w['kv_a']['kernel'])
    c_kv = rms_norm(kv[..., :rank], w['kv_norm']['scale'], eps)
    k_pe = rope(kv[:, None, :, rank:], theta)              # [1, 1, S, rope]

    def heads(ws):
        q_b, kv_b = ws                  # [q_rank, hb, .], [rank, hb, .]
        q = matmul('bsr,rhk->bhsk', c_q, q_b)
        kv_h = matmul('bsc,chk->bhsk', c_kv, kv_b)         # [1, hb, S, .]
        q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)],
                            axis=-1)
        k = jnp.concatenate([kv_h[..., :nope], jnp.broadcast_to(
            k_pe, kv_h.shape[:3] + k_pe.shape[3:])], axis=-1)
        return attention(q, k, kv_h[..., nope:])[0]        # [hb, S, v]

    def blocks(t):                      # [r, H, k] -> [H / hb, r, hb, k]
        h = t.shape[1]
        hb = _HEAD_BLOCK if h % _HEAD_BLOCK == 0 else h
        return jnp.moveaxis(t.reshape(t.shape[0], h // hb, hb, t.shape[2]),
                            1, 0)

    out = jax.lax.map(heads, (blocks(w['q_b']['kernel']),
                              blocks(w['kv_b'])))
    out = out.reshape((1, -1) + out.shape[2:])             # [1, H, S, v]
    return matmul('bhsk,hkd->bsd', out, w['o_proj']['kernel'])


def dense_ffn(m, x, matmul):
    """SwiGLU over x [T, hidden], `_FFN_BLOCK` of its columns at a time:
    sum over blocks j of (silu(x W_gate,j) * (x W_up,j)) W_down,j."""
    gate, up, down = (m[k]['kernel'] for k in ('gate_proj', 'up_proj',
                                               'down_proj'))
    f = gate.shape[1]
    block = _FFN_BLOCK if f % _FFN_BLOCK == 0 else f

    def add(j, out):
        cols = functools.partial(jax.lax.dynamic_slice_in_dim,
                                 start_index=j * block, slice_size=block)
        return out + swiglu(x, cols(gate, axis=1), cols(up, axis=1),
                            cols(down, axis=0), matmul)

    return jax.lax.fori_loop(0, f // block, add,
                             jnp.zeros(x.shape, jnp.float32))


def layer_forward(w, x, *, dense: bool, rank, nope, theta, eps, held, top_k,
                  scaling, matmul=plain_matmul):
    """One block over one row.  x [1, S, hidden] float32; `w` one layer of
    the tree the family's `layer_weights` makes, in the type it is served
    in: a weight is cast to float32 where it is multiplied."""
    def mm(spec, a, b):
        return matmul(spec, a, b.astype(jnp.float32))

    h = rms_norm(x, w['attn_norm']['scale'], eps)
    h = latent_attention(w['attn'], h, rank=rank, nope=nope, theta=theta,
                         eps=eps, matmul=mm)
    x = x + rms_norm(h, w['attn_post_norm']['scale'], eps)
    h = rms_norm(x, w['ffn_norm']['scale'], eps)
    if dense:
        h = dense_ffn(w['mlp'], h[0], mm)[None]
    else:
        h = expert_layer(w['moe'], h, held=held, top_k=top_k,
                         scaling=scaling, matmul=mm)
    return x + rms_norm(h, w['ffn_post_norm']['scale'], eps)


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
            dense: jax.jit(functools.partial(
                layer_forward, dense=dense, rank=dims.kv_rank,
                nope=dims.nope, theta=dims.rope_theta, eps=dims.eps,
                held=dims.held_ids, top_k=dims.top_k, scaling=dims.scaling,
                matmul=mm)) for dense in (True, False)}
        self._head = jax.jit(functools.partial(
            head_logits, eps=dims.eps, matmul=mm))

    def hidden(self, tokens):
        """Final hidden states [B, S, hidden] for tokens [B, S]."""
        with jax.default_matmul_precision('highest'):
            table = self._make_outer()['embed']['embedding']
            rows = [table[tokens[r:r + 1]] for r in range(tokens.shape[0])]
            del table
            for i in range(self.dims.layers):
                w = self._make_layer(i)
                layer = self._layers[i < self.dims.dense_layers]
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
