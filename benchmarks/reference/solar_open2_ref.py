"""The Solar-Open2 forward pass in plain `jax.numpy`.

float32 throughout, `jax.default_matmul_precision("highest")` set by the
caller, no cache, no chunks, no kernels, no batching tricks; one layer at a
time, and the softmax layer's scores a row of the batch at a time, so that
four rows of 1,536 positions fit beside a layer's float32 weights.  It imports nothing
of the program.

What is computed (`model_type` `solar_open2`; the linear layer is Kimi
Delta Attention, arXiv:2510.26692, section 3, and the `KimiDeltaAttention`
module of its public code):

* blocks `x + Mix(norm(x))`, `x + MoE(norm(x))`, RMSNorm, a final norm, an
  untied head;
* Mix of a softmax layer (`gqa_layers`): grouped-query causal attention
  with no rotary embedding, `y = W_o (attn * sigmoid(W_g x))`;
* Mix of every other layer: with c(.) a depthwise causal convolution of
  `conv` taps over time followed by SiLU, per head and S in R^{dk x dv},

      q_t = l2norm(c(W_q x)_t)   k_t = l2norm(c(W_k x)_t)   v_t = c(W_v x)_t
      a_t = -exp(A_log) * softplus(W_f2 W_f1 x_t + dt_bias)
      beta_t = 2 * sigmoid(W_b x_t)
      S'  = diag(exp(a_t)) S_{t-1}
      S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
      o_t = S_t^T q_t / sqrt(dk)
      y_t = W_o (rmsnorm(o_t) * sigmoid(W_g2 W_g1 x_t))

  as a `lax.scan` over the positions, one update a token;
* MoE: `s = sigmoid(W_r x)` over ALL experts, the `top_k` largest, weights
  `s_e / sum of the k` times the scaling; the sum runs over the experts
  HELD (a loop, each expert over every token under its mask) plus the
  shared expert.  What an expert held elsewhere would add is left out, as
  in the program: the reference is given the same share.

`matmul` is the one hook, as in `llama_ref`: the control puts a W8A8
product in its place.  The router, the recurrence and the norms are not
products of the hook: they stay float32 in the control too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference.llama_ref import (MATMULS, attention, plain_matmul,
                                            rms_norm)


def _rows(fn, *args):
    """`fn` over the rows of the batch, one at a time."""
    return jax.lax.map(lambda xs: fn(*(x[None] for x in xs))[0], args)


def gated_attention(w, h, *, matmul):
    """h [B, S, hidden] -> [B, S, hidden]."""
    q = matmul('bsd,dhk->bhsk', h, w['q_proj']['kernel'])
    k = matmul('bsd,dhk->bhsk', h, w['k_proj']['kernel'])
    v = matmul('bsd,dhk->bhsk', h, w['v_proj']['kernel'])
    gate = matmul('bsd,dhk->bhsk', h, w['g_proj']['kernel'])
    out = _rows(attention, q, k, v) * jax.nn.sigmoid(gate)
    return matmul('bhsk,hkd->bsd', out, w['o_proj']['kernel'])


def short_conv(x, taps):
    """Depthwise causal convolution, then SiLU.  x [B, S, H, D]; taps [T,
    H, D], the last one on the current position."""
    n = taps.shape[0]
    padded = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0), (0, 0)))
    s = x.shape[1]
    return jax.nn.silu(sum(padded[:, j:j + s] * taps[j] for j in range(n)))


def l2norm(x, eps=1e-6):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def delta_rule(q, k, v, a, beta):
    """q, k, a [B, S, H, dk]; v [B, S, H, dv]; beta [B, S, H].  Returns o
    [B, S, H, dv]: one update a position, from a zero state."""
    b, _, h, dk = q.shape
    dv = v.shape[-1]

    def step(state, xs):
        q_t, k_t, v_t, a_t, b_t = xs                 # [B, H, dk] ... [B, H]
        decayed = jnp.exp(a_t)[..., None] * state              # [B,H,dk,dv]
        u = v_t - jnp.einsum('bhkv,bhk->bhv', decayed, k_t)
        state = decayed + b_t[..., None, None] * (
            k_t[..., :, None] * u[..., None, :])
        return state, jnp.einsum('bhkv,bhk->bhv', state, q_t) / jnp.sqrt(
            jnp.float32(dk))

    state = jnp.zeros((b, h, dk, dv), jnp.float32)
    by_time = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, a, beta))
    return jnp.moveaxis(jax.lax.scan(step, state, by_time)[1], 0, 1)


def kimi_delta_attention(w, h, *, eps, matmul):
    """h [B, S, hidden] -> [B, S, hidden]."""
    q = l2norm(short_conv(matmul('bsd,dhk->bshk', h, w['q_proj']['kernel']),
                          w['q_conv']))
    k = l2norm(short_conv(matmul('bsd,dhk->bshk', h, w['k_proj']['kernel']),
                          w['k_conv']))
    v = short_conv(matmul('bsd,dhk->bshk', h, w['v_proj']['kernel']),
                   w['v_conv'])
    low = matmul('bsd,dr->bsr', h, w['f_a']['kernel'])
    a = -jnp.exp(w['A_log'])[:, None] * jax.nn.softplus(
        matmul('bsr,rhk->bshk', low, w['f_b']['kernel']) + w['dt_bias'])
    beta = 2.0 * jax.nn.sigmoid(
        matmul('bsd,dh->bsh', h, w['b_proj']['kernel']))
    o = delta_rule(q, k, v, a, beta)
    low = matmul('bsd,dr->bsr', h, w['g_a']['kernel'])
    gate = jax.nn.sigmoid(matmul('bsr,rhk->bshk', low, w['g_b']['kernel']))
    o = rms_norm(o, w['o_norm'], eps) * gate
    return matmul('bshk,hkd->bsd', o, w['o_proj']['kernel'])


def swiglu(x, gate, up, down, matmul):
    return matmul('tf,fd->td', jax.nn.silu(matmul('td,df->tf', x, gate)) *
                  matmul('td,df->tf', x, up), down)


def expert_layer(w, h, *, held, top_k, scaling, matmul):
    """h [B, S, hidden] -> the held experts' part plus the shared expert."""
    b, s, d = h.shape
    x = h.reshape(b * s, d)
    scores = jax.nn.sigmoid(jnp.einsum('td,de->te', x, w['router']))
    kth = jnp.sort(scores, axis=-1)[:, -top_k][:, None]
    chosen = scores >= kth                                      # [T, E]
    weight = jnp.where(chosen, scores, 0.0)
    weight = weight / jnp.sum(weight, axis=-1, keepdims=True) * scaling
    out = swiglu(x, w['shared_gate']['kernel'], w['shared_up']['kernel'],
                 w['shared_down']['kernel'], matmul)

    def add_expert(i, out):
        y = swiglu(x, w['w_gate'][i], w['w_up'][i], w['w_down'][i], matmul)
        return out + jnp.take(weight, jnp.asarray(held)[i], axis=1)[:, None] * y

    out = jax.lax.fori_loop(0, len(held), add_expert, out)
    return out.reshape(b, s, d)


def layer_forward(w, x, *, softmax: bool, eps, held, top_k, scaling,
                  matmul=plain_matmul):
    """One block.  x [B, S, hidden] float32; `w` one layer of the tree the
    family's `layer_weights` makes, in float32."""
    h = rms_norm(x, w['mix_norm']['scale'], eps)
    if softmax:
        x = x + gated_attention(w['attn'], h, matmul=matmul)
    else:
        x = x + kimi_delta_attention(w['kda'], h, eps=eps, matmul=matmul)
    h = rms_norm(x, w['moe_norm']['scale'], eps)
    return x + expert_layer(w['moe'], h, held=held, top_k=top_k,
                            scaling=scaling, matmul=matmul)


def head_logits(outer, x, *, eps, matmul=plain_matmul):
    h = rms_norm(x, outer['final_norm']['scale'], eps)
    return matmul('bsd,dv->bsv', h, outer['lm_head']['kernel'])


class LayerwiseModel:
    """Forward pass, layer by layer, with the weights made again from the
    seed for each layer (`make_layer(i)` and `make_outer()` return float32
    trees).  Holds one layer at a time."""

    def __init__(self, dims, make_layer, make_outer, precision='float32'):
        self.dims = dims
        self._make_layer = make_layer
        self._make_outer = make_outer
        mm = MATMULS[precision]
        self._layers = {
            softmax: jax.jit(functools.partial(
                layer_forward, softmax=softmax, eps=dims.eps,
                held=dims.held_ids, top_k=dims.top_k, scaling=dims.scaling,
                matmul=mm)) for softmax in (True, False)}
        self._head = jax.jit(functools.partial(
            head_logits, eps=dims.eps, matmul=mm))

    def hidden(self, tokens):
        """Final hidden states [B, S, hidden] for tokens [B, S]."""
        with jax.default_matmul_precision('highest'):
            x = self._make_outer()['embed']['embedding'][tokens]
            for i in range(self.dims.layers):
                x = self._layers[i in self.dims.softmax_layers](
                    self._make_layer(i), x)
        return x

    def logits_at(self, hidden_rows):
        """The output head over rows [B, T, hidden] picked from `hidden`."""
        with jax.default_matmul_precision('highest'):
            return self._head(self._make_outer(), hidden_rows)
