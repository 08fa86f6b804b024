"""The Granite-4.0-H forward pass in plain `jax.numpy`.

float32 throughout, `jax.default_matmul_precision("highest")` set by the
caller, no cache, no chunks, no kernels, no batching tricks; one layer at a
time, and the attention layer's scores a row of the batch at a time.  It
imports nothing of the program.

What is computed (`model_type` `granitemoehybrid` with no routed experts;
the Mamba-2 mixer of arXiv:2405.21060 and the public `GraniteMoeHybrid`
module):

* the stream starts as `embedding_multiplier * E[token]`; blocks
  `h = x + r Mix(norm(x))`, `y = h + r FFN(norm(h))` with `r` the
  `residual_multiplier`, RMSNorm, `FFN(u) = W_out (silu(W_gate u) * W_up
  u)` with gate and up the two halves of one matrix; a final norm, and
  `logits = (h E^T) / logits_scaling` over the same table E;
* Mix of an attention layer (`layer_types[i] == "attention"`):
  grouped-query causal softmax attention with no positional term at all,
  scores scaled by `attention_multiplier` (not head_dim ** -0.5);
* Mix of every other layer, with `u` the normed stream:

      [z | xBC | dt] = W_in u
      xBC_t = silu(b_c + sum_j w_c[j] * xBC_{t-taps+j})    (zeros before 0)
      x_t [H, P], B_t [N], C_t [N] = split(xBC_t)
      dt_t = softplus(dt_t + dt_bias),   A = -exp(A_log)          a head
      S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) B_t^T,  y_t = S_t C_t + D x_t
      o = W_o (rmsnorm_over_all_channels(y * silu(z)) * w_n)

  as a `lax.scan` over the positions, ONE update a token from a zero
  state: the one-step equations, not the chunked form the program's
  prefill takes.

`matmul` is the one hook, as in `llama_ref`: the control puts a W8A8
product in its place.  The recurrence, the convolution and the norms are
not products of the hook: they stay float32 in the control too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference.llama_ref import MATMULS, plain_matmul, rms_norm


def _rows(fn, *args):
    """`fn` over the rows of the batch, one at a time."""
    return jax.lax.map(lambda xs: fn(*(x[None] for x in xs))[0], args)


def scaled_attention(q, k, v, scale):
    """Causal softmax attention; q [B, H, S, D], k and v [B, KV, S, D];
    query head h reads KV head h // group."""
    group = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = q.shape[2]
    scores = jnp.einsum('bhqd,bhkd->bhqk', q, k) * scale
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(mask, scores, -jnp.inf)
    return jnp.einsum('bhqk,bhkd->bhqd', jax.nn.softmax(scores, axis=-1), v)


def attention_mixer(w, h, *, scale, matmul):
    """h [B, S, hidden] -> [B, S, hidden]."""
    q = matmul('bsd,dhk->bhsk', h, w['q_proj']['kernel'])
    k = matmul('bsd,dhk->bhsk', h, w['k_proj']['kernel'])
    v = matmul('bsd,dhk->bhsk', h, w['v_proj']['kernel'])
    out = _rows(functools.partial(scaled_attention, scale=scale), q, k, v)
    return matmul('bhsk,hkd->bsd', out, w['o_proj']['kernel'])


def causal_conv(x, taps, bias):
    """Depthwise causal convolution with a bias, then SiLU.  x [B, S, W];
    taps [T, W], the last one on the current position."""
    n = taps.shape[0]
    padded = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
    s = x.shape[1]
    return jax.nn.silu(bias + sum(padded[:, j:j + s] * taps[j]
                                  for j in range(n)))


def recurrence(x, dt, a, b, c, d):
    """x [B, S, H, P]; dt [B, S, H]; a, d [H]; b, c [B, S, N].  Returns y
    [B, S, H, P]: one update a position, from a zero state."""
    n_b, _, h, p = x.shape

    def step(state, xs):
        x_t, dt_t, b_t, c_t = xs          # [B, H, P], [B, H], [B, N] x 2
        state = (jnp.exp(dt_t * a)[..., None, None] * state +
                 (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        y = jnp.einsum('bhpn,bn->bhp', state, c_t) + d[:, None] * x_t
        return state, y

    state = jnp.zeros((n_b, h, p, b.shape[-1]), jnp.float32)
    by_time = tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c))
    return jnp.moveaxis(jax.lax.scan(step, state, by_time)[1], 0, 1)


def mamba_mixer(w, h, *, heads, n_state, eps, matmul):
    """h [B, S, hidden] -> [B, S, hidden]."""
    proj = matmul('bsd,df->bsf', h, w['in_proj']['kernel'])
    inner = w['norm'].shape[0]
    wide = inner + 2 * n_state
    z, stream, dt = (proj[..., :inner], proj[..., inner:inner + wide],
                     proj[..., inner + wide:])
    stream = causal_conv(stream, w['conv_w'], w['conv_b'])
    x = stream[..., :inner].reshape(stream.shape[:2] + (heads, -1))
    b, c = stream[..., inner:inner + n_state], stream[..., inner + n_state:]
    dt = jax.nn.softplus(dt + w['dt_bias'])
    y = recurrence(x, dt, -jnp.exp(w['A_log']), b, c, w['D'])
    g = y.reshape(y.shape[:2] + (inner,)) * jax.nn.silu(z)
    return matmul('bsf,fd->bsd', rms_norm(g, w['norm'], eps),
                  w['out_proj']['kernel'])


def layer_forward(w, x, *, attention: bool, heads, n_state, scale, residual,
                  eps, matmul=plain_matmul):
    """One block.  x [B, S, hidden] float32; `w` one layer of the tree the
    family's `layer_weights` makes, in float32."""
    h = rms_norm(x, w['mix_norm']['scale'], eps)
    if attention:
        mixed = attention_mixer(w['attn'], h, scale=scale, matmul=matmul)
    else:
        mixed = mamba_mixer(w['mamba'], h, heads=heads, n_state=n_state,
                            eps=eps, matmul=matmul)
    x = x + residual * mixed
    h = rms_norm(x, w['ffn_norm']['scale'], eps)
    both = matmul('bsd,df->bsf', h, w['ffn']['gate_up']['kernel'])
    gate, up = jnp.split(both, 2, axis=-1)
    return x + residual * matmul('bsf,fd->bsd', jax.nn.silu(gate) * up,
                                 w['ffn']['down']['kernel'])


def head_logits(outer, x, *, eps, scaling, matmul=plain_matmul):
    h = rms_norm(x, outer['final_norm']['scale'], eps)
    return matmul('bsd,vd->bsv', h, outer['embed']['embedding']) / scaling


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
            attention: jax.jit(functools.partial(
                layer_forward, attention=attention, heads=dims.ssm_heads,
                n_state=dims.ssm_state, scale=dims.attention_multiplier,
                residual=dims.residual_multiplier, eps=dims.eps, matmul=mm))
            for attention in (True, False)}
        self._head = jax.jit(functools.partial(
            head_logits, eps=dims.eps, scaling=dims.logits_scaling,
            matmul=mm))

    def hidden(self, tokens):
        """Final hidden states [B, S, hidden] for tokens [B, S]."""
        with jax.default_matmul_precision('highest'):
            x = (self._make_outer()['embed']['embedding'][tokens] *
                 self.dims.embedding_multiplier)
            for i in range(self.dims.layers):
                x = self._layers[i in self.dims.attention_layers](
                    self._make_layer(i), x)
        return x

    def logits_at(self, hidden_rows):
        """The output head over rows [B, T, hidden] picked from `hidden`."""
        with jax.default_matmul_precision('highest'):
            return self._head(self._make_outer(), hidden_rows)
