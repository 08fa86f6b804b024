"""The published Llama forward pass in plain `jax.numpy`.

float32 throughout, `jax.default_matmul_precision("highest")` set by the
caller, no cache, no kernels, no batching tricks; one layer at a time so
that a model larger than the device still fits.  It imports nothing of the
program.  Follows the public description (Touvron et al. 2023; the
`modeling_llama.py` of the Yi checkpoints): pre-norm blocks, RMSNorm,
rotary embedding on the two halves of each head (rotate_half), grouped
query attention with query head h reading key/value head h // group,
SwiGLU, untied output head.

`matmul` is the one hook: the control of "How correct is decided" puts a
lower-precision product in its place (`quantized_matmul`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def plain_matmul(spec: str, x, w):
    return jnp.einsum(spec, x, w)


def _fake_quant_int8(x, axis):
    """Symmetric absmax int8 along `axis`, returned in float32."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


def _fake_quant_fp8(x):
    """Per-tensor scaled float8_e4m3, returned in float32."""
    scale = jnp.max(jnp.abs(x)) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _straight_through(fn, x):
    """`fn(x)` forward, identity backward."""
    return x + jax.lax.stop_gradient(fn(x) - x)


def int8_matmul(spec: str, x, w):
    """W8A8: activations quantised per token, weights per output channel
    (every axis of `w` that is contracted is reduced over)."""
    ins, out = spec.split('->')
    xs, ws = ins.split(',')
    contracted = tuple(i for i, c in enumerate(ws) if c in xs and c not in out)
    xq = _fake_quant_int8(x, axis=tuple(
        i for i, c in enumerate(xs) if c in ws and c not in out))
    wq = _fake_quant_int8(w, axis=contracted)
    return jnp.einsum(spec, xq, wq)


def fp8_matmul(spec: str, x, w):
    """Both operands rounded to scaled float8_e4m3; gradients pass through
    the rounding unchanged."""
    return jnp.einsum(spec, _straight_through(_fake_quant_fp8, x),
                      _straight_through(_fake_quant_fp8, w))


MATMULS = {'float32': plain_matmul, 'int8': int8_matmul, 'fp8': fp8_matmul}


def rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, theta):
    """x: [B, H, S, D]; positions 0..S-1."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal softmax attention; q [B, H, S, D], k and v [B, KV, S, D]."""
    group = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = q.shape[2]
    scores = jnp.einsum('bhqd,bhkd->bhqk', q, k) * (q.shape[-1] ** -0.5)
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(mask, scores, -jnp.inf)
    return jnp.einsum('bhqk,bhkd->bhqd', jax.nn.softmax(scores, axis=-1), v)


def layer_forward(w, x, *, theta, eps, matmul=plain_matmul):
    """One block. x: [B, S, hidden] float32; `w` one layer of the tree made
    by the family's `layer_weights`, in float32."""
    a = w['attn']
    h = rms_norm(x, w['attn_norm']['scale'], eps)
    q = matmul('bsd,dhk->bhsk', h, a['q_proj']['kernel'])
    k = matmul('bsd,dhk->bhsk', h, a['k_proj']['kernel'])
    v = matmul('bsd,dhk->bhsk', h, a['v_proj']['kernel'])
    out = attention(rope(q, theta), rope(k, theta), v)
    x = x + matmul('bhsk,hkd->bsd', out, a['o_proj']['kernel'])
    m = w['mlp']
    h = rms_norm(x, w['mlp_norm']['scale'], eps)
    gate = matmul('bsd,df->bsf', h, m['gate_proj']['kernel'])
    up = matmul('bsd,df->bsf', h, m['up_proj']['kernel'])
    return x + matmul('bsf,fd->bsd', jax.nn.silu(gate) * up,
                      m['down_proj']['kernel'])


def embed(outer, tokens):
    return outer['embed']['embedding'][tokens]


def head_logits(outer, x, *, eps, matmul=plain_matmul):
    h = rms_norm(x, outer['final_norm']['scale'], eps)
    return matmul('bsd,dv->bsv', h, outer['lm_head']['kernel'])


def next_token_loss(outer, x, tokens, *, eps, matmul=plain_matmul):
    """Sum (not mean) of the next-token cross-entropies of the rows in `x`;
    the caller divides by the count of the whole batch."""
    logits = head_logits(outer, x, eps=eps, matmul=matmul)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.sum(picked)


def to_f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


class LayerwiseModel:
    """Forward pass, layer by layer, with the weights made again from the
    seed for each layer (`make_layer(i)` and `make_outer()` return float32
    trees).  Holds one layer at a time."""

    def __init__(self, dims, make_layer, make_outer, precision='float32'):
        self.dims = dims
        self._make_layer = make_layer
        self._make_outer = make_outer
        mm = MATMULS[precision]
        self._layer = jax.jit(functools.partial(
            layer_forward, theta=dims.rope_theta, eps=dims.eps, matmul=mm))
        self._head = jax.jit(functools.partial(
            head_logits, eps=dims.eps, matmul=mm))

    def hidden(self, tokens):
        """Final hidden states [B, S, hidden] for tokens [B, S]."""
        with jax.default_matmul_precision('highest'):
            x = embed(self._make_outer(), tokens)
            for i in range(self.dims.layers):
                x = self._layer(self._make_layer(i), x)
        return x

    def logits_at(self, hidden_rows):
        """The output head over rows [B, T, hidden] picked from `hidden`."""
        with jax.default_matmul_precision('highest'):
            return self._head(self._make_outer(), hidden_rows)
