"""The ZAYA1 forward pass in plain `jax.numpy`.

float32 arithmetic throughout, `jax.default_matmul_precision("highest")`
set by the caller, no cache, no taps kept, no kernels: the convolutions and
the value's shift are sums over t and t - 1 of the whole sequence, the
attention is the full [S, S] score matrix under the causal mask, a head
and a block of its rows at a time, and the expert layer a loop over every expert under a mask of who
chose it.  One row of the batch goes through one layer at a time, and one
layer's weights are held at a time, in the type they are served in (a
bfloat16 weight is cast where it is multiplied, which is exact).  It
imports nothing of the program.

What is computed (`model_type` `zaya`; arXiv:2510.04476 for the attention,
arXiv:2511.17127 for the expert layer, its router and the merges), with N
an RMSNorm of eps `eps` and every `+` of a block the scaled merge
`merge(u, f) = (u + b_u) * s_u + (f + b_f) * s_f`:

    a = merge1(x, CCA(N1(x)))        y = merge2(a, Exp(N2(a), r_{l-1}))

a final norm, and `logits = h E^T` with E the embedding (tied, no bias).

CCA, on u_t = N1(x)_t, `heads` query heads and `kv` KV heads of D:

* `[q~_t | k~_t | v1_t | v2_t] = W_down u_t` (one packed matrix; q~ is
  heads x D, k~ is kv x D, v1 and v2 kv x D / 2 each), `c_t = [q~_t | k~_t]`
  as heads + kv groups of D;
* convolution 1, depthwise over time, causal, two taps, bias:
  `c1_t[ch] = w1[0, ch] c_{t-1}[ch] + w1[1, ch] c_t[ch] + b1[ch]`;
* convolution 2, over time and over the D channels inside each group:
  `c2_t[g] = c1_{t-1}[g] W2[g, 0] + c1_t[g] W2[g, 1] + b2[g]`;
  `c_{-1} = c1_{-1} = 0`;
* the q-k mean, from the streams BEFORE the convolutions:
  `mq_t[h] = (q~_t[h] + k~_t[h // group]) / 2`,
  `mk_t[j] = (mean over group j's query heads of q~_t[h] + k~_t[j]) / 2`;
  `q_t = c2_t[q part] + mq_t`, `k_t = c2_t[k part] + mk_t`;
* `q_t[h] <- sqrt(D) q_t[h] / |q_t[h]|`,
  `k_t[j] <- exp(temp_j) sqrt(D) k_t[j] / |k_t[j]|`;
* `v_t = [v1_t | v2_{t-1}]` (`v2_{-1} = 0`), split into the kv heads of D;
* RoPE on the first `rotated` of each q and k head (rotate_half pairing),
  causal softmax of `q . k / sqrt(D)`, query head h over KV head
  h // group; `CCA = o_t W_up`.

Exp, on z = N2(a) with the previous layer's router state r_{l-1}:

* `d = z W_d + b_d`; `r_l = d + gamma * r_{l-1}` (layer 0 has no state to
  scale: `r_0 = d`, its gamma multiplies nothing); r_l goes on to layer
  l + 1;
* `p = softmax(gelu(gelu(N_r(r_l) W_1 + b_1) W_2 + b_2) W_3)` over
  experts + 1 outputs (N_r an RMSNorm, the erf GELU);
* `e = argmax(p + beta)`, `w = p[e]`, not renormalised;
* `Exp = w * E_e(z)`, `E_e` a SwiGLU, for e < experts; `Exp = w * z` for
  e = experts (the skip: no expert).

`matmul` is the one hook, as in `llama_ref`: the control puts a W8A8
product in its place.  The router, the norms, the first convolution, the
rotation, the scores and the weighted sum are not products of the hook:
they stay float32 in the control too.  A second control breaks the expert
path alone (`WRONG_EXPERT` as the precision): float32 arithmetic, and a
token that chose an expert meets the next expert under its own weight.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.llama_ref import (MATMULS, plain_matmul, rms_norm,
                                            rope)
from benchmarks.reference.solar_open2_ref import swiglu


WRONG_EXPERT = 'wrong_expert'      # a `precision` of `LayerwiseModel`


def before(rows):
    """rows [S, W] -> the rows at t - 1, zeros at t = 0."""
    return jnp.concatenate([jnp.zeros_like(rows[:1]), rows[:-1]], axis=0)


def f32(a):
    return a.astype(jnp.float32)


def unit(t):
    """t [..., D] -> sqrt(D) t / |t|."""
    return t.shape[-1] ** 0.5 * t / jnp.linalg.norm(t, axis=-1,
                                                    keepdims=True)


def qk_mean(q_lat, k_lat):
    """q~ [S, heads, D], k~ [S, kv, D] -> (mq [S, heads, D], mk [S, kv,
    D]): each head's mean with its group's partner."""
    s, heads, d = q_lat.shape
    kv = k_lat.shape[1]
    return ((q_lat + jnp.repeat(k_lat, heads // kv, axis=1)) / 2,
            (q_lat.reshape(s, kv, heads // kv, d).mean(axis=2) + k_lat) / 2)


def _block(n: int, most: int) -> int:
    """The largest divisor of n that is at most `most`."""
    return next(b for b in range(min(n, most), 0, -1) if n % b == 0)


def attention(q, k, v):
    """Causal softmax attention of q [H, S, D] over k, v [KV, S, D], query
    head h over KV head h // (H / KV): the whole [S, S] score matrix, a
    head and a block of its rows at a time (the scores of one head over
    12,800 positions are 0.66 GB)."""
    h, s, d = q.shape
    rows = _block(s, 2048)
    blocks = s // rows
    head = jnp.repeat(jnp.arange(h), blocks)
    start = jnp.tile(jnp.arange(blocks) * rows, h)

    def some_rows(args):
        q_rows, j, first = args
        seen = (first + jnp.arange(rows))[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.where(seen, q_rows @ k[j].T * d ** -0.5, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v[j]

    out = jax.lax.map(some_rows, (q.reshape(h * blocks, rows, d),
                                  head // (h // k.shape[0]), start))
    return out.reshape(h, s, d)


def cca(w, u, *, heads, kv, head_dim, rotated, theta, matmul):
    """u [S, hidden], one row -> [S, hidden]."""
    s, d = u.shape[0], head_dim
    wide, half = (heads + kv) * d, kv * d // 2
    down = matmul('sd,dw->sw', u, w['down_proj']['kernel'])
    c, v1, v2 = jnp.split(down, [wide, wide + half], axis=-1)
    w1 = f32(w['conv1_w'])
    c1 = w1[0] * before(c) + w1[1] * c + f32(w['conv1_b'])
    by_group = lambda t: t.reshape(s, heads + kv, d)  # noqa: E731
    c2 = (matmul('sgi,gio->sgo', by_group(before(c1)), w['conv2_w'][:, 0]) +
          matmul('sgi,gio->sgo', by_group(c1), w['conv2_w'][:, 1]) +
          f32(w['conv2_b']).reshape(heads + kv, d))
    mean_q, mean_k = qk_mean(by_group(c)[:, :heads], by_group(c)[:, heads:])
    q = unit(c2[:, :heads] + mean_q)
    k = unit(c2[:, heads:] + mean_k) * jnp.exp(f32(w['temp']))[:, None]
    v = jnp.concatenate([v1, before(v2)], axis=-1).reshape(s, kv, d)

    def turned(t):          # [S, H, D] -> [H, S, D], the first rotated
        t = t.transpose(1, 0, 2)[None]
        return jnp.concatenate([rope(t[..., :rotated], theta),
                                t[..., rotated:]], axis=-1)[0]

    out = attention(turned(q), turned(k), v.transpose(1, 0, 2))
    return matmul('hsk,hkd->sd', out, w['up_proj']['kernel'])


def route(w, z, carried, *, eps):
    """z [S, hidden], r_{l-1} [S, R] or None -> (weights [S, experts + 1],
    zero but for the chosen output's probability; r_l)."""
    state = z @ f32(w['down']) + f32(w['down_b'])
    if carried is not None:
        state = state + f32(w['gamma']) * carried
    h = rms_norm(state, f32(w['norm']), eps)
    for i in ('1', '2'):
        h = jax.nn.gelu(h @ f32(w['w' + i]) + f32(w['b' + i]),
                        approximate=False)
    p = jax.nn.softmax(h @ f32(w['w3']), axis=-1)
    chosen = jnp.argmax(p + f32(w['balance']), axis=-1)
    mask = chosen[:, None] == jnp.arange(p.shape[-1])[None, :]
    return jnp.where(mask, p, 0.0), state


def experts(w, z, weight, *, matmul):
    """sum over the experts e of weight[:, e] * E_e(z), plus the skip's
    weight[:, experts] * z."""
    n = w['w_gate'].shape[0]

    def add_expert(i, out):
        y = swiglu(z, w['w_gate'][i], w['w_up'][i], w['w_down'][i], matmul)
        return out + jnp.take(weight, i, axis=1)[:, None] * y

    return jax.lax.fori_loop(0, n, add_expert, weight[:, n:] * z)


def merge(w, u, f):
    return ((u + f32(w['stream_bias'])) * f32(w['stream_scale']) +
            (f + f32(w['branch_bias'])) * f32(w['branch_scale']))


def layer_forward(w, x, carried, *, heads, kv, head_dim, rotated, theta, eps,
                  matmul=plain_matmul, wrong_expert=False):
    """One block over one row: x [S, hidden] float32 and the previous
    layer's router state [S, R] (None at layer 0) -> (y, r_l).  `w` is one
    layer of the tree the family's `layer_weights` makes, in the type it
    is served in: a weight is cast to float32 where it is multiplied."""
    def mm(spec, a, b):
        return matmul(spec, a, f32(b))

    u = rms_norm(x, f32(w['attn_norm']['scale']), eps)
    a = merge(w['attn_merge'], x, cca(
        w['attn'], u, heads=heads, kv=kv, head_dim=head_dim, rotated=rotated,
        theta=theta, matmul=mm))
    z = rms_norm(a, f32(w['ffn_norm']['scale']), eps)
    weight, state = route(w['router'], z, carried, eps=eps)
    if wrong_expert:    # the control: expert e's weight to expert e + 1
        n = weight.shape[-1] - 1
        weight = jnp.concatenate(
            [jnp.roll(weight[:, :n], 1, axis=1), weight[:, n:]], axis=1)
    return merge(w['ffn_merge'], a, experts(w['moe'], z, weight,
                                            matmul=mm)), state


def tied_logits(outer, x, *, eps, matmul=plain_matmul):
    """x [T, hidden] -> logits against the embedding table, a slice of the
    vocabulary at a time: [slices, T, vocab / slices], slice i holding ids
    from i * vocab / slices (the table stays in the type it is served in
    and a slice is cast where it is multiplied: whole and in float32 it is
    2.1 GB)."""
    h = rms_norm(x, f32(outer['final_norm']['scale']), eps)
    table = outer['embed']['embedding']
    slices = _block(table.shape[0], 8)
    return jax.lax.map(
        lambda rows: matmul('td,vd->tv', h, f32(rows)),
        table.reshape(slices, table.shape[0] // slices, table.shape[1]))


class LayerwiseModel:
    """Forward pass, layer by layer, with the weights made again from the
    seed for each layer (`make_layer(i)` and `make_outer()` in the type the
    weights are served in).  Holds one layer at a time; it has to fit
    BESIDE the engine, whose weights and cache the harness still holds
    while the reference runs (3 GB of the chip are left)."""

    def __init__(self, dims, make_layer, make_outer, precision='float32'):
        self.dims = dims
        self._make_layer = make_layer
        self._make_outer = make_outer
        # A second control beside the lower precisions: float32 arithmetic
        # with every routed token's expert swapped for its neighbour.
        wrong = precision == WRONG_EXPERT
        mm = MATMULS['float32' if wrong else precision]
        self._layer = jax.jit(functools.partial(
            layer_forward, heads=dims.heads, kv=dims.kv_heads,
            head_dim=dims.head_dim, rotated=dims.rope, theta=dims.rope_theta,
            eps=dims.eps, matmul=mm, wrong_expert=wrong))
        self._head = jax.jit(functools.partial(
            tied_logits, eps=dims.eps, matmul=mm))

    def hidden(self, tokens):
        """Final hidden states [B, S, hidden] for tokens [B, S]."""
        with jax.default_matmul_precision('highest'):
            table = self._make_outer()['embed']['embedding']
            rows = [(f32(table[tokens[r]]), None)
                    for r in range(tokens.shape[0])]
            del table
            for i in range(self.dims.layers):
                w = self._make_layer(i)
                rows = [self._layer(w, x, carried) for x, carried in rows]
                # Before the next layer's weights are made.
                jax.block_until_ready(rows)
                del w
        return jnp.stack([x for x, _ in rows])

    def logits_at(self, hidden_rows):
        """The output head over rows [B, T, hidden] picked from `hidden`:
        [B, T, vocab] on the host, a row of the batch at a time (512
        positions' logits over 262,272 ids are 0.5 GB)."""
        with jax.default_matmul_precision('highest'):
            outer = self._make_outer()
            return np.stack([
                np.asarray(self._head(outer, row)).transpose(1, 0, 2).reshape(
                    row.shape[0], -1) for row in hidden_rows])
