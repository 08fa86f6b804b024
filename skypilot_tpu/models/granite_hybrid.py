"""Granite-4.0-H decoder: Mamba-2 layers beside a few NoPE grouped-query
attention layers, a shared SwiGLU in every block, four scalar multipliers
and a tied head.

The published architecture (`model_type` `granitemoehybrid`, here with no
routed experts: `num_local_experts` 0).  With N an RMSNorm a block is
pre-norm with a multiplier `r` (`residual_multiplier`) on both branches,

    h = x + r * Mix_i(N1(x))        y = h + r * FFN(N2(h))

`FFN(u) = W_out (silu(W_gate u) * W_up u)`, gate and up the two halves of
one matrix.  The stream starts as `embedding_multiplier * E[token]`; after
the last block a final norm, and `logits = (h E^T) / logits_scaling`.
Layer i mixes tokens with

- NoPE grouped-query softmax attention where `i` is in `attention_layers`
  (no rotary, no other position term; causal softmax of `(q . k) *
  attention_multiplier`, which is not `head_dim ** -0.5`), whose keys and
  values per position live in the cache; else
- a Mamba-2 mixer (arXiv:2405.21060), `ssm_heads` heads of `ssm_head_dim`
  channels over a state of `ssm_state` per channel, B and C shared by all
  heads (one group).  With `u = N1(x)`:

      [z | xBC | dt] = W_in u                  widths H P | H P + 2 N | H
      xBC_t = silu(b_c + sum_j w_c[j] * xBC_{t-taps+j})   (depthwise, causal)
      x_t, B_t, C_t = split(xBC_t)             [H, P], [N], [N]
      dt_t = softplus(dt_t + dt_bias)          a head
      S_t  = exp(dt_t A) S_{t-1} + (dt_t x_t) B_t^T       A = -exp(A_log)
      y_t  = S_t C_t + D x_t                   a head, S in R^{P x N} f32
      g    = y * silu(z)
      o    = W_o (g / rms(g) * w_n)            the mean square over all H P

  Its per-sequence state is S and the convolution's last taps: a fixed
  size, not a cache that grows.

Both kinds of state are leaves of the `cache` collection with the slot as
leading axis, so `DecodeEngine` (inference/engine.py) inserts, donates and
lays them out together.  Padding must not reach a recurrent state: a call
over S > 1 positions takes `lengths` [B], each row's count of valid
positions; a padded position has `dt` 0 (decay 1, no input) and the taps
stop at the row's length.

Prefill runs the recurrence chunk-wise (`ssm_chunk` positions a step of a
`lax.scan`, matrix products inside; `chunk_scan`), decode is one update a
token (`ssm_step`).  Every exponent taken is <= 0: decays between two
positions of a chunk are formed pairwise, not as a quotient of cumulative
products (models/solar_open2.py gives the reason).  A decode step is bound
by the bytes of the float32 state, so on one TPU device it takes the
update through one Pallas call that reads each head's tile once and
writes it once in place (`ops/pallas/ssm_state_update.py`, chosen by
`ssm_step_groups` from what it can see); anything else keeps `ssm_step`.
The state's cache leaf is laid out for that step: `[B, H / pack, N, pack
* P]`, the state's N on the sublanes and `pack` heads' channels side by
side on the lanes (two heads of 64 a row of 128), so that `y`'s sum over
N adds vregs and crosses no lanes (`state_to_leaf`; the kernel's file
has the reading).

An attention layer's heads are of 64, half a row of 128 lanes: its cache
leaves hold TWO KV heads' values a position side by side (`k`, `v`: [B,
Hkv / 2, S, 2 x head_dim]), so that a leaf is whole lane tiles in HBM and
the decode step reads it through `ops/attention.py decode_attention` (the
Pallas kernel on one TPU device) as a cache of Hkv / 2 heads of 128: a
query head's 64 values stand in its KV head's half of the row beside
zeros, and its output is that half of the weighted sum.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from skypilot_tpu.models.llama import RMSNorm
from skypilot_tpu.models.served import Served, publish_state_updates
from skypilot_tpu.ops import attention as attn_lib

_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    dim: int = 2048
    n_layers: int = 40
    attention_layers: Tuple[int, ...] = (5, 15, 25, 35)
    n_heads: int = 32
    n_kv_heads: int = 8                # even: two share a row of the cache
    head_dim: int = 64
    ffn_dim: int = 8192
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_conv: int = 4
    ssm_chunk: int = 256               # positions a step of the prefill scan
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.015625
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    norm_eps: float = 1e-5
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.n_kv_heads % 2:
            raise ValueError('an attention layer caches two KV heads a row: '
                             f'{self.n_kv_heads} KV heads are not even')

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_pack(self) -> int:
        """Heads whose channels share a row of the state's leaf: as many
        as fit 128 lanes, a divisor of the heads."""
        most = max(1, 128 // self.ssm_head_dim)
        return max(k for k in range(1, most + 1) if self.ssm_heads % k == 0)

    @property
    def conv_width(self) -> int:
        """The convolved stream: x of every head, then B, then C."""
        return self.ssm_inner + 2 * self.ssm_state

    def mixer_params(self, i: int) -> int:
        d = self.dim
        if i in self.attention_layers:
            return (2 * d * self.n_heads * self.head_dim +
                    2 * d * self.n_kv_heads * self.head_dim)
        inner, conv = self.ssm_inner, self.conv_width
        return (d * (inner + conv + self.ssm_heads) +        # W_in
                conv * self.ssm_conv + conv +                # taps and bias
                3 * self.ssm_heads +                         # A_log, dt_bias, D
                inner + inner * d)                           # w_n, W_o

    def layer_params(self, i: int) -> int:
        return self.mixer_params(i) + 3 * self.dim * self.ffn_dim + \
            2 * self.dim

    def num_params(self) -> int:
        return (sum(self.layer_params(i) for i in range(self.n_layers)) +
                self.vocab_size * self.dim + self.dim)


# ----- the state-space recurrence --------------------------------------------
def state_to_leaf(state: jax.Array, pack: int) -> jax.Array:
    """A state [B, H, P, N] as the cache keeps it, [B, H / pack, N, pack *
    P]: lane `i * P + p` of group `g` is channel `p` of head `g * pack +
    i`."""
    b, h, p, n = state.shape
    return state.reshape(b, h // pack, pack, p, n).transpose(
        0, 1, 4, 2, 3).reshape(b, h // pack, n, pack * p)


def state_from_leaf(leaf: jax.Array, pack: int) -> jax.Array:
    """The inverse of `state_to_leaf`."""
    b, g, n, lanes = leaf.shape
    return leaf.reshape(b, g, n, pack, lanes // pack).transpose(
        0, 1, 3, 4, 2).reshape(b, g * pack, lanes // pack, n)


def ssm_step(state, decay, dtx, dx, b, c):
    """One position, on the cache's leaf.  state [B, G, N, L] f32; decay,
    dtx, dx [B, G, L] (`exp(dt A)`, `dt x` and `D x` a channel); b, c
    [B, N].  Returns (y [B, G, L], new state)."""
    state = (decay[:, :, None, :] * state +
             b[:, None, :, None] * dtx[:, :, None, :])
    y = jnp.einsum('bgnl,bn->bgl', state, c, precision=_HIGHEST)
    return y + dx, state


def ssm_step_groups(state: jax.Array, positions: int,
                    mesh: Optional[Mesh] = None) -> Optional[int]:
    """The groups of heads one grid step of the decode kernel updates
    (`ops/pallas/ssm_state_update.py`) for a leaf like `state` [B, G, N,
    L], or None where the update goes through XLA: a call over more than
    one position (prefill's `chunk_scan`), off the TPU, under a mesh of
    several devices (XLA cannot partition a Mosaic call), a state that is
    not float32, or sizes the kernel's tiling cannot take (the heads' of
    a group not filling 128 lanes)."""
    if (positions != 1 or state.dtype != jnp.float32 or
            jax.default_backend() != 'tpu' or
            (mesh is not None and mesh.size > 1)):
        return None
    from skypilot_tpu.ops.pallas import ssm_state_update as pallas_ssm
    return pallas_ssm.block_groups(*state.shape[1:])


def chunk_scan(state, x, dt, a, b, c, chunk: int):
    """S positions, `chunk` at a step.  state [B, H, P, N] f32; x [B, S,
    H, P]; dt [B, S, H] (0 at a padded position); a [H] (< 0); b, c [B,
    S, N]; S a multiple of `chunk`.  Returns (y [B, S, H, P] without the
    `D x` term, state after the last position).

    Inside a chunk with g_r the cumulative sum of dt_r a (<= 0) and
    L[r, i] = exp(g_r - g_i) for i <= r, the recurrence unrolls to

        Y = ((C B^T) * L) (dt * X) + exp(g) * (C S_0^T)
        S_end = exp(g_end) S_0 + (dt * X * exp(g_end - g))^T B

    a head: `C B^T` once for all heads, the decays pairwise so that no
    exponent is > 0.
    """
    n_b, s, h, p = x.shape
    n = s // chunk

    def split(t):                    # [B, S, ...] -> [n, B, C, ...]
        return jnp.moveaxis(t.reshape((n_b, n, chunk) + t.shape[2:]), 1, 0)

    lower = jnp.tril(jnp.ones((chunk, chunk), bool))

    def body(state, xs):
        xc, dtc, bc, cc = xs         # [B, C, H, P], [B, C, H], [B, C, N] x 2
        g = jnp.cumsum(dtc * a, axis=1)                          # [B, C, H]
        g_h = jnp.moveaxis(g, 2, 1)                              # [B, H, C]
        diff = g_h[:, :, :, None] - g_h[:, :, None, :]           # [B,H,C,C]
        pair = jnp.exp(jnp.where(lower, diff, -jnp.inf))
        cb = jnp.einsum('brn,bin->bri', cc, bc, precision=_HIGHEST)
        dtx = dtc[..., None] * xc                                # [B,C,H,P]
        y = (jnp.einsum('bhri,bihp->brhp', cb[:, None] * pair, dtx,
                        precision=_HIGHEST) +
             jnp.exp(g)[..., None] * jnp.einsum(
                 'brn,bhpn->brhp', cc, state, precision=_HIGHEST))
        g_end = g[:, -1:, :]                                     # [B, 1, H]
        state = (jnp.exp(g_end[:, 0])[..., None, None] * state +
                 jnp.einsum('bihp,bin->bhpn',
                            dtx * jnp.exp(g_end - g)[..., None], bc,
                            precision=_HIGHEST))
        return state, y

    state, y = jax.lax.scan(body, state,
                            tuple(split(t) for t in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1).reshape(n_b, s, h, p), state


def ssm_mix(seq, dt, lengths, state, *, conv_w, conv_b, a_log, dt_bias, d,
            heads: int, chunk: int, mesh: Optional[Mesh] = None):
    """The mixer between its projections, in float32.  seq [B, taps + S,
    H P + 2 N]: the stream before the convolution, the taps of earlier
    calls in front; dt [B, S, H] as projected; lengths [B]; state [B, H /
    pack, N, pack * P], the cache's leaf.  Returns (y [B, S, H P], new
    state).  One position's update is the kernel's where
    `ssm_step_groups` says so."""
    n_taps = conv_w.shape[0]
    s = seq.shape[1] - (n_taps - 1)
    n_batch, n_groups, n_state, lanes = state.shape
    pack = heads // n_groups
    mixed = nn.silu(conv_b + sum(
        seq[:, j:j + s].astype(jnp.float32) * conv_w[j]
        for j in range(n_taps)))
    x, b, c = jnp.split(mixed, [mixed.shape[-1] - 2 * n_state,
                                mixed.shape[-1] - n_state], axis=-1)
    x = x.reshape(x.shape[:2] + (heads, -1))                     # [B,S,H,P]
    a = -jnp.exp(a_log)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
    # A padded position leaves the state as it was: decay 1, no input.
    valid = jnp.arange(s)[None, :] < lengths[:, None]
    dt = jnp.where(valid[:, :, None], dt, 0.0)
    if s == 1:
        step = ssm_step
        block = ssm_step_groups(state, s, mesh)
        if block is not None:
            from skypilot_tpu.ops.pallas import ssm_state_update as pallas_ssm
            step = functools.partial(pallas_ssm.ssm_state_update_fwd,
                                     groups=block)
        x0, dt0 = x[:, 0], dt[:, 0]                     # [B, H, P], [B, H]

        def channels(t):             # a value a head -> one a channel
            return jnp.broadcast_to(t[..., None], x0.shape).reshape(
                n_batch, n_groups, lanes)

        y, state = step(state, channels(jnp.exp(dt0 * a)),
                        channels(dt0) * x0.reshape(n_batch, n_groups, lanes),
                        (d[:, None] * x0).reshape(n_batch, n_groups, lanes),
                        b[:, 0], c[:, 0])
        return y.reshape(n_batch, 1, -1), state
    chunk = min(chunk, s)
    pad = -s % chunk
    xs = (x, dt, b, c)
    if pad:                          # dt == 0: nothing happens
        xs = tuple(jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                   for t in xs)
    y, state = chunk_scan(state_from_leaf(state, pack), xs[0], xs[1], a,
                          xs[2], xs[3], chunk)
    y = y[:, :s] + d[:, None] * x
    return y.reshape(y.shape[:2] + (-1,)), state_to_leaf(state, pack)


class MambaMixer(nn.Module):
    cfg: GraniteHybridConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, u: jax.Array, decode: bool,
                 lengths: Optional[jax.Array]) -> jax.Array:
        cfg = self.cfg
        b, s, _ = u.shape
        h, p, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
        inner, wide, taps = cfg.ssm_inner, cfg.conv_width, cfg.ssm_conv - 1

        def vector(name, shape, fill=nn.initializers.zeros):
            return self.param(name, fill, shape,
                              cfg.param_dtype).astype(jnp.float32)

        proj = nn.Dense(inner + wide + h, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name='in_proj')(u)
        z, stream, dt = jnp.split(proj, [inner, inner + wide], axis=-1)
        weights = dict(
            conv_w=vector('conv_w', (cfg.ssm_conv, wide),
                          nn.initializers.lecun_normal()),
            conv_b=vector('conv_b', (wide,)), a_log=vector('A_log', (h,)),
            dt_bias=vector('dt_bias', (h,)),
            d=vector('D', (h,), nn.initializers.ones))
        norm_scale = vector('norm', (inner,), nn.initializers.ones)

        fresh = not (decode and self.has_variable('cache', 'state'))
        state = conv = None
        leaf = (b, h // cfg.ssm_pack, n, cfg.ssm_pack * p)
        if decode:
            state = self.variable('cache', 'state', jnp.zeros, leaf,
                                  jnp.float32)
            conv = self.variable('cache', 'conv', jnp.zeros, (b, taps, wide),
                                 cfg.dtype)
        s0 = jnp.zeros(leaf, jnp.float32) if fresh else state.value
        before = (jnp.zeros((b, taps, wide), cfg.dtype) if fresh
                  else conv.value)
        if lengths is None:
            lengths = jnp.full((b,), s, jnp.int32)
        seq = jnp.concatenate([before, stream], axis=1)  # [B, taps + S, wide]
        y, s1 = ssm_mix(seq, dt, lengths, s0, heads=h, chunk=cfg.ssm_chunk,
                        mesh=self.mesh, **weights)
        if decode:
            state.value = s1
            # The last `taps` valid inputs: rows length .. length + taps - 1
            # of (taps before, this call).
            conv.value = jax.vmap(
                lambda row, at: jax.lax.dynamic_slice_in_dim(row, at, taps))(
                    seq, lengths)
        g = y * nn.silu(z.astype(jnp.float32))
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) +
                              cfg.norm_eps) * norm_scale
        return nn.Dense(cfg.dim, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name='out_proj')(
                            g.astype(cfg.dtype))


class Attention(nn.Module):
    """NoPE grouped-query softmax attention scaled by
    `attention_multiplier`.  The cache protocol is `models/llama.py`
    `_decode_attend`'s: every step attends only k_pos <= q_pos and writes
    at q_pos, and an insert overwrites a slot's whole cache, so padding
    lies at masked positions until it is overwritten."""
    cfg: GraniteHybridConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 decode: bool) -> jax.Array:
        cfg = self.cfg
        b, s, _ = x.shape
        hd, scale = cfg.head_dim, cfg.attention_multiplier

        def heads(name, n):             # -> [B, n, S, hd]
            return nn.DenseGeneral(
                features=(n, hd), axis=-1, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                name=name)(x).transpose(0, 2, 1, 3)

        q = heads('q_proj', cfg.n_heads)
        k = heads('k_proj', cfg.n_kv_heads)
        v = heads('v_proj', cfg.n_kv_heads)

        def over_itself():
            # The kernels scale by hd ** -0.5: what is left of the
            # multiplier goes into q (1/8 at the published sizes, exact).
            folded = (q.astype(jnp.float32) * (scale * hd ** 0.5)).astype(
                cfg.dtype)
            return attn_lib.flash_attention_on_mesh(folded, k, v, self.mesh)

        if not decode:
            out = over_itself()
        else:
            fresh = not self.has_variable('cache', 'k')
            shape = (b, cfg.n_kv_heads // 2, cfg.max_seq_len, 2 * hd)
            ck = self.variable('cache', 'k', jnp.zeros, shape, cfg.dtype)
            cv = self.variable('cache', 'v', jnp.zeros, shape, cfg.dtype)
            pair = attn_lib.pack_rope_keys      # two KV heads side by side
            if fresh:
                # Left-aligned prompts: the prompt is cache[:S], and
                # attention is over the prompt itself.
                ck.value = jax.lax.dynamic_update_slice(
                    ck.value, pair(k), (0, 0, 0, 0))
                cv.value = jax.lax.dynamic_update_slice(
                    cv.value, pair(v), (0, 0, 0, 0))
                out = over_itself()
            elif s > 1:
                out = self._chunk(ck, cv, q, pair(k), pair(v), positions)
            else:
                out = self._step(ck, cv, q, pair(k)[:, :, 0], pair(v)[:, :, 0],
                                 positions[:, 0])
        return nn.DenseGeneral(
            features=cfg.dim, axis=(-2, -1), use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name='o_proj')(
                out.transpose(0, 2, 1, 3))

    def _chunk(self, ck, cv, q, k, v, positions):
        """A chunk of a long prompt against the cache: its rows (k, v [B,
        Hkv / 2, S, 2 hd]) land at their positions (a scatter by position:
        out-of-range updates drop, where dynamic_update_slice would clamp
        and overwrite) and it attends over what the cache then holds."""
        cfg = self.cfg
        b = q.shape[0]
        rows = jnp.arange(b)[:, None]
        ck.value = ck.value.at[rows, :, positions, :].set(
            k.transpose(0, 2, 1, 3))
        cv.value = cv.value.at[rows, :, positions, :].set(
            v.transpose(0, 2, 1, 3))
        kept = cfg.max_seq_len
        return attn_lib.mha_reference(
            q, attn_lib.unpack_rope_keys(ck.value),
            attn_lib.unpack_rope_keys(cv.value), causal=True,
            scale=cfg.attention_multiplier, segment_positions=positions,
            kv_positions=jnp.broadcast_to(jnp.arange(kept)[None, :],
                                          (b, kept)))

    def _step(self, ck, cv, q, k, v, pos):
        """One position a slot: this step's rows k, v [B, Hkv / 2, 2 hd]
        written at `pos` [B], then attention up to the row just written.
        The rows are scattered over (slot x pair of heads, position),
        which leaves each leaf row-major as the kernel reads it
        (models/llama.py `_decode_attend` says why).  A query head's hd
        values go into its KV head's half of a row of 2 hd, zeros in the
        other half, so that a score over the row is the head's own; its
        output is that half of the weighted sum of the row."""
        cfg = self.cfg
        b, hd = pos.shape[0], cfg.head_dim

        def write(cache, row):
            n, kept, wide = cache.shape[1:]
            flat = cache.reshape(b * n, kept, wide)
            flat = flat.at[jnp.arange(b * n), jnp.repeat(pos, n), :].set(
                row.reshape(b * n, wide))
            return flat.reshape(cache.shape)

        ck.value, cv.value = write(ck.value, k), write(cv.value, v)
        group = cfg.n_heads // cfg.n_kv_heads
        second = ((jnp.arange(cfg.n_heads) // group) % 2 == 1)[
            None, :, None, None]
        zeros = jnp.zeros_like(q)
        q_row = jnp.concatenate([jnp.where(second, zeros, q),
                                 jnp.where(second, q, zeros)], axis=-1)
        out = attn_lib.decode_attention(
            q_row, ck.value, cv.value, pos + 1, self.mesh,
            scale=cfg.attention_multiplier)
        return jnp.where(second, out[..., hd:], out[..., :hd])


class SharedMLP(nn.Module):
    """SwiGLU whose gate and up are the two halves of one matrix."""
    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        gate, up = jnp.split(nn.Dense(
            2 * cfg.ffn_dim, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name='gate_up')(x), 2, axis=-1)
        return nn.Dense(cfg.dim, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name='down')(
                            nn.silu(gate) * up)


class Block(nn.Module):
    cfg: GraniteHybridConfig
    index: int
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x, positions, decode, lengths):
        cfg = self.cfg
        r = cfg.residual_multiplier

        def norm(name, inp):
            return RMSNorm(cfg.norm_eps, cfg.dtype, cfg.param_dtype,
                           name=name)(inp)

        h = norm('mix_norm', x)
        if self.index in cfg.attention_layers:
            mixed = Attention(cfg, self.mesh, name='attn')(h, positions,
                                                           decode)
        else:
            mixed = MambaMixer(cfg, self.mesh, name='mamba')(h, decode,
                                                             lengths)
        x = x + (mixed.astype(jnp.float32) * r).astype(cfg.dtype)
        out = SharedMLP(cfg, name='ffn')(norm('ffn_norm', x))
        return x + (out.astype(jnp.float32) * r).astype(cfg.dtype)


class GraniteHybrid(nn.Module):
    """tokens [B, S] -> logits [B, S, vocab] float32.  `lengths` [B]: the
    valid positions of each row of this call (None: all S); with it and
    S > 1 the logits are those of each row's last valid position alone,
    [B, 1, vocab]."""
    cfg: GraniteHybridConfig
    # The mesh the program is partitioned over, if any: the Pallas kernels
    # are for one device (ops/attention.py, `ssm_step_groups`).
    mesh: Optional[Mesh] = None

    def served(self) -> Served:
        cfg = self.cfg
        return Served(
            unpaged_cache='keeps recurrent state beside its keys and values',
            # A row of 512 positions builds a chunk's pairwise decays
            # [64 heads, 256, 256] in float32 (17 MB, and the products
            # beside them) and leaves 76 MB of state: 8 rows are 4,096
            # tokens a matrix product and under a gigabyte of both.
            prefill_rows=8,
            # A tile of an attention layer's decode kernel: two KV heads
            # a row of the cache.
            decode_kv_block=attn_lib.decode_kv_block(
                cfg.n_kv_heads // 2, 2 * cfg.head_dim, cfg.max_seq_len,
                cfg.dtype, self.mesh),
            publish_stats=self.publish_stats)

    @nn.compact
    def __call__(self, tokens: jax.Array,
                 positions: Optional[jax.Array] = None,
                 decode: bool = False,
                 lengths: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.cfg
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1])[None, :], tokens.shape)
        embed = nn.Embed(cfg.vocab_size, cfg.dim, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype,
                         embedding_init=nn.initializers.normal(stddev=1.0),
                         name='embed')
        x = (embed(tokens).astype(jnp.float32) *
             cfg.embedding_multiplier).astype(cfg.dtype)
        for i in range(cfg.n_layers):
            x = Block(cfg, i, self.mesh, name=f'layer_{i}')(
                x, positions, decode, lengths)
        if lengths is not None and x.shape[1] > 1:
            # A prefill reads one position's logits a row, the last valid
            # one: the head runs on that position alone ([B, 1, vocab]).
            x = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)
        x = RMSNorm(cfg.norm_eps, cfg.dtype, cfg.param_dtype,
                    name='final_norm')(x)
        if decode and tokens.shape[1] == 1:
            # A decode step: every Mamba layer updates every head's state
            # of every row (summed over a call's steps by the engine).
            self.sow('stats', 'rows_stepped',
                     jnp.full((1,), tokens.shape[0], jnp.int32))
        logits = jnp.einsum('bsd,vd->bsv', x,
                            embed.embedding.astype(cfg.dtype),
                            preferred_element_type=jnp.float32)
        return logits / cfg.logits_scaling

    def publish_stats(self, stats) -> None:
        """A decode call's summed `stats` collection (host arrays), to the
        /metrics registry: the head-states its steps updated, under the
        path the program was traced with."""
        cfg = self.cfg
        rows = int(stats['rows_stepped'][0][0])
        publish_state_updates(
            'skytpu_ssm_state_updates_total',
            rows * (cfg.n_layers - len(cfg.attention_layers)) * cfg.ssm_heads,
            ssm_step_groups(jax.ShapeDtypeStruct(
                (1, cfg.ssm_heads // cfg.ssm_pack, cfg.ssm_state,
                 cfg.ssm_pack * cfg.ssm_head_dim),
                jnp.float32), 1, self.mesh) is not None)
