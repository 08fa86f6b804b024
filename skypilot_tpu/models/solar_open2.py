"""Solar-Open2 decoder: a layer pattern of softmax and linear attention,
and a dropless expert layer in every block.

The published architecture (`model_type` `solar_open2`): pre-norm residual
blocks `x + Mix(norm(x))`, `x + MoE(norm(x))`, a final norm and an untied
head.  Layer i mixes tokens with

- a NoPE, gated grouped-query softmax layer where `i` is in `gqa_layers`
  (no rotary; `y = W_o (attn(q, k, v) * sigmoid(W_g x))`), whose keys and
  values per position live in the cache as Llama's do; else
- a Kimi Delta Attention layer (arXiv:2510.26692): a gated delta rule with
  a decay per channel.  With c(.) a depthwise causal convolution over time
  followed by SiLU, per head with S in R^{dk x dv} float32:

      q_t = l2norm(c(W_q x)_t)   k_t = l2norm(c(W_k x)_t)   v_t = c(W_v x)_t
      a_t = -exp(A_log) * softplus(W_f2 W_f1 x_t + dt_bias)  (log decay, dk)
      beta_t = 2 * sigmoid(W_b x_t)        (2: eigenvalues down to -1)
      S'  = diag(exp(a_t)) S_{t-1}
      S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
      o_t = S_t^T q_t / sqrt(dk)
      y_t = W_o (rmsnorm(o_t) * sigmoid(W_g2 W_g1 x_t))

  Its per-sequence state is S and the convolution's last taps: a fixed
  size, not a cache that grows.

Both kinds of state are leaves of the `cache` collection with the slot as
leading axis, so `DecodeEngine` (inference/engine.py) inserts, donates and
lays them out together.  What differs from keys and values: padding must
not reach a recurrent state.  A call over S > 1 positions therefore takes
`lengths` [B], each row's count of valid positions, and the state and the
taps stop there: a padded position has decay 1 and beta 0.

Prefill runs the recurrence chunk-wise (`kda_chunk` positions a step of a
`lax.scan`, matrix products inside; `chunk_delta_rule`), decode is one
update a token (`delta_rule_step`).  Every exponent taken is <= 0: decays
between two positions of a chunk are formed pairwise, not as a quotient of
cumulative products, which overflows where a channel forgets fast.  A
decode step is bound by the bytes of the float32 state, so on one TPU
device with head sizes that are multiples of 128 it takes the update
through one Pallas call that reads each head's tile once and writes it
once in place (`ops/pallas/delta_rule_step.py`, chosen by
`kda_step_heads` from what it can see); anything else keeps
`delta_rule_step`, which XLA runs as several sweeps of the state.

The expert layer is `models/moe.py` `DroplessMoE`: told which experts it
holds, it routes over all of them and computes its own experts' part.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from skypilot_tpu.models import moe as moe_lib
from skypilot_tpu.models.llama import RMSNorm
from skypilot_tpu.models.served import Served, publish_state_updates
from skypilot_tpu.ops import attention as attn_lib

_HIGHEST = jax.lax.Precision.HIGHEST
# Rows of a prefill whose float32 intermediates are live at once: the
# softmax layer's scores [rows, kv heads, group * S, S], and the linear
# layer's q, k, v, decays and pairwise decays of a chunk.
_PREFILL_ROWS = 2


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config:
    vocab_size: int = 196608
    dim: int = 4096
    n_layers: int = 48
    gqa_layers: Tuple[int, ...] = tuple(range(0, 48, 4))
    n_heads: int = 64                  # softmax layer: query heads
    n_kv_heads: int = 8
    head_dim: int = 128
    kda_heads: int = 64                # linear layer: K and V heads equal
    kda_head_dim: int = 128
    kda_conv: int = 4
    kda_rank: int = 128                # low-rank width of decay and gate
    kda_chunk: int = 32                # positions a step of the prefill scan
    n_experts: int = 320
    held_experts: Tuple[int, ...] = tuple(range(320))
    experts_per_token: int = 8
    expert_dim: int = 1280
    n_shared_experts: int = 1
    routed_scaling: float = 1.0
    norm_eps: float = 1e-5
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def layer_params(self, i: int) -> int:
        d = self.dim
        if i in self.gqa_layers:
            mix = (3 * d * self.n_heads * self.head_dim +    # q, gate, o
                   2 * d * self.n_kv_heads * self.head_dim)
        else:
            wide = self.kda_heads * self.kda_head_dim
            mix = (4 * d * wide +                             # q, k, v, o
                   2 * (d * self.kda_rank + self.kda_rank * wide) +
                   d * self.kda_heads +                       # beta
                   3 * wide * self.kda_conv +
                   self.kda_heads + wide + self.kda_head_dim)
        expert = 3 * d * self.expert_dim
        return (mix + d * self.n_experts +
                (len(self.held_experts) + self.n_shared_experts) * expert +
                2 * d)

    def num_params(self) -> int:
        """Parameters held here (the held experts, the held vocabulary)."""
        return (sum(self.layer_params(i) for i in range(self.n_layers)) +
                2 * self.vocab_size * self.dim + self.dim)


# ----- the gated delta rule --------------------------------------------------
def delta_rule_step(state, q, k, v, a, beta):
    """One position.  state [B, H, dk, dv] f32; q, k, a [B, H, dk]; v
    [B, H, dv]; beta [B, H].  Returns (o [B, H, dv], new state)."""
    decayed = jnp.exp(a)[..., None] * state
    u = v - jnp.einsum('bhkv,bhk->bhv', decayed, k, precision=_HIGHEST)
    state = decayed + (beta[..., None] * k)[..., None] * u[..., None, :]
    o = jnp.einsum('bhkv,bhk->bhv', state, q, precision=_HIGHEST)
    return o * (q.shape[-1] ** -0.5), state


def kda_step_heads(state: jax.Array, positions: int,
                   mesh: Optional[Mesh] = None) -> Optional[int]:
    """The heads one grid step of the decode kernel updates
    (`ops/pallas/delta_rule_step.py`) for a state like `state` [B, H, dk,
    dv], or None where the update goes through XLA: a call over more than
    one position (prefill's `chunk_delta_rule`), off the TPU, under a mesh
    of several devices (XLA cannot partition a Mosaic call), a state that
    is not float32, or head sizes the kernel's tiling cannot take."""
    if (positions != 1 or state.dtype != jnp.float32 or
            jax.default_backend() != 'tpu' or
            (mesh is not None and mesh.size > 1)):
        return None
    from skypilot_tpu.ops.pallas import delta_rule_step as pallas_dr
    return pallas_dr.block_heads(*state.shape[1:])


def chunk_delta_rule(state, q, k, v, a, beta, chunk: int):
    """S positions, `chunk` at a step.  state [B, H, dk, dv] f32; q, k, a
    [B, S, H, dk]; v [B, S, H, dv]; beta [B, S, H]; S a multiple of
    `chunk`.  Returns (o [B, S, H, dv], state after the last position).

    Inside a chunk of C positions with cumulative log decay g_r (<= 0),
    D[r, i] = exp(g_r - g_i) for i <= r, the rule unrolls to

        (I + tril(A, -1) diag(beta)) U = V - (K * exp(g)) S_0
        O = (Q * exp(g)) S_0 + tril(B) diag(beta) U
        S_C = diag(exp(g_C)) S_0 + (K * exp(g_C - g))^T diag(beta) U

    with A[r, i] = sum_c k_r k_i D[r, i], B[r, i] = sum_c q_r k_i D[r, i]:
    a unit lower-triangular solve and matrix products (the paper's WY
    form, section 3; the decays pairwise so that no exponent is > 0).
    """
    b, s, h, dk = q.shape
    n = s // chunk

    def split(t):                    # [B, S, H, ...] -> [n, B, H, C, ...]
        t = t.reshape((b, n, chunk) + t.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(t, 3, 2), 1, 0)

    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)

    def body(state, xs):
        qc, kc, vc, ac, bc = xs                  # [B, H, C, dk] ... [B, H, C]
        g = jnp.cumsum(ac, axis=2)
        diff = g[:, :, :, None, :] - g[:, :, None, :, :]      # [B,H,C,C,dk]
        decay = jnp.exp(jnp.where(lower[:, :, None], diff, -jnp.inf))
        kd = kc[:, :, None, :, :] * decay
        a_mat = jnp.sum(kc[:, :, :, None, :] * kd, axis=-1)   # [B, H, C, C]
        b_mat = jnp.sum(qc[:, :, :, None, :] * kd, axis=-1)
        eg = jnp.exp(g)
        rhs = vc - jnp.einsum('bhck,bhkv->bhcv', kc * eg, state,
                              precision=_HIGHEST)
        system = (jnp.where(strict, a_mat, 0.0) * bc[:, :, None, :] +
                  jnp.eye(chunk, dtype=a_mat.dtype))
        u = jax.scipy.linalg.solve_triangular(
            system, rhs, lower=True, unit_diagonal=True)
        ub = u * bc[..., None]
        o = (jnp.einsum('bhck,bhkv->bhcv', qc * eg, state,
                        precision=_HIGHEST) +
             jnp.einsum('bhri,bhiv->bhrv', jnp.where(lower, b_mat, 0.0), ub,
                        precision=_HIGHEST))
        g_end = g[:, :, -1:, :]
        state = (jnp.exp(g_end[:, :, 0, :])[..., None] * state +
                 jnp.einsum('bhck,bhcv->bhkv', kc * jnp.exp(g_end - g), ub,
                            precision=_HIGHEST))
        return state, o * (dk ** -0.5)

    state, o = jax.lax.scan(body, state,
                            tuple(split(t) for t in (q, k, v, a, beta)))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2)      # [B, n, C, H, dv]
    return o.reshape(b, s, h, o.shape[-1]), state


def _l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _by_rows(fn, rows: int, *args):
    """`fn(*args)` over the leading axis, `rows` of it at a time (a
    `lax.map`): what `fn` builds in float32 is then live for `rows` rows
    only.  Straight through where the axis is no multiple of `rows`."""
    b = args[0].shape[0]
    if b <= rows or b % rows:
        return fn(*args)
    out = jax.lax.map(lambda xs: fn(*xs), tuple(
        t.reshape((b // rows, rows) + t.shape[1:]) for t in args))
    return jax.tree.map(lambda t: t.reshape((b,) + t.shape[2:]), out)


def kda_mix(seq, a, beta, gate, lengths, state, *, conv_w, a_log, dt_bias,
            norm_scale, eps: float, chunk: int,
            mesh: Optional[Mesh] = None):
    """The layer between its projections, in float32.  seq [B, taps + S,
    3, H, hd]: q, k and v before the convolution, the taps of earlier
    calls in front; a, gate [B, S, H, hd] and beta [B, S, H] as projected;
    lengths [B]; state [B, H, hd, hd].  Returns (gated output [B, S, H,
    hd] in seq's type, new state).  One position's update is the kernel's
    where `kda_step_heads` says so."""
    n_taps = conv_w.shape[1]
    s = seq.shape[1] - (n_taps - 1)
    mixed = nn.silu(sum(
        seq[:, j:j + s].astype(jnp.float32) * conv_w[None, None, :, j]
        for j in range(n_taps)))
    q, k, v = _l2norm(mixed[:, :, 0]), _l2norm(mixed[:, :, 1]), mixed[:, :, 2]
    a = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
        a.astype(jnp.float32) + dt_bias)
    beta = 2.0 * jax.nn.sigmoid(beta.astype(jnp.float32))
    # A padded position leaves the state as it was: decay 1, beta 0.
    valid = jnp.arange(s)[None, :] < lengths[:, None]
    a = jnp.where(valid[:, :, None, None], a, 0.0)
    beta = jnp.where(valid[:, :, None], beta, 0.0)
    if s == 1:
        step = delta_rule_step
        heads = kda_step_heads(state, s, mesh)
        if heads is not None:
            from skypilot_tpu.ops.pallas import delta_rule_step as pallas_dr
            step = functools.partial(pallas_dr.delta_rule_step_fwd,
                                     heads=heads)
        o, state = step(state, q[:, 0], k[:, 0], v[:, 0], a[:, 0],
                        beta[:, 0])
        o = o[:, None]
    else:
        chunk = min(chunk, s)
        pad = -s % chunk
        if pad:                      # a == 0, beta == 0: nothing happens
            q, k, v, a, beta = (
                jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                for t in (q, k, v, a, beta))
        o, state = chunk_delta_rule(state, q, k, v, a, beta, chunk)
        o = o[:, :s]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    o = o * norm_scale * jax.nn.sigmoid(gate.astype(jnp.float32))
    return o.astype(seq.dtype), state


class KimiDeltaAttention(nn.Module):
    cfg: SolarOpen2Config
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x: jax.Array, decode: bool,
                 lengths: Optional[jax.Array]) -> jax.Array:
        cfg = self.cfg
        b, s, _ = x.shape
        h, hd, taps = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv - 1
        init = nn.initializers.lecun_normal()

        def dense(name, features, inp=x, axis=-1):
            return nn.DenseGeneral(
                features=features, axis=axis, use_bias=False,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype, name=name)(inp)

        def vector(name, shape, fill=nn.initializers.zeros):
            return self.param(name, fill, shape,
                              cfg.param_dtype).astype(jnp.float32)

        # q, k and v before the convolution, side by side: [B, S, 3, H, hd]
        qkv = jnp.stack([dense(f'{n}_proj', (h, hd)) for n in 'qkv'], axis=2)
        a = dense('f_b', (h, hd), dense('f_a', cfg.kda_rank))
        beta = dense('b_proj', h)
        gate = dense('g_b', (h, hd), dense('g_a', cfg.kda_rank))
        weights = dict(
            conv_w=jnp.stack([vector(f'{n}_conv', (cfg.kda_conv, h, hd), init)
                              for n in 'qkv']),
            a_log=vector('A_log', (h,)), dt_bias=vector('dt_bias', (h, hd)),
            norm_scale=vector('o_norm', (hd,), nn.initializers.ones))

        fresh = not (decode and self.has_variable('cache', 'state'))
        state = conv = None
        if decode:
            state = self.variable('cache', 'state', jnp.zeros,
                                  (b, h, hd, hd), jnp.float32)
            conv = self.variable('cache', 'conv', jnp.zeros,
                                 (b, taps, 3, h, hd), cfg.dtype)
        s0 = jnp.zeros((b, h, hd, hd), jnp.float32) if fresh else state.value
        before = (jnp.zeros((b, taps, 3, h, hd), cfg.dtype) if fresh
                  else conv.value)
        if lengths is None:
            lengths = jnp.full((b,), s, jnp.int32)
        seq = jnp.concatenate([before, qkv], axis=1)   # [B, taps + S, ...]
        mix = functools.partial(kda_mix, eps=cfg.norm_eps,
                                chunk=cfg.kda_chunk, mesh=self.mesh,
                                **weights)
        o, s1 = _by_rows(mix, _PREFILL_ROWS, seq, a, beta, gate, lengths, s0) \
            if s > 1 else mix(seq, a, beta, gate, lengths, s0)
        if decode:
            state.value = s1
            # The last `taps` valid inputs: rows length .. length + taps - 1
            # of (taps before, this call).
            conv.value = jax.vmap(
                lambda row, at: jax.lax.dynamic_slice_in_dim(row, at, taps))(
                    seq, lengths)
        return dense('o_proj', cfg.dim, o, axis=(-2, -1))


class GatedAttention(nn.Module):
    """NoPE grouped-query softmax attention with an elementwise output
    gate.  The cache protocol is `models/llama.py` `_decode_attend`'s:
    every step attends only k_pos <= q_pos and writes at q_pos, and an
    insert overwrites a slot's whole cache, so padding lies at masked
    positions until it is overwritten."""
    cfg: SolarOpen2Config

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 decode: bool) -> jax.Array:
        cfg = self.cfg
        b, s, _ = x.shape

        def dense(name, heads):
            return nn.DenseGeneral(
                features=(heads, cfg.head_dim), axis=-1, use_bias=False,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype, name=name)(x)

        q = dense('q_proj', cfg.n_heads).transpose(0, 2, 1, 3)
        k = dense('k_proj', cfg.n_kv_heads).transpose(0, 2, 1, 3)
        v = dense('v_proj', cfg.n_kv_heads).transpose(0, 2, 1, 3)
        gate = dense('g_proj', cfg.n_heads)                    # [B, S, H, D]
        if not decode:
            out = self._attend_rows(q, k, v)
        else:
            fresh = not self.has_variable('cache', 'k')
            shape = (b, cfg.n_kv_heads, cfg.max_seq_len, cfg.head_dim)
            ck = self.variable('cache', 'k', jnp.zeros, shape, cfg.dtype)
            cv = self.variable('cache', 'v', jnp.zeros, shape, cfg.dtype)
            if fresh:
                # Left-aligned prompts: the prompt is cache[:S], and
                # attention is over the prompt itself.
                ck.value = jax.lax.dynamic_update_slice(ck.value, k,
                                                        (0, 0, 0, 0))
                cv.value = jax.lax.dynamic_update_slice(cv.value, v,
                                                        (0, 0, 0, 0))
                out = self._attend_rows(q, k, v)
            else:
                # A scatter by position: out-of-range updates drop, where
                # dynamic_update_slice would clamp and overwrite.
                rows = jnp.arange(b)[:, None]
                ck.value = ck.value.at[rows, :, positions, :].set(
                    k.transpose(0, 2, 1, 3))
                cv.value = cv.value.at[rows, :, positions, :].set(
                    v.transpose(0, 2, 1, 3))
                k_pos = jnp.broadcast_to(
                    jnp.arange(cfg.max_seq_len)[None, :],
                    (b, cfg.max_seq_len))
                out = attn_lib.mha_reference(
                    q, ck.value, cv.value, causal=True,
                    segment_positions=positions, kv_positions=k_pos)
        out = out.transpose(0, 2, 1, 3) * jax.nn.sigmoid(
            gate.astype(jnp.float32)).astype(cfg.dtype)
        return nn.DenseGeneral(
            features=cfg.dim, axis=(-2, -1), use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name='o_proj')(out)

    @staticmethod
    def _attend_rows(q, k, v):
        """Causal attention of each row over itself, a few rows at a time:
        the scores of 32 rows of 1024 at once would be 8.6 GB."""
        return _by_rows(
            lambda q, k, v: attn_lib.mha_reference(q, k, v, causal=True),
            _PREFILL_ROWS, q, k, v)


class Block(nn.Module):
    cfg: SolarOpen2Config
    index: int
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x, positions, decode, lengths):
        cfg = self.cfg
        h = RMSNorm(cfg.norm_eps, cfg.dtype, cfg.param_dtype,
                    name='mix_norm')(x)
        if self.index in cfg.gqa_layers:
            x = x + GatedAttention(cfg, name='attn')(h, positions, decode)
        else:
            x = x + KimiDeltaAttention(cfg, self.mesh, name='kda')(
                h, decode, lengths)
        h = RMSNorm(cfg.norm_eps, cfg.dtype, cfg.param_dtype,
                    name='moe_norm')(x)
        return x + moe_lib.DroplessMoE(
            dim=cfg.dim, ffn_dim=cfg.expert_dim, n_experts=cfg.n_experts,
            held=cfg.held_experts, router=moe_lib.LinearRouter(
                top_k=cfg.experts_per_token, scaling=cfg.routed_scaling),
            n_shared=cfg.n_shared_experts, dtype=cfg.dtype, param_dtype=cfg.param_dtype, mesh=self.mesh,
            name='moe')(h)


class SolarOpen2(nn.Module):
    """tokens [B, S] -> logits [B, S, vocab] float32.  `lengths` [B]: the
    valid positions of each row of this call (None: all S); with it and
    S > 1 the logits are those of each row's last valid position alone,
    [B, 1, vocab]."""
    cfg: SolarOpen2Config
    # The mesh the program is partitioned over, if any: the decode kernels
    # of the expert layer (models/moe.py `expert_tile`) and of the KDA
    # state (`kda_step_heads`) are for one device.
    mesh: Optional[Mesh] = None

    def served(self) -> Served:
        return Served(
            unpaged_cache='keeps recurrent state beside its keys and values',
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
        x = nn.Embed(cfg.vocab_size, cfg.dim, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype,
                     embedding_init=nn.initializers.normal(stddev=1.0),
                     name='embed')(tokens)
        for i in range(cfg.n_layers):
            x = Block(cfg, i, self.mesh, name=f'layer_{i}')(
                x, positions, decode, lengths)
        if lengths is not None and x.shape[1] > 1:
            # A prefill reads one position's logits a row, the last valid
            # one: the head runs on that position alone ([B, 1, vocab]).
            x = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)
        x = RMSNorm(cfg.norm_eps, cfg.dtype, cfg.param_dtype,
                    name='final_norm')(x)
        logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                          param_dtype=cfg.param_dtype, name='lm_head')(x)
        return logits.astype(jnp.float32)

    def publish_stats(self, stats) -> None:
        """A decode call's summed `stats` collection (host arrays), to the
        /metrics registry: the routing counts, and the KDA head-states its
        steps updated under the path the program was traced with."""
        cfg = self.cfg
        pairs = moe_lib.publish_stats(cfg.held_experts, stats)
        # Every expert layer (each has an entry in `stats`) routed each
        # (slot, step) of the call to `experts_per_token` experts, and
        # every KDA layer updated all its heads' states at each.
        slot_steps = int(pairs.sum()) // (len(stats) * cfg.experts_per_token)
        publish_state_updates(
            'skytpu_kda_state_updates_total',
            slot_steps * (cfg.n_layers - len(cfg.gqa_layers)) * cfg.kda_heads,
            kda_step_heads(jax.ShapeDtypeStruct(
                (1, cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim),
                jnp.float32), 1, self.mesh) is not None)
