"""ZAYA1 decoder: attention in a compressed, convolved latent (CCA) and a
top-1 expert layer behind a router that is an MLP with a state carried
from layer to layer, every residual sum a learned, scaled merge.

The published architecture (`model_type` `zaya`; the equations are those of
arXiv:2510.04476, Compressed Convolutional Attention, and arXiv:2511.17127,
the ZAYA1 report, as `benchmarks/configs/zaya1-8b-pp2.json` `assumed`
states them).  With N an RMSNorm, layer l of one kind (`hybrid`) is

    a = merge1(x, CCA(N1(x)))        y = merge2(a, Exp(N2(a), r_{l-1}))
    merge(u, f) = (u + b_u) * s_u + (f + b_f) * s_f

with four learned vectors a merge; a final norm and the embedding's own
table as the head close the model.

**CCA**, on u_t = N1(x)_t, `n_heads` query heads over `n_kv_heads` KV
heads of `head_dim`, all INSIDE the latent: one packed down-projection
gives q~ [Hq x D], k~ [Hkv x D] and two half values v1, v2 [Hkv x D / 2];
the packed stream c = [q~ | k~] goes through two causal convolutions of
two taps (the first depthwise, the second over the D channels inside each
head); the mean of q~ and k~ of a group is added back; q and k are
L2-normalised to sqrt(D) a head, k times a learned temperature a KV head;
the value is [v1_t | v2_{t-1}], its second half the PREVIOUS token's; RoPE
turns the first `rope_dim` of each head; grouped-query softmax attention;
one up-projection from Hq x D.

**What a slot keeps, a layer.**  K and V of the `n_kv_heads` latent heads a
position (the engine's `k` / `v` leaves, [slots, Hkv, positions, D]), and
three leaves of fixed size: `tap0` and `tap1` [slots, (Hq + Hkv) D], the
last position's c and its first convolution's output, and `v_shift`
[slots, Hkv D / 2], its v2.  A call reads them in front of its own positions
(zeros where no cache is yet: position 0 sees zeros) and leaves those of
each row's last VALID position (`lengths`), so padding reaches neither.

**Exp**, on z = N2(a) and the previous layer's router state r_{l-1}
[`router_dim`], all of the router in float32 at `highest` precision (one
expert a token: a flipped choice changes the whole sublayer's output):

    d = W_d z + b_d          r_l = d + gamma * r_{l-1}   (r_0 = d)
    p = softmax(W_3 gelu(W_2 gelu(W_1 N_r(r_l) + b_1) + b_2))   [E + 1]
    e = argmax(p + beta)     w = p[e], not renormalised
    Exp = w * E_e(z) for e < E;  w * z for e = E (the skip: no expert runs)

r_l is handed to layer l + 1: the stack carries it beside the stream.  The
experts are `models/moe.py DroplessMoE`, handed (e, w) as `routed`; it
multiplies and counts, and the skip's pairs are a count of their own.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from skypilot_tpu.models import moe as moe_lib
from skypilot_tpu.models.llama import RMSNorm, _rope
from skypilot_tpu.models.served import Served
from skypilot_tpu.ops import attention as attn_lib


@dataclasses.dataclass(frozen=True)
class ZayaConfig:
    vocab_size: int = 262272
    dim: int = 2048
    n_layers: int = 40
    n_heads: int = 8                   # query heads, in the latent
    n_kv_heads: int = 2
    head_dim: int = 128
    rope_dim: int = 64                 # the first of head_dim, rotated
    rope_theta: float = 5e6
    n_experts: int = 16                # the router has one output more
    expert_dim: int = 2048
    router_dim: int = 256
    expert_block: int = 256            # pairs a trip of the experts' loop
    norm_eps: float = 1e-5
    max_seq_len: int = 13312
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @property
    def stream(self) -> int:
        """The packed stream the convolutions mix: q~ then k~."""
        return (self.n_heads + self.n_kv_heads) * self.head_dim

    @property
    def half_value(self) -> int:
        return self.n_kv_heads * self.head_dim // 2

    def attention_params(self) -> int:
        d, hd = self.dim, self.head_dim
        groups = self.n_heads + self.n_kv_heads
        return (d * (self.stream + 2 * self.half_value) +   # down, packed
                self.stream * 2 + self.stream +             # convolution 1
                groups * hd * hd * 2 + self.stream +        # convolution 2
                self.n_kv_heads +                           # temperatures
                self.n_heads * hd * d)                      # up

    def router_params(self) -> int:
        r, out = self.router_dim, self.n_experts + 1
        return (self.dim * r + r + r + r +       # W_d, b_d, gamma, N_r
                2 * (r * r + r) + r * out + out)

    def layer_params(self) -> int:
        return (self.attention_params() + self.router_params() +
                3 * self.dim * self.expert_dim * self.n_experts +
                2 * self.dim + 2 * 4 * self.dim)

    def num_params(self) -> int:
        return (self.n_layers * self.layer_params() +
                self.vocab_size * self.dim + self.dim)


def _vector(module, name, shape, init=nn.initializers.zeros):
    return module.param(name, init, shape,
                        module.cfg.param_dtype).astype(jnp.float32)


def _unit_heads(x: jax.Array) -> jax.Array:
    """x [..., D] float32 -> sqrt(D) x / |x|."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True))


def _last_valid(rows: jax.Array, lengths: Optional[jax.Array]) -> jax.Array:
    """rows [B, S, W] -> [B, W]: each row's last valid position's."""
    if lengths is None:
        return rows[:, -1]
    return jnp.take_along_axis(rows, (lengths - 1)[:, None, None],
                               axis=1)[:, 0]


class CCA(nn.Module):
    """Compressed convolutional attention (the module docstring)."""
    cfg: ZayaConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, u: jax.Array, positions: jax.Array, decode: bool,
                 lengths: Optional[jax.Array] = None,
                 live: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.cfg
        b, s, _ = u.shape
        hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        wide, half, group = cfg.stream, cfg.half_value, hq // hkv

        # The four down-projections as one product.
        down = nn.Dense(wide + 2 * half, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name='down_proj')(u)
        c, v_now, v_next = jnp.split(down, [wide, wide + half], axis=-1)

        fresh = not (decode and self.has_variable('cache', 'k'))
        if decode:
            shape = (b, hkv, cfg.max_seq_len, hd)
            ck = self.variable('cache', 'k', jnp.zeros, shape, cfg.dtype)
            cv = self.variable('cache', 'v', jnp.zeros, shape, cfg.dtype)
            tap0 = self.variable('cache', 'tap0', jnp.zeros, (b, wide),
                                 cfg.dtype)
            tap1 = self.variable('cache', 'tap1', jnp.zeros, (b, wide),
                                 cfg.dtype)
            shift = self.variable('cache', 'v_shift', jnp.zeros, (b, half),
                                  cfg.dtype)
        if fresh:       # position 0 sees zeros
            c_before = c1_before = jnp.zeros((b, wide), cfg.dtype)
            v_before = jnp.zeros((b, half), cfg.dtype)
        else:
            c_before, c1_before = tap0.value, tap1.value
            v_before = shift.value

        def shifted(rows, before):      # rows at t - 1, [B, S, W]
            return jnp.concatenate([before[:, None], rows[:, :-1]], axis=1)

        # Convolution 1: depthwise over time.
        w1 = _vector(self, 'conv1_w', (2, wide), nn.initializers.ones)
        c32 = c.astype(jnp.float32)
        c1 = (w1[0] * shifted(c, c_before).astype(jnp.float32) +
              w1[1] * c32 + _vector(self, 'conv1_b', (wide,))).astype(
                  cfg.dtype)
        # Convolution 2: over time and over the channels inside a head.
        w2 = self.param('conv2_w', nn.initializers.lecun_normal(),
                        (hq + hkv, 2, hd, hd), cfg.param_dtype).astype(
                            cfg.dtype)
        by_head = lambda t: t.reshape(b, s, hq + hkv, hd)  # noqa: E731
        c2 = (jnp.einsum('bsgi,gio->bsgo', by_head(shifted(c1, c1_before)),
                         w2[:, 0], preferred_element_type=jnp.float32) +
              jnp.einsum('bsgi,gio->bsgo', by_head(c1), w2[:, 1],
                         preferred_element_type=jnp.float32) +
              _vector(self, 'conv2_b', (wide,)).reshape(hq + hkv, hd))
        # The q-k mean, from the streams before the convolutions.
        q_lat = by_head(c32)[:, :, :hq].reshape(b, s, hkv, group, hd)
        k_lat = by_head(c32)[:, :, hq:]
        mean_q = (q_lat + k_lat[:, :, :, None]) / 2
        mean_k = (jnp.mean(q_lat, axis=3) + k_lat) / 2
        temp = jnp.exp(_vector(self, 'temp', (hkv,)))
        q = _unit_heads(c2[:, :, :hq] + mean_q.reshape(b, s, hq, hd))
        k = _unit_heads(c2[:, :, hq:] + mean_k) * temp[:, None]
        # [B, H, S, D], the first rope_dim of a head rotated.
        q, k = (t.astype(cfg.dtype).transpose(0, 2, 1, 3) for t in (q, k))
        turn = lambda t: jnp.concatenate(  # noqa: E731
            [_rope(t[..., :cfg.rope_dim], positions, cfg.rope_theta),
             t[..., cfg.rope_dim:]], axis=-1)
        q, k = turn(q), turn(k)
        # The value: this token's half, then the previous token's.
        v = jnp.concatenate([v_now, shifted(v_next, v_before)], axis=-1)
        v = v.reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)

        if decode:
            tap0.value = _last_valid(c, lengths)
            tap1.value = _last_valid(c1, lengths)
            shift.value = _last_valid(v_next, lengths)
        if fresh:       # no cache to read: attention over the rows themselves
            if decode:
                # Left-aligned prompts: the prompt is cache[:S], padding
                # at positions every later step masks until it overwrites
                # them (models/llama.py `_decode_attend`).
                ck.value = jax.lax.dynamic_update_slice(ck.value, k,
                                                        (0, 0, 0, 0))
                cv.value = jax.lax.dynamic_update_slice(cv.value, v,
                                                        (0, 0, 0, 0))
            out = attn_lib.flash_attention_on_mesh(q, k, v, self.mesh)
        elif s > 1:
            out = self._chunk(ck, cv, q, k, v, positions)
        else:
            out = self._step(ck, cv, q, k[:, :, 0], v[:, :, 0],
                             positions[:, 0], live)
        return nn.DenseGeneral(
            features=cfg.dim, axis=(-2, -1), use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name='up_proj')(
                out.transpose(0, 2, 1, 3))

    def _chunk(self, ck, cv, q, k, v, positions):
        """A chunk of a long prompt against the cache: its rows land at
        their positions (a scatter, which drops rows past the cache's
        end) and it attends over what the cache then holds."""
        b = q.shape[0]
        rows = jnp.arange(b)[:, None]
        ck.value = ck.value.at[rows, :, positions, :].set(
            k.transpose(0, 2, 1, 3))
        cv.value = cv.value.at[rows, :, positions, :].set(
            v.transpose(0, 2, 1, 3))
        kept = self.cfg.max_seq_len
        return attn_lib.mha_reference(
            q, ck.value, cv.value, causal=True, segment_positions=positions,
            kv_positions=jnp.broadcast_to(jnp.arange(kept)[None, :],
                                          (b, kept)))

    def _step(self, ck, cv, q, k, v, pos, live):
        """One position a slot: this step's rows k, v [B, Hkv, D] written
        at `pos` [B], then attention up to the row just written, and
        nothing of a row that holds no request (`live`).  The rows are
        scattered over (slot x head, position), which leaves each leaf
        row-major as the kernel reads it (models/llama.py
        `_decode_attend` says why)."""
        b = pos.shape[0]

        def write(cache, row):
            n, kept, wide = cache.shape[1:]
            flat = cache.reshape(b * n, kept, wide)
            flat = flat.at[jnp.arange(b * n), jnp.repeat(pos, n), :].set(
                row.reshape(b * n, wide))
            return flat.reshape(cache.shape)

        ck.value, cv.value = write(ck.value, k), write(cv.value, v)
        lens = pos + 1 if live is None else jnp.where(live, pos + 1, 0)
        return attn_lib.decode_attention(q, ck.value, cv.value, lens,
                                         self.mesh)


class Router(nn.Module):
    """z [T, dim], the previous layer's state [T, router_dim] or None ->
    (the chosen output [T, 1], its probability [T, 1], this layer's
    state): the module docstring's router, float32 throughout."""
    cfg: ZayaConfig

    @nn.compact
    def __call__(self, z: jax.Array, carried: Optional[jax.Array]):
        cfg = self.cfg
        r, out = cfg.router_dim, cfg.n_experts + 1

        def matrix(name, shape):
            return self.param(name, nn.initializers.lecun_normal(), shape,
                              cfg.param_dtype).astype(jnp.float32)

        dot = functools.partial(jnp.dot,
                                precision=jax.lax.Precision.HIGHEST)
        state = dot(z.astype(jnp.float32), matrix('down', (cfg.dim, r))) + \
            _vector(self, 'down_b', (r,))
        # Every layer holds a gamma; the first has no state to scale.
        gamma = _vector(self, 'gamma', (r,), nn.initializers.ones)
        if carried is not None:
            state = state + gamma * carried
        h = state * jax.lax.rsqrt(
            jnp.mean(state * state, axis=-1, keepdims=True) + cfg.norm_eps)
        h = h * _vector(self, 'norm', (r,), nn.initializers.ones)
        for i in (1, 2):
            h = jax.nn.gelu(dot(h, matrix(f'w{i}', (r, r))) +
                            _vector(self, f'b{i}', (r,)), approximate=False)
        p = jax.nn.softmax(dot(h, matrix('w3', (r, out))), axis=-1)
        # The balancing bias decides who is chosen and never the weight.
        e = jnp.argmax(p + _vector(self, 'balance', (out,)), axis=-1)
        return (e[:, None].astype(jnp.int32),
                jnp.take_along_axis(p, e[:, None], axis=-1), state)


class Merge(nn.Module):
    """(u + b_u) * s_u + (f + b_f) * s_f: a residual sum whose two terms
    are each shifted and scaled by learned vectors."""
    cfg: ZayaConfig

    @nn.compact
    def __call__(self, u: jax.Array, f: jax.Array) -> jax.Array:
        d = (self.cfg.dim,)
        ones = nn.initializers.ones
        out = ((u.astype(jnp.float32) + _vector(self, 'stream_bias', d)) *
               _vector(self, 'stream_scale', d, ones) +
               (f.astype(jnp.float32) + _vector(self, 'branch_bias', d)) *
               _vector(self, 'branch_scale', d, ones))
        return out.astype(self.cfg.dtype)


class Block(nn.Module):
    cfg: ZayaConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x, carried, positions, decode, lengths, live):
        """-> (the stream, this layer's router state [B, S, router_dim])."""
        cfg = self.cfg
        b, s, d = x.shape

        def norm(name, inp):
            return RMSNorm(cfg.norm_eps, cfg.dtype, cfg.param_dtype,
                           name=name)(inp)

        a = Merge(cfg, name='attn_merge')(
            x, CCA(cfg, self.mesh, name='attn')(
                norm('attn_norm', x), positions, decode, lengths, live))
        z = norm('ffn_norm', a)
        flat = z.reshape(b * s, d)
        e, w, state = Router(cfg, name='router')(
            flat, None if carried is None else carried.reshape(b * s, -1))
        # A padded prompt's rows past its length are not the experts' to
        # multiply.
        valid = None if lengths is None or s == 1 else \
            jnp.arange(s)[None, :] < lengths[:, None]
        experts = moe_lib.DroplessMoE(
            dim=d, ffn_dim=cfg.expert_dim, n_experts=cfg.n_experts,
            held=tuple(range(cfg.n_experts)), n_shared=0, n_skip=1,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            block=cfg.expert_block, mesh=self.mesh, name='moe')(
                z, valid, routed=(e, w))
        # The skip: no expert runs, the token keeps w * z.
        skip = jnp.where(e == cfg.n_experts, w, 0.0) * flat.astype(
            jnp.float32)
        branch = experts.astype(jnp.float32) + skip.reshape(b, s, d)
        return (Merge(cfg, name='ffn_merge')(a, branch),
                state.reshape(b, s, -1))


class Zaya(nn.Module):
    """tokens [B, S] -> logits [B, S, vocab] float32.  `lengths` [B]: the
    valid positions of each row of this call (None: all S); with it and
    S > 1 the logits are those of each row's last valid position alone,
    [B, 1, vocab].  `live` [B] bool (the decode step): the rows that hold
    a request; the others read nothing of their K and V."""
    cfg: ZayaConfig
    # The mesh the program is partitioned over, if any: the Pallas kernels
    # are for one device (ops/attention.py, models/moe.py `expert_tile`).
    mesh: Optional[Mesh] = None

    def served(self) -> Served:
        cfg = self.cfg
        return Served(
            unpaged_cache=('keeps the last position\'s convolution taps '
                           'and half value, of fixed size a slot, beside '
                           'its keys and values'),
            # One row at a time through the whole stack: a row of 12,288
            # positions is 50 MB a copy of the stream, and a layer's
            # latent, taps, router state and expert loop hold a dozen.
            prefill_rows=1,
            decode_takes_live=True,
            decode_kv_block=attn_lib.decode_kv_block(
                cfg.n_kv_heads, cfg.head_dim, cfg.max_seq_len, cfg.dtype,
                self.mesh),
            publish_stats=functools.partial(
                moe_lib.publish_stats, tuple(range(cfg.n_experts))))

    @nn.compact
    def __call__(self, tokens: jax.Array,
                 positions: Optional[jax.Array] = None,
                 decode: bool = False,
                 lengths: Optional[jax.Array] = None,
                 live: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.cfg
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1])[None, :], tokens.shape)
        embed = nn.Embed(cfg.vocab_size, cfg.dim, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype,
                         embedding_init=nn.initializers.normal(stddev=1.0),
                         name='embed')
        x, carried = embed(tokens), None
        for i in range(cfg.n_layers):
            x, carried = Block(cfg, self.mesh, name=f'layer_{i}')(
                x, carried, positions, decode, lengths, live)
            if x.shape[1] > 1:
                # A prompt's layers one after the other.  Left to itself
                # the TPU compiler keeps a dozen layers' float32 sums of
                # 12,288 rows alive at once: a row's prefill then wants
                # 3.9 GB of temporaries beside 13.7 GB of weights and
                # cache, with the barrier 2.2 (scratch compiles of the
                # whole program for a described v5e, PR 47).
                x, carried = jax.lax.optimization_barrier((x, carried))
        if lengths is not None and x.shape[1] > 1:
            # A prefill reads one position's logits a row, the last valid
            # one: the head runs on that position alone ([B, 1, vocab]).
            x = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)
        x = RMSNorm(cfg.norm_eps, cfg.dtype, cfg.param_dtype,
                    name='final_norm')(x)
        return embed.attend(x).astype(jnp.float32)
