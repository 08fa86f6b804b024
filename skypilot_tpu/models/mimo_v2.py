"""MiMo-V2 decoder: window layers beside full layers, each kind with its
own KV heads, RoPE base and cache; keys wider than values; a sink in the
window softmax; a leading dense layer, then a dropless expert layer whose
router chooses by a correction bias.

The published architecture (`model_type` `mimo_v2`).  With N an RMSNorm a
block is pre-norm,

    h = x + Attn_i(N1(x))        y = h + FFN_i(N2(h))

a final norm and an untied head close the model.  `layer_pattern[i]` says
which attention layer i has:

- 0, **full**: `n_kv_heads` KV heads, RoPE base `rope_theta`, causal over
  the whole context, a plain softmax;
- 1, **window**: `window_kv_heads` KV heads, RoPE base
  `window_rope_theta`, position i sees j iff i - `window` < j <= i, and a
  learned logit a query head, the sink, that joins the softmax's
  denominator and brings no value:
  `p_j = exp(a_j - m) / (exp(s_h - m) + sum_j exp(a_j - m))`.

Both have `n_heads` query heads, queries and keys of `qk_dim` (192) of
which the first `rope_dim` (64) are rotated (the two halves of the 64
paired) and the rest are not, values of `v_dim` (128) multiplied by
`value_scale` where they are made, scores scaled by `qk_dim ** -0.5`, no
biases.  FFN is a dense SwiGLU in the first `n_dense_layers` blocks and
`models/moe.py DroplessMoE` after them: sigmoid scores, the `top_k`
largest of score + correction bias, weighted by the scores alone,
normalised; no shared expert.

**Two kinds of cache, a slot.**  A full layer keeps every position of the
context, a window layer the last `window` alone, as a ring: position p
lives at p % window.  Keys are rotated before they are cached and a
softmax does not care for the order of its terms, so the decode step's
attention over a ring is attention over a cache of `window` positions
bounded by min(length, window), and the kernel needs nothing of its own
for it.  The leaves (`DecodeEngine` inserts, donates and lays them out as
any others; `perf/cost_model.py` reads the kinds from the names below):

    full    k: {nope [B, Hkv, S, 128], rope [B, Hkv/2, S, 128]}
            v: [B, Hkv, S, 128]
    window  ring_k: {nope [B, Hw, W, 128], rope [B, Hw/2, W, 128]}
            ring_v: [B, Hw, W, 128]

A key of 192 is 1.5 lane tiles, which a leaf of its own would pad to 256
in HBM.  It is cached as its unrotated 128 and its rotated 64, the 64 of
two KV heads side by side in one row of 128 lanes (`rope`): 320 values a
head and position, every leaf whole tiles, every write a whole row
(`ops/pallas/decode_attention.py` has the kernel's side of it).

Paths from the one set of weights: a prompt through the engine (no cache
yet) attends over itself, the full layers under the causal mask and the
window layers inside their band (`ops/attention.py
flash_attention_on_mesh`: the Pallas flash kernel on the TPU, tiles
outside the band neither fetched nor multiplied), and leaves the whole
prompt in a full layer's cache and each row's last `window` valid
positions in a ring; the decode step (S == 1) writes its row at the
position, or at position % window, and reads through
`ops/attention.py decode_attention`; a chunk of a long prompt (S > 1
against a cache) reads a ring's positions back from where they lie;
without a cache (`decode=False`) the whole sequence.

Not here: the vision and audio towers and the three multi-token-prediction
layers of the published model (they draft for speculative decoding, which
the engine offers over the paged pool only, and the paged pool holds one
kind of K and V: ROADMAP B2, B3).
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
from skypilot_tpu.models.llama import RMSNorm, _rope
from skypilot_tpu.models.openpangu_moe import DenseFFN
from skypilot_tpu.models.served import Served
from skypilot_tpu.ops import attention as attn_lib


# A window layer's cache leaves: rings of `window` positions a slot (what
# the model declares as `Served.window_leaves`).
WINDOW_LEAVES = ('ring_k', 'ring_v')


def published_pattern(n_layers: int) -> Tuple[int, ...]:
    """`hybrid_layer_pattern` as published: layer 0 and every sixth layer
    from 5 on are full (0), the others window (1)."""
    return tuple(0 if i == 0 or i % 6 == 5 else 1 for i in range(n_layers))


@dataclasses.dataclass(frozen=True)
class MiMoV2Config:
    vocab_size: int = 152576
    dim: int = 4096
    n_layers: int = 48
    layer_pattern: Tuple[int, ...] = published_pattern(48)
    n_dense_layers: int = 1            # leading blocks with a dense FFN
    n_heads: int = 64
    qk_dim: int = 192                  # head_dim: queries and keys
    v_dim: int = 128                   # v_head_dim
    rope_dim: int = 64                 # the first of qk_dim, rotated
    n_kv_heads: int = 4                # a full layer's
    window_kv_heads: int = 8           # a window layer's
    window: int = 128
    rope_theta: float = 1e7
    window_rope_theta: float = 1e4
    value_scale: float = 0.707
    ffn_dim: int = 16384               # the dense layers' width
    n_experts: int = 256
    held_experts: Tuple[int, ...] = tuple(range(256))
    experts_per_token: int = 8
    expert_dim: int = 2048
    # Pairs a trip of the expert layer's loop over blocks (a prefill).  A
    # row of 8,192 tokens sends 8,192 x 8 / 256 = 256 pairs to an expert on
    # average, 16 more or fewer by the row: at `DroplessMoE`'s 256 an
    # expert takes one trip or two by the toss of a coin, and a wave's
    # prefill 7.49-7.70 s by the seed (PERF.md section 6, PR 41); 384
    # holds 8 standard deviations more, one trip an expert.
    expert_block: int = 384
    norm_eps: float = 1e-5
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def kv_heads(self, i: int) -> int:
        return self.window_kv_heads if self.layer_pattern[i] else \
            self.n_kv_heads

    def attention_params(self, i: int) -> int:
        d, h = self.dim, self.n_heads
        return (d * h * self.qk_dim +
                d * self.kv_heads(i) * (self.qk_dim + self.v_dim) +
                h * self.v_dim * d + (h if self.layer_pattern[i] else 0))

    def layer_params(self, i: int) -> int:
        d = self.dim
        if i < self.n_dense_layers:
            ffn = 3 * d * self.ffn_dim
        else:
            ffn = (d * self.n_experts + self.n_experts +
                   3 * d * self.expert_dim * len(self.held_experts))
        return self.attention_params(i) + ffn + 2 * d

    def num_params(self) -> int:
        """Parameters held here (the held experts, the held vocabulary)."""
        return (sum(self.layer_params(i) for i in range(self.n_layers)) +
                2 * self.vocab_size * self.dim + self.dim)


def ring_source(lengths: jax.Array, window: int) -> jax.Array:
    """[B, window]: the position that ring row r holds once a sequence has
    `lengths[b]` positions, the last one that is r modulo the window;
    negative where the sequence has none yet."""
    last = (lengths - 1)[:, None]
    return last - (last - jnp.arange(window)[None, :]) % window


class Attention(nn.Module):
    """One layer's attention, full or window (`windowed`)."""
    cfg: MiMoV2Config
    windowed: bool
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array, decode: bool,
                 lengths: Optional[jax.Array] = None,
                 live: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.cfg
        b, s, _ = x.shape
        rope, nope = cfg.rope_dim, cfg.qk_dim - cfg.rope_dim
        h_kv = cfg.window_kv_heads if self.windowed else cfg.n_kv_heads
        theta = cfg.window_rope_theta if self.windowed else cfg.rope_theta
        window = cfg.window if self.windowed else 0
        scale = cfg.qk_dim ** -0.5

        def heads(name, n, width):      # -> [B, n, S, width]
            return nn.DenseGeneral(
                features=(n, width), axis=-1, use_bias=False,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name=name)(x).transpose(0, 2, 1, 3)

        q = heads('q_proj', cfg.n_heads, cfg.qk_dim)
        k = heads('k_proj', h_kv, cfg.qk_dim)
        v = (heads('v_proj', h_kv, cfg.v_dim).astype(jnp.float32) *
             cfg.value_scale).astype(cfg.dtype)
        # [rotated | not rotated] of a head's qk_dim.
        q_rope, q_nope = _rope(q[..., :rope], positions, theta), q[..., rope:]
        k_rope, k_nope = _rope(k[..., :rope], positions, theta), k[..., rope:]
        sink = self.param('sink', nn.initializers.zeros, (cfg.n_heads,),
                          cfg.param_dtype).astype(jnp.float32) \
            if self.windowed else None

        def whole(q_nope, q_rope, k_nope, k_rope, v, **mask):
            """Attention with the two parts of queries and keys side by
            side; over the sequence itself, or under `mask`'s positions."""
            q_all = jnp.concatenate([q_nope, q_rope], axis=-1)
            k_all = jnp.concatenate([k_nope, k_rope], axis=-1)
            if mask:
                return attn_lib.mha_reference(
                    q_all, k_all, v, causal=True, scale=scale, window=window,
                    sink=sink, **mask)
            return attn_lib.flash_attention_on_mesh(
                q_all, k_all, v, self.mesh, causal=True, window=window,
                sink=sink)

        if not decode:
            out = whole(q_nope, q_rope, k_nope, k_rope, v)
        else:
            kept = window or cfg.max_seq_len
            names = WINDOW_LEAVES if self.windowed else ('k', 'v')
            fresh = not self.has_variable('cache', names[0])
            ck = self.variable('cache', names[0], lambda: {
                'nope': jnp.zeros((b, h_kv, kept, nope), cfg.dtype),
                'rope': jnp.zeros((b, h_kv // 2, kept, 2 * rope),
                                  cfg.dtype)})
            cv = self.variable('cache', names[1], jnp.zeros,
                               (b, h_kv, kept, cfg.v_dim), cfg.dtype)
            if fresh:
                out = whole(q_nope, q_rope, k_nope, k_rope, v)
                self._keep_prompt(ck, cv, k_nope, k_rope, v, lengths)
            elif s > 1:
                out = self._chunk(ck, cv, q_nope, q_rope, k_nope, k_rope, v,
                                  positions, lengths, whole)
            else:
                out = self._step(ck, cv, q_nope, q_rope, k_nope[:, :, 0],
                                 k_rope[:, :, 0], v[:, :, 0],
                                 positions[:, 0], live, sink, scale)
        return nn.DenseGeneral(
            features=cfg.dim, axis=(-2, -1), use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name='o_proj')(
                out.transpose(0, 2, 1, 3))

    def _keep_prompt(self, ck, cv, k_nope, k_rope, v, lengths):
        """A prompt's K and V into a cache just made.  Prompts are left-
        aligned: a full layer's is cache[:S], with padding at positions
        that every later step masks until it overwrites them
        (models/llama.py `_decode_attend`).  A ring takes each row's last
        `window` VALID positions (`lengths` [B]; None: all S), position p
        at p % window; rows of the ring that a short prompt has not
        reached hold whatever was gathered, and min(length, window)
        bounds them out."""
        k_rope = attn_lib.pack_rope_keys(k_rope)
        if self.windowed:
            b, s = v.shape[0], v.shape[2]
            if lengths is None:
                lengths = jnp.full((b,), s, jnp.int32)
            at = jnp.clip(ring_source(lengths, self.cfg.window), 0, s - 1)
            take = lambda t: jnp.take_along_axis(  # noqa: E731
                t, at[:, None, :, None], axis=2)
            ck.value = {'nope': take(k_nope), 'rope': take(k_rope)}
            cv.value = take(v)
            return
        put = lambda big, small: jax.lax.dynamic_update_slice(  # noqa: E731
            big, small, (0, 0, 0, 0))
        ck.value = {'nope': put(ck.value['nope'], k_nope),
                    'rope': put(ck.value['rope'], k_rope)}
        cv.value = put(cv.value, v)

    def _chunk(self, ck, cv, q_nope, q_rope, k_nope, k_rope, v, positions,
               lengths, whole):
        """A chunk of a long prompt against the cache: it attends over
        what the cache holds before it and over itself, and its rows land
        at their positions.  A full layer's scatter drops rows past the
        cache's end.  A ring's rows are read back with the positions they
        hold (`ring_source` of the chunk's start) in front of the chunk's
        own, and it then takes the last `window` of the chunk's VALID
        positions (`lengths`: the valid rows of this chunk)."""
        cfg = self.cfg
        b, s = positions.shape
        rows = jnp.arange(b)[:, None]
        if not self.windowed:
            def put(big, small):
                return big.at[rows, :, positions, :].set(
                    small.transpose(0, 2, 1, 3))
            ck.value = {'nope': put(ck.value['nope'], k_nope),
                        'rope': put(ck.value['rope'],
                                    attn_lib.pack_rope_keys(k_rope))}
            cv.value = put(cv.value, v)
            kept = cfg.max_seq_len
            return whole(
                q_nope, q_rope, ck.value['nope'],
                attn_lib.unpack_rope_keys(ck.value['rope']), cv.value,
                segment_positions=positions,
                kv_positions=jnp.broadcast_to(jnp.arange(kept)[None, :],
                                              (b, kept)))
        start = positions[:, 0]
        held = ring_source(start, cfg.window)            # [B, W]
        # A ring row with no position yet is put after every query.
        held = jnp.where(held < 0, jnp.iinfo(jnp.int32).max, held)
        side = lambda old, new: jnp.concatenate([old, new],  # noqa: E731
                                                axis=2)
        out = whole(
            q_nope, q_rope, side(ck.value['nope'], k_nope),
            side(attn_lib.unpack_rope_keys(ck.value['rope']), k_rope),
            side(cv.value, v), segment_positions=positions,
            kv_positions=jnp.concatenate([held, positions], axis=1))
        if lengths is None:
            lengths = jnp.full((b,), s, jnp.int32)
        source = ring_source(start + lengths, cfg.window) - start[:, None]
        fresh = (source >= 0)[:, None, :, None]
        at = jnp.clip(source, 0, s - 1)[:, None, :, None]
        take = lambda old, new: jnp.where(  # noqa: E731
            fresh, jnp.take_along_axis(new, at, axis=2), old)
        ck.value = {'nope': take(ck.value['nope'], k_nope),
                    'rope': take(ck.value['rope'],
                                 attn_lib.pack_rope_keys(k_rope))}
        cv.value = take(cv.value, v)
        return out

    def _step(self, ck, cv, q_nope, q_rope, k_nope, k_rope, v, pos, live,
              sink, scale):
        """One position a slot: this step's rows k_nope [B, Hkv, 128],
        k_rope [B, Hkv, 64], v [B, Hkv, 128] written at `pos` [B] (a
        ring: at pos % window), then attention up to the row just
        written (a ring: over min(pos + 1, window) rows), and nothing of
        a row that holds no request (`live` [B] bool, where the engine
        gives it).  The rows are scattered over (slot x head, position)
        as rows of 128, which leaves each leaf row-major as the kernel
        reads it (models/llama.py `_decode_attend` says why)."""
        b = pos.shape[0]
        reach = pos + 1
        if self.windowed:
            pos, reach = pos % self.cfg.window, jnp.minimum(
                reach, self.cfg.window)

        def write(cache, row):          # row [B, heads, 128]
            n, kept, wide = cache.shape[1:]
            flat = cache.reshape(b * n, kept, wide)
            flat = flat.at[jnp.arange(b * n), jnp.repeat(pos, n), :].set(
                row.reshape(b * n, wide))
            return flat.reshape(cache.shape)

        ck.value = {'nope': write(ck.value['nope'], k_nope),
                    'rope': write(ck.value['rope'],
                                  k_rope.reshape(b, -1, 2 * k_rope.shape[-1]))}
        cv.value = write(cv.value, v)
        lens = reach if live is None else jnp.where(live, reach, 0)
        return attn_lib.decode_attention(
            q_nope, ck.value['nope'], cv.value, lens, self.mesh,
            q_rope=q_rope, k_rope=ck.value['rope'], sink=sink, scale=scale)


class Block(nn.Module):
    cfg: MiMoV2Config
    index: int
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x, positions, decode, lengths, live):
        cfg = self.cfg

        def norm(name, inp):
            return RMSNorm(cfg.norm_eps, cfg.dtype, cfg.param_dtype,
                           name=name)(inp)

        x = x + Attention(cfg, bool(cfg.layer_pattern[self.index]),
                          self.mesh, name='attn')(
                              norm('attn_norm', x), positions, decode,
                              lengths, live)
        if self.index < cfg.n_dense_layers:
            ffn = DenseFFN(cfg, name='mlp')
        else:
            ffn = moe_lib.DroplessMoE(
                dim=cfg.dim, ffn_dim=cfg.expert_dim,
                n_experts=cfg.n_experts, held=cfg.held_experts,
                router=moe_lib.LinearRouter(top_k=cfg.experts_per_token,
                                            bias=True),
                n_shared=0, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                block=cfg.expert_block, mesh=self.mesh, name='moe')
        h = norm('ffn_norm', x)
        if self.index < cfg.n_dense_layers or lengths is None or \
                x.shape[1] == 1:
            return x + ffn(h)
        # A padded prompt's rows past its length are not the experts' to
        # multiply: a quarter of a wave's rows here, all of one token, which
        # an untrained router sends to the same few experts (a wave's
        # prefill then took 7.5-7.9 s by whether the seed's held 16 were
        # among them: PERF.md section 6, PR 41).
        return x + ffn(h, jnp.arange(x.shape[1])[None, :] < lengths[:, None])


class MiMoV2(nn.Module):
    """tokens [B, S] -> logits [B, S, vocab] float32.  `lengths` [B]: the
    valid positions of each row of this call (None: all S); with it and
    S > 1 the logits are those of each row's last valid position alone,
    [B, 1, vocab].  `live` [B] bool (the decode step): the rows that hold
    a request; the others read nothing of their caches."""
    cfg: MiMoV2Config
    # The mesh the program is partitioned over, if any: the Pallas kernels
    # are for one device (ops/attention.py, models/moe.py `expert_tile`).
    mesh: Optional[Mesh] = None

    def served(self) -> Served:
        cfg = self.cfg
        return Served(
            # A slot's cache is of two kinds; the page manager holds one.
            unpaged_cache=("keeps a ring of its window's positions in its "
                           'window layers beside the whole context in its '
                           'full layers'),
            # Rings of `window` positions: a step reads min(context,
            # window) of them, whatever the context.
            window_leaves=WINDOW_LEAVES,
            # One row at a time through the whole stack: a row of 8,192
            # positions builds 64 heads' queries of 192 (0.2 GB), a full
            # layer's K and V repeated for them (0.33 GB) and the outputs,
            # beside 6.9 GB of weights and 1.6 GB of cache.
            prefill_rows=1,
            decode_takes_live=True,
            # A tile of a FULL layer's decode attention.
            decode_kv_block=attn_lib.decode_kv_block(
                cfg.n_kv_heads, cfg.qk_dim - cfg.rope_dim, cfg.max_seq_len,
                cfg.dtype, self.mesh),
            publish_stats=functools.partial(moe_lib.publish_stats,
                                            cfg.held_experts))

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
        x = nn.Embed(cfg.vocab_size, cfg.dim, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype,
                     embedding_init=nn.initializers.normal(stddev=1.0),
                     name='embed')(tokens)
        for i in range(cfg.n_layers):
            x = Block(cfg, i, self.mesh, name=f'layer_{i}')(
                x, positions, decode, lengths, live)
        if lengths is not None and x.shape[1] > 1:
            # A prefill reads one position's logits a row, the last valid
            # one: the head runs on that position alone ([B, 1, vocab]).
            x = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)
        x = RMSNorm(cfg.norm_eps, cfg.dtype, cfg.param_dtype,
                    name='final_norm')(x)
        logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                          param_dtype=cfg.param_dtype, name='lm_head')(x)
        return logits.astype(jnp.float32)
