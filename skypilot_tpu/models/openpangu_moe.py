"""openPangu-Ultra-MoE decoder: multi-head latent attention, sandwich
norms, leading dense layers, then a dropless expert layer in every block.

The published architecture (`model_type` `pangu_ultra_moe`).  With N_i an
RMSNorm, a block is

    h = x + N2(Attn(N1(x)))        y = h + N4(FFN(N3(h)))

(`sandwich_norm`: a norm before and after each sublayer).  FFN is a dense
SwiGLU in the first `n_dense_layers` blocks and `models/moe.py`
`DroplessMoE` (told which experts it holds) after them; a final norm and
an untied head close the model.

Attention is multi-head latent attention (MLA, arXiv:2405.04434): queries
and keys/values are made through low-rank bottlenecks, and the keys and
values of a position are functions of ONE vector a layer, the latent:

    c_q = N_q(W_qa x)                      [q_rank]
    q_h = W_qb,h c_q = [q_nope_h | q_pe_h] [nope + rope], q_pe_h rotated
    [c_kv | k_pe] = W_kva x                [kv_rank + rope]
    c_kv = N_kv(c_kv),  k_pe rotated       (one k_pe shared by every head)
    [k_nope_h | v_h] = W_kvb,h c_kv        [nope + v]
    s_h = (q_nope_h . k_nope_h + q_pe_h . k_pe) / sqrt(nope + rope)
    o = W_o concat_h(softmax(s_h) v_h)

**What is cached a position and layer is `c_kv` after its norm and the
rotated `k_pe`: kv_rank + rope values (512 + 64), not H heads' keys and
values.**  They are the `cache` leaves `c_kv` [B, S, kv_rank] and `k_pe`
[B, S, rope], the slot leading, per position like Llama's `k` / `v` but
with no head axis: `DecodeEngine` inserts, donates and lays them out like
any other leaf, and `perf/cost_model.py` counts them as `kind="latent"`.

Two paths from the one set of weights:

- a prompt (S > 1) expands K and V per head from the latent through
  `W_kvb` and runs causal attention with keys of nope + rope and values of
  v (`ops/attention.py flash_attention_on_mesh`: the Pallas flash kernel on
  the TPU, the XLA reference elsewhere);
- the decode step (S == 1) absorbs `W_kvb` into the query and the output
  and never expands the cache: with `W_kvb,h = [W_UK,h | W_UV,h]`,

      q_lat,h = W_UK,h^T q_nope_h          [kv_rank]
      s_h = (q_lat,h . c_kv + q_pe_h . k_pe) / sqrt(nope + rope)
      o_lat,h = sum_t softmax(s_h)_t c_kv,t
      v_out,h = W_UV,h o_lat,h

  which is attention of H query heads over one shared key of kv_rank +
  rope of which the first kv_rank are also the value:
  `ops/attention.py latent_decode_attention` (a Pallas kernel bounded by
  the slots' lengths on one TPU device, plain `jnp` elsewhere).

The row written by the decode step is scattered over (slot, position) as
a row of the leaf's width, which leaves the cache row-major as the kernel
reads it (`models/llama.py` `_decode_attend` says why).

The multi-token-prediction layer of the published model
(`num_nextn_predict_layers`) is not here: it drafts for speculative
decoding, which the engine offers over the paged pool only, and the paged
pool holds keys and values, not a latent (ROADMAP B3).
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
from skypilot_tpu.models.served import Served
from skypilot_tpu.ops import attention as attn_lib


@dataclasses.dataclass(frozen=True)
class OpenPanguMoEConfig:
    vocab_size: int = 153600
    dim: int = 7680
    n_layers: int = 61
    n_dense_layers: int = 3            # leading blocks with a dense FFN
    n_heads: int = 128
    q_rank: int = 1536                 # q_lora_rank
    kv_rank: int = 512                 # kv_lora_rank: the latent's width
    nope_dim: int = 128                # qk_nope_head_dim
    rope_dim: int = 64                 # qk_rope_head_dim
    v_dim: int = 128                   # v_head_dim
    ffn_dim: int = 18432               # the dense layers' width
    n_experts: int = 256
    held_experts: Tuple[int, ...] = tuple(range(256))
    experts_per_token: int = 8
    expert_dim: int = 2048
    n_shared_experts: int = 1
    routed_scaling: float = 2.5
    rope_theta: float = 25600000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def attention_params(self) -> int:
        d, h = self.dim, self.n_heads
        return (d * self.q_rank + self.q_rank +
                self.q_rank * h * (self.nope_dim + self.rope_dim) +
                d * (self.kv_rank + self.rope_dim) + self.kv_rank +
                self.kv_rank * h * (self.nope_dim + self.v_dim) +
                h * self.v_dim * d)

    def layer_params(self, i: int) -> int:
        d = self.dim
        if i < self.n_dense_layers:
            ffn = 3 * d * self.ffn_dim
        else:
            ffn = (d * self.n_experts + 3 * d * self.expert_dim * (
                len(self.held_experts) + self.n_shared_experts))
        return self.attention_params() + ffn + 4 * d

    def num_params(self) -> int:
        """Parameters held here (the held experts, the held vocabulary)."""
        return (sum(self.layer_params(i) for i in range(self.n_layers)) +
                2 * self.vocab_size * self.dim + self.dim)


class LatentAttention(nn.Module):
    cfg: OpenPanguMoEConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 decode: bool) -> jax.Array:
        cfg = self.cfg
        b, s, _ = x.shape
        h, nope, rope, rank = (cfg.n_heads, cfg.nope_dim, cfg.rope_dim,
                               cfg.kv_rank)

        def dense(name, features, inp, axis=-1):
            return nn.DenseGeneral(
                features=features, axis=axis, use_bias=False,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype, name=name)(inp)

        def norm(name, inp):
            return RMSNorm(cfg.norm_eps, cfg.dtype, cfg.param_dtype,
                           name=name)(inp)

        q = dense('q_b', (h, nope + rope), norm('q_norm', dense(
            'q_a', cfg.q_rank, x))).transpose(0, 2, 1, 3)   # [B, H, S, .]
        q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], positions,
                                            cfg.rope_theta)
        kv = dense('kv_a', rank + rope, x)
        c_kv = norm('kv_norm', kv[..., :rank])               # [B, S, rank]
        k_pe = _rope(kv[:, None, :, rank:], positions,
                     cfg.rope_theta)[:, 0]                   # [B, S, rope]
        # W_kvb [rank, H, nope + v]: a head's [W_UK | W_UV].
        w_kvb = self.param('kv_b', nn.initializers.lecun_normal(),
                           (rank, h, nope + cfg.v_dim),
                           cfg.param_dtype).astype(cfg.dtype)
        scale = (nope + rope) ** -0.5

        def expanded(c_all, pe_all, **mask):
            """Attention over K and V expanded from the latent `c_all`
            [B, T, rank] and `pe_all` [B, T, rope]."""
            kv_h = jnp.einsum('btc,chd->bhtd', c_all, w_kvb)
            k = jnp.concatenate([kv_h[..., :nope], jnp.broadcast_to(
                pe_all[:, None], (b, h) + pe_all.shape[1:])], axis=-1)
            q_all = jnp.concatenate([q_nope, q_pe], axis=-1)
            if mask:
                return attn_lib.mha_reference(q_all, k, kv_h[..., nope:],
                                              causal=True, scale=scale,
                                              **mask)
            return attn_lib.flash_attention_on_mesh(
                q_all, k, kv_h[..., nope:], self.mesh, causal=True)

        if not decode:
            out = expanded(c_kv, k_pe)
        else:
            fresh = not self.has_variable('cache', 'c_kv')
            cc = self.variable('cache', 'c_kv', jnp.zeros,
                               (b, cfg.max_seq_len, rank), cfg.dtype)
            cp = self.variable('cache', 'k_pe', jnp.zeros,
                               (b, cfg.max_seq_len, rope), cfg.dtype)
            if fresh:
                # Left-aligned prompts: the prompt is cache[:S], and
                # attention is over the prompt itself.  Padding lies at
                # positions that every later step masks until it
                # overwrites them (models/llama.py `_decode_attend`).
                cc.value = jax.lax.dynamic_update_slice(cc.value, c_kv,
                                                        (0, 0, 0))
                cp.value = jax.lax.dynamic_update_slice(cp.value, k_pe,
                                                        (0, 0, 0))
                out = expanded(c_kv, k_pe)
            elif s > 1:
                # A chunk of a long prompt against the cache: its rows
                # land at their positions (a scatter: out-of-range updates
                # drop) and it attends over everything before them.
                rows = jnp.arange(b)[:, None]
                cc.value = cc.value.at[rows, positions, :].set(c_kv)
                cp.value = cp.value.at[rows, positions, :].set(k_pe)
                out = expanded(
                    cc.value, cp.value, segment_positions=positions,
                    kv_positions=jnp.broadcast_to(
                        jnp.arange(cfg.max_seq_len)[None, :],
                        (b, cfg.max_seq_len)))
            else:
                out = self._decode_step(q_nope[:, :, 0], q_pe[:, :, 0],
                                        c_kv[:, 0], k_pe[:, 0],
                                        positions[:, 0], cc, cp, w_kvb,
                                        scale)[:, :, None]
        return dense('o_proj', cfg.dim, out.transpose(0, 2, 1, 3),
                     axis=(-2, -1))

    def _decode_step(self, q_nope, q_pe, c_row, pe_row, pos, cc, cp, w_kvb,
                     scale):
        """One position a slot, absorbed: q_nope [B, H, nope], q_pe
        [B, H, rope], this step's latent row c_row [B, rank] and pe_row
        [B, rope] written at `pos` [B]; the cache is read as it is
        stored and never expanded.  Returns [B, H, v]."""
        nope = self.cfg.nope_dim
        rows = jnp.arange(pos.shape[0])
        cc.value = cc.value.at[rows, pos, :].set(c_row)
        cp.value = cp.value.at[rows, pos, :].set(pe_row)
        q_lat = jnp.einsum('bhn,chn->bhc', q_nope, w_kvb[..., :nope],
                           preferred_element_type=jnp.float32) * scale
        o_lat = attn_lib.latent_decode_attention(
            q_lat, q_pe.astype(jnp.float32) * scale, cc.value, cp.value,
            pos + 1, self.mesh)
        return jnp.einsum('bhc,chv->bhv', o_lat, w_kvb[..., nope:])


class DenseFFN(nn.Module):
    """SwiGLU of the leading dense layers."""
    cfg: OpenPanguMoEConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        dense = lambda name, feat: nn.Dense(  # noqa: E731
            feat, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name)
        return dense('down_proj', cfg.dim)(
            nn.silu(dense('gate_proj', cfg.ffn_dim)(x)) *
            dense('up_proj', cfg.ffn_dim)(x))


class Block(nn.Module):
    cfg: OpenPanguMoEConfig
    index: int
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x, positions, decode):
        cfg = self.cfg

        def norm(name, inp):
            return RMSNorm(cfg.norm_eps, cfg.dtype, cfg.param_dtype,
                           name=name)(inp)

        x = x + norm('attn_post_norm', LatentAttention(
            cfg, self.mesh, name='attn')(norm('attn_norm', x), positions,
                                         decode))
        if self.index < cfg.n_dense_layers:
            ffn = DenseFFN(cfg, name='mlp')
        else:
            ffn = moe_lib.DroplessMoE(
                dim=cfg.dim, ffn_dim=cfg.expert_dim,
                n_experts=cfg.n_experts, held=cfg.held_experts,
                router=moe_lib.LinearRouter(top_k=cfg.experts_per_token,
                                            scaling=cfg.routed_scaling),
                n_shared=cfg.n_shared_experts, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, mesh=self.mesh, name='moe')
        return x + norm('ffn_post_norm', ffn(norm('ffn_norm', x)))


class OpenPanguMoE(nn.Module):
    """tokens [B, S] -> logits [B, S, vocab] float32.  `lengths` [B]: the
    valid positions of each row of this call (None: all S); with it and
    S > 1 the logits are those of each row's last valid position alone,
    [B, 1, vocab]."""
    cfg: OpenPanguMoEConfig
    # The mesh the program is partitioned over, if any: the Pallas kernels
    # are for one device (ops/attention.py, models/moe.py `expert_tile`).
    mesh: Optional[Mesh] = None

    def served(self) -> Served:
        cfg = self.cfg
        return Served(
            # The cache is a latent a position ([slots, positions, width]
            # leaves), not keys and values a head.
            unpaged_cache=('caches a latent a position in place of keys and '
                           'values a head'),
            latent_leaves=('c_kv', 'k_pe'),
            # One row at a time through the whole stack: what a row of
            # 4,096 positions builds per layer, 128 heads' queries, keys
            # and values among it, is 1.7 GB (a v5e compile at the
            # published widths; two rows at a time leave 0.3 GB of the chip
            # beside 9.8 GB of weights and 32 slots of cache), and the
            # expert layer over every row's tokens at once does not fit
            # either, so bounding a sublayer's rows inside the model
            # (models/solar_open2.py `_by_rows`) would not do.
            prefill_rows=1,
            decode_kv_block=attn_lib.latent_kv_block(
                cfg.kv_rank, cfg.max_seq_len, self.mesh),
            publish_stats=functools.partial(moe_lib.publish_stats,
                                            cfg.held_experts))

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
                x, positions, decode)
        if lengths is not None and x.shape[1] > 1:
            # A prefill reads one position's logits a row, the last valid
            # one: the head runs on that position alone ([B, 1, vocab]).
            x = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)
        x = RMSNorm(cfg.norm_eps, cfg.dtype, cfg.param_dtype,
                    name='final_norm')(x)
        logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                          param_dtype=cfg.param_dtype, name='lm_head')(x)
        return logits.astype(jnp.float32)
