"""SDAR-MoE decoder: a GQA block with per-head q/k norms and a dropless
expert layer, generating by diffusion over blocks of positions.

The published architecture (`model_type` `sdar_moe`): with N an RMSNorm,

    h = x + Attn(N(x))        y = h + MoE(N(h))

every layer the same, a final norm and an untied head.  Attn is grouped-
query attention (`n_heads` query heads of `head_dim` over `n_kv_heads`),
an RMSNorm over the `head_dim` of every query and key head before RoPE
(the Qwen3 family's), RoPE over the whole head.  MoE is `models/moe.py`
`DroplessMoE` with a softmax over all the experts, the k largest
normalised to sum 1, and no shared expert.

**What is different is the mask and what a step is.**  With block length
B, position i sees position j iff `j // B <= i // B`: both ways inside a
block, causal from block to block.  A sequence is generated a block at a
time: the block's open positions start as the mask token, a *denoising
pass* runs the block's B rows against the cache of the blocks before it
and the block itself, logits are read AT each masked position (no shift)
and some positions take their token (`BlockSchedule`); when none is masked
a *commit pass* runs the B clean tokens, which leaves their K and V in the
cache, and the block is the output.  So the serving step is a pass over a
block a slot, not one token a slot: the model declares `block_length`
and `block_schedule` (`served()`) and `DecodeEngine` runs such passes
(inference/engine.py, "Generation by blocks"); a model without them is
served a token a step as before.

Three paths from the one set of weights:

- a prompt through the engine (no cache yet): its K and V land at
  cache[:S] and it attends under the mask by blocks (`ops/attention.py
  flash_attention_on_mesh(mask_block=B)`: the Pallas flash kernel on the
  TPU, the XLA reference elsewhere).  Rows past a prompt's whole blocks
  write K and V that nothing reads before the block's passes overwrite
  them: a position of a whole block sees only whole blocks;
- a pass over a block (S == B against the cache): the B rows' K and V are
  written at `positions` (the block's own, `start .. start + B`), and all
  B rows attend the positions `< start + B`: inside a block nothing is
  masked, so it is the decode kernel's one length bound with B x group
  query rows a KV head (`ops/attention.py decode_attention`);
- no cache (`decode=False`): the whole sequence under the mask by blocks.

The rows of a pass are scattered over (slot x head, position) as rows of
`head_dim`, which leaves the cache row-major as the kernel reads it
(`models/llama.py` `_decode_attend` says why).
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

REMASKINGS = ('low_confidence_static', 'low_confidence_dynamic',
              'sequential')


@dataclasses.dataclass(frozen=True)
class BlockSchedule:
    """How a block's masked positions take their tokens, a denoising pass
    (what the model declares as `Served.block_schedule`):

    - `low_confidence_static`: the k masked positions whose chosen token
      has the highest probability, k = block_length / `steps`;
    - `low_confidence_dynamic`: every masked position whose chosen token
      has a probability above `threshold`, and at least the most
      confident one;
    - `sequential`: the first k masked positions, k as above.
    """
    mask_id: int
    remasking: str = 'low_confidence_static'
    steps: int = 4
    threshold: float = 0.9

    def __post_init__(self):
        if self.remasking not in REMASKINGS:
            raise ValueError(f'remasking {self.remasking!r} is not one of '
                             f'{REMASKINGS}')
        if self.steps < 1:
            raise ValueError(f'denoising steps must be positive, got '
                             f'{self.steps}')

    def least_per_pass(self, block: int) -> int:
        """Positions of a block of `block` that a denoising pass unmasks
        at the least (the engine bounds a call's tokens by it)."""
        if self.remasking == 'low_confidence_dynamic':
            return 1
        return -(-block // self.steps)

    def choose(self, conf, masked):
        """Which masked positions of each slot's block take their token
        in this denoising pass: `conf` [n, block] float32 (the probability
        of each row's chosen token), `masked` [n, block] bool -> [n, block]
        bool.  The three schedules differ in this one choice; ties go to
        the lower position."""
        block = masked.shape[1]
        pos = jnp.arange(block)
        if self.remasking == 'low_confidence_dynamic':
            most = jnp.argmax(jnp.where(masked, conf, -1.0), axis=1)
            return masked & ((conf > self.threshold) |
                             (pos[None, :] == most[:, None]))
        if self.remasking == 'sequential':
            rank = jnp.cumsum(masked, axis=1) - 1
        else:   # low_confidence_static: rank among the masked by confidence
            c = jnp.where(masked, conf, -1.0)
            ahead = (c[:, None, :] > c[:, :, None]) | (
                (c[:, None, :] == c[:, :, None]) &
                (pos[None, None, :] < pos[None, :, None]))
            rank = jnp.sum(ahead & masked[:, None, :], axis=2)
        return masked & (rank < self.least_per_pass(block))


@dataclasses.dataclass(frozen=True)
class SDARMoEConfig:
    vocab_size: int = 151936
    dim: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    n_experts: int = 128
    held_experts: Tuple[int, ...] = tuple(range(128))
    experts_per_token: int = 8
    expert_dim: int = 768
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-6
    max_seq_len: int = 32768
    # Generation by blocks: the mask's block, and how a block is denoised.
    block_length: int = 4
    mask_id: int = 151669
    remasking: str = 'low_confidence_static'
    denoising_steps: int = 4
    confidence_threshold: float = 0.9
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def layer_params(self) -> int:
        d, hd = self.dim, self.head_dim
        return (2 * d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd +
                2 * hd + 2 * d + d * self.n_experts +
                len(self.held_experts) * 3 * d * self.expert_dim)

    def num_params(self) -> int:
        """Parameters held here (the held experts)."""
        return (self.n_layers * self.layer_params() +
                2 * self.vocab_size * self.dim + self.dim)


class BlockAttention(nn.Module):
    cfg: SDARMoEConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array, decode: bool,
                 live: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.cfg

        def heads(name, n):
            y = nn.DenseGeneral(
                features=(n, cfg.head_dim), axis=-1, use_bias=False,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype, name=name)(x)
            return y                                        # [B, S, n, D]

        def head_norm(name, y):
            return RMSNorm(cfg.norm_eps, cfg.dtype, cfg.param_dtype,
                           name=name)(y)

        q = head_norm('q_norm', heads('q_proj', cfg.n_heads))
        k = head_norm('k_norm', heads('k_proj', cfg.n_kv_heads))
        v = heads('v_proj', cfg.n_kv_heads)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
        if decode:
            out = self._cached(q, k, v, positions, live)
        else:
            out = self._by_blocks(q, k, v)
        return nn.DenseGeneral(
            features=cfg.dim, axis=(-2, -1), use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name='o_proj')(
                out.transpose(0, 2, 1, 3))

    def _by_blocks(self, q, k, v):
        """A left-aligned sequence under the mask by blocks."""
        return attn_lib.flash_attention_on_mesh(
            q, k, v, self.mesh, causal=True,
            mask_block=self.cfg.block_length)

    def _cached(self, q, k, v, positions, live):
        cfg = self.cfg
        b, h_kv, s, d = k.shape
        max_len = cfg.max_seq_len
        fresh = not self.has_variable('cache', 'k')
        ck = self.variable('cache', 'k', jnp.zeros, (b, h_kv, max_len, d),
                           cfg.dtype)
        cv = self.variable('cache', 'v', jnp.zeros, (b, h_kv, max_len, d),
                           cfg.dtype)
        if fresh:
            # A prompt: cache[:S] and attention over the prompt itself.
            ck.value = jax.lax.dynamic_update_slice(ck.value, k, (0, 0, 0, 0))
            cv.value = jax.lax.dynamic_update_slice(cv.value, v, (0, 0, 0, 0))
            return self._by_blocks(q, k, v)
        if s != cfg.block_length:
            raise ValueError(
                f'against its cache this model runs passes over a block '
                f'of {cfg.block_length} positions, not {s} (no chunked '
                f'prefill for blocks)')
        # A pass over a block: `positions` [B, S] are the block's own.
        bh_idx = jnp.arange(b * h_kv)[:, None]
        bh_pos = jnp.repeat(positions, h_kv, axis=0)        # [B * Hkv, S]

        def write(cache, rows):
            flat = cache.reshape(b * h_kv, max_len, d)
            flat = flat.at[bh_idx, bh_pos, :].set(
                rows.reshape(b * h_kv, s, d))
            return flat.reshape(cache.shape)

        ck.value = write(ck.value, k)
        cv.value = write(cv.value, v)
        lens = positions[:, 0] + s
        if live is not None:
            lens = jnp.where(live, lens, 0)
        return attn_lib.decode_attention(q, ck.value, cv.value, lens,
                                         self.mesh)


class Block(nn.Module):
    cfg: SDARMoEConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x, positions, decode, live=None):
        cfg = self.cfg

        def norm(name, inp):
            return RMSNorm(cfg.norm_eps, cfg.dtype, cfg.param_dtype,
                           name=name)(inp)

        x = x + BlockAttention(cfg, self.mesh, name='attn')(
            norm('attn_norm', x), positions, decode, live)
        return x + moe_lib.DroplessMoE(
            dim=cfg.dim, ffn_dim=cfg.expert_dim, n_experts=cfg.n_experts,
            held=cfg.held_experts, router=moe_lib.LinearRouter(
                top_k=cfg.experts_per_token, scoring='softmax'),
            n_shared=0, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            mesh=self.mesh, name='moe')(norm('moe_norm', x))


class SDARMoE(nn.Module):
    """tokens [B, S] -> logits [B, S, vocab] float32, position t's logits
    being those of position t's own token (no shift).  `masked` [B, S]
    bool: the positions that hold the mask token whatever `tokens` has
    there.  `lengths` [B]: the valid positions of each row of a prompt;
    with it and S > 1 the logits are those of each row's last valid
    position alone, [B, 1, vocab] (a prompt's logits are not read)."""
    cfg: SDARMoEConfig
    mesh: Optional[Mesh] = None

    def served(self) -> Served:
        cfg = self.cfg
        return Served(
            # A step is a pass over a block, so one token a sequence and
            # step, which the page manager, speculation and KV transfer
            # count by, does not hold.
            unpaged_cache='generates by passes over blocks of positions',
            block_length=cfg.block_length,
            block_schedule=BlockSchedule(
                cfg.mask_id, cfg.remasking, cfg.denoising_steps,
                cfg.confidence_threshold),
            # A pass reads nothing of a slot that `live` says holds no
            # request.
            decode_takes_live=True,
            decode_kv_block=attn_lib.decode_kv_block(
                cfg.n_kv_heads, cfg.head_dim, cfg.max_seq_len, cfg.dtype,
                self.mesh),
            publish_stats=functools.partial(moe_lib.publish_stats,
                                            cfg.held_experts))

    @nn.compact
    def __call__(self, tokens: jax.Array,
                 positions: Optional[jax.Array] = None,
                 decode: bool = False,
                 lengths: Optional[jax.Array] = None,
                 live: Optional[jax.Array] = None,
                 masked: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.cfg
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1])[None, :], tokens.shape)
        if masked is not None:
            tokens = jnp.where(masked, cfg.mask_id, tokens)
        x = nn.Embed(cfg.vocab_size, cfg.dim, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype,
                     embedding_init=nn.initializers.normal(stddev=1.0),
                     name='embed')(tokens)
        for i in range(cfg.n_layers):
            x = Block(cfg, self.mesh, name=f'layer_{i}')(
                x, positions, decode, live)
        if lengths is not None and x.shape[1] > 1:
            x = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)
        x = RMSNorm(cfg.norm_eps, cfg.dtype, cfg.param_dtype,
                    name='final_norm')(x)
        logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                          param_dtype=cfg.param_dtype, name='lm_head')(x)
        return logits.astype(jnp.float32)
