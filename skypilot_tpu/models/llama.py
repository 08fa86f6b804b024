"""Llama-family decoder — the flagship model.

JAX/Flax twin of the torch models the reference fine-tunes/serves through
recipe YAMLs (llm/llama-3_1-finetuning, examples/tpu/v6e/train-llama3-8b —
reference drives them via env plumbing; here the model is first-party).

TPU-first design:
- bf16 compute / f32 params & accumulators (MXU-native);
- every matmul annotated with *logical* axes (`parallel/sharding.py` maps
  them to mesh axes; fsdp/tp/sp are rule changes, not model changes);
- attention dispatches to the Pallas flash kernel on TPU, ring attention
  when the sequence is context-parallel sharded;
- rotary embeddings precomputed once, `lax.scan`-friendly static shapes;
- optional per-block remat (`jax.checkpoint`) to trade FLOPs for HBM.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh

from skypilot_tpu.inference import kv_quant
from skypilot_tpu.models.served import Served
from skypilot_tpu.ops import attention as attn_lib


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14336
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 8192
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16          # compute dtype
    param_dtype: Any = jnp.float32
    remat: bool = True                 # checkpoint each block
    # What the per-block checkpoint keeps besides the block's input.
    # 'fit', the default: what fits in `remat_keep_bytes`, dearest first
    # (`keep_plan`): the flash kernel's output and logsumexp in every
    # layer, then q/k/v, gate/up and the post-attention stream a layer at
    # a time.  'none' keeps nothing and the backward pass runs the whole
    # forward again (min HBM; also what 'fit' is with no bytes).
    remat_policy: str = 'fit'          # 'fit' | 'none'
    # Bytes of named activations a device may keep under 'fit'.  None is
    # "whoever runs the step says": `Trainer` counts what the device has
    # left beside the state, the gradients and the loss's temporaries
    # (train/trainer.py activation_budget) and sets it; a model run
    # without one keeps nothing.
    remat_keep_bytes: Optional[int] = None
    attention_impl: str = 'flash'      # 'flash' | 'xla' | 'ring'
    # MoE: n_experts > 0 swaps every block's MLP for a top-k
    # mixture-of-experts (models/moe.py); experts shard over the mesh's
    # 'expert' axis (Mixtral-family shape).
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def num_params(self) -> int:
        d, f = self.dim, self.ffn_dim
        if self.n_experts > 0:
            ffn = self.n_experts * 3 * d * f + d * self.n_experts  # +router
        else:
            ffn = 3 * d * f                          # gate, up, down
        per_layer = (d * d * 2                       # q, o proj
                     + 2 * d * (self.n_kv_heads * self.head_dim)  # k, v
                     + ffn
                     + 2 * d)                        # norms
        embed = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return self.n_layers * per_layer + embed + d


LLAMA_CONFIGS: Dict[str, LlamaConfig] = {
    # test-size model: exercises GQA (4 q heads over 2 kv heads)
    'tiny': LlamaConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                        n_kv_heads=2, ffn_dim=128, max_seq_len=128,
                        remat=False, rope_theta=10000.0),
    'llama3-1b': LlamaConfig(vocab_size=128256, dim=2048, n_layers=16,
                             n_heads=32, n_kv_heads=8, ffn_dim=8192,
                             tie_embeddings=True),
    # single-chip bench model: fits one v5e (16 GB HBM) with Adam in f32
    'bench-600m': LlamaConfig(vocab_size=32768, dim=1536, n_layers=16,
                              n_heads=12, n_kv_heads=4, ffn_dim=6144,
                              max_seq_len=2048),
    # HBM-sized single-chip bench model: ~948M params, 11.4 GB optimizer
    # state in f32 Adam; head_dim 128 keeps the flash kernel lane-aligned
    'bench-1b': LlamaConfig(vocab_size=32768, dim=2048, n_layers=14,
                            n_heads=16, n_kv_heads=8, ffn_dim=8192,
                            max_seq_len=4096, tie_embeddings=True),
    # graft-entry model: modest size so single-chip compile checks are fast
    'llama-250m': LlamaConfig(vocab_size=32000, dim=1024, n_layers=16,
                              n_heads=16, n_kv_heads=8, ffn_dim=4096,
                              max_seq_len=2048, remat=False),
    'llama3-8b': LlamaConfig(),
    'llama3-70b': LlamaConfig(dim=8192, n_layers=80, n_heads=64,
                              n_kv_heads=8, ffn_dim=28672),
    'llama2-7b': LlamaConfig(vocab_size=32000, dim=4096, n_layers=32,
                             n_heads=32, n_kv_heads=32, ffn_dim=11008,
                             rope_theta=10000.0, max_seq_len=4096),
}


# What a block's checkpoint can keep, by group, dearest first: the device
# time the backward pass spent making a GB of each again on one v5e at
# 4 x 4,096 tokens (PERF.md section 6, PR 40: the flash kernel's output
# 82 ms a GB, q/k/v 21, the others by count a little under that).  A
# group is the names that spare one piece of recompute only together
# (`ops/attention.py _flash_fwd` names the first).
KEEP_GROUPS: Dict[str, Tuple[str, ...]] = {
    'attn_out': ('attn_out', 'attn_lse'),
    'qkv': ('attn_q', 'attn_k', 'attn_v'),
    'gate_up': ('mlp_gate', 'mlp_up'),
    'stream': ('attn_stream',),
}


@dataclasses.dataclass(frozen=True)
class KeepPlan:
    """What the blocks keep for the backward pass of one step."""
    layers: Tuple[Tuple[str, ...], ...]  # groups kept, layer by layer
    kept_bytes: Dict[str, int]           # on one device, by group
    layer_bytes: Dict[str, int]          # of it one layer's, if it can keep
    forward_flops: float                 # the blocks' forward, whole batch
    recomputed_flops: float              # of it, run again by the backward

    def policy(self, layer: int):
        names = [n for g in self.layers[layer] for n in KEEP_GROUPS[g]]
        if not names:
            return jax.checkpoint_policies.nothing_saveable
        return jax.checkpoint_policies.save_only_these_names(*names)


def device_share(cfg: LlamaConfig, mesh, batch: int,
                 seq: int) -> Tuple[int, int]:
    """(tokens of a [batch, seq] step one device holds, the devices that
    share its heads, FFN columns and vocabulary): tokens divide over the
    mesh's batch axes and the rest over 'tensor', where they divide at
    all (as `_constrain_activations` and `flash_attention_on_mesh` shard
    them)."""
    mesh_shape = dict(mesh.shape) if mesh is not None else {}
    shards = math.prod(mesh_shape.get(a, 1)
                       for a in ('dcn', 'data', 'fsdp', 'expert'))
    if cfg.attention_impl != 'ring' and batch % shards:
        shards = 1
    tp = mesh_shape.get('tensor', 1)
    if cfg.n_heads % tp or cfg.n_kv_heads % tp:
        tp = 1
    return batch * seq // shards, tp


def keep_plan(cfg: LlamaConfig, mesh, batch: int, seq: int) -> KeepPlan:
    """Turn `cfg.remat_keep_bytes` into the groups each block keeps of a
    [batch, seq] step, and count what the choice costs.

    Bytes are one device's (`device_share`).  Every kept byte is alive
    at the loss, so which layers keep a group is free: the first ones
    do.  A group that does not fit is passed over for a smaller one
    further down.  FLOPs are the whole batch's, two a multiply-add,
    causal attention at half its square; the block's last matmul
    (down_proj) is dead in the recompute and never counted in it.
    """
    if cfg.remat_policy not in ('fit', 'none'):
        raise ValueError(f"remat_policy must be 'fit' or 'none', got "
                         f'{cfg.remat_policy!r}')
    local, tp = device_share(cfg, mesh, batch, seq)
    tokens = batch * seq
    act = jnp.dtype(cfg.dtype).itemsize
    d, f, hd = cfg.dim, cfg.ffn_dim, cfg.head_dim
    qkv_heads = cfg.n_heads + 2 * cfg.n_kv_heads
    moe = cfg.n_experts > 0
    ffn_flops = 2.0 * tokens * d * f * (cfg.moe_top_k if moe else 1)
    # group -> (bytes a device keeps, FLOPs of the recompute it spares)
    groups = {
        'attn_out': (local * cfg.n_heads // tp * (hd * act + 4),
                     2.0 * batch * cfg.n_heads * seq * seq * hd),
        'qkv': (local * qkv_heads // tp * hd * act,
                2.0 * tokens * d * qkv_heads * hd),
        'gate_up': (local * 2 * f // tp * act, 2 * ffn_flops),
        'stream': (local * d * act, 2.0 * tokens * cfg.n_heads * hd * d),
    }
    forward = sum(flops for _, flops in groups.values()) + ffn_flops
    can_keep = [g for g in KEEP_GROUPS
                if not (g == 'attn_out' and cfg.attention_impl != 'flash')
                and not (g == 'gate_up' and moe)]
    layers = [[] for _ in range(cfg.n_layers)]
    if not cfg.remat:
        # No checkpoint: autodiff keeps these and more, nothing runs twice.
        layers = [list(groups)] * cfg.n_layers
    elif cfg.remat_policy == 'fit':
        left = cfg.remat_keep_bytes or 0
        for g in can_keep:
            for kept in layers:
                if groups[g][0] <= left:
                    kept.append(g)
                    left -= groups[g][0]
    kept_bytes = {g: groups[g][0] * sum(g in kept for kept in layers)
                  for g in groups}
    recomputed = sum(flops for kept in layers for g, (_, flops)
                     in groups.items() if g not in kept)
    return KeepPlan(tuple(map(tuple, layers)), kept_bytes,
                    {g: groups[g][0] for g in can_keep},
                    cfg.n_layers * forward, recomputed)


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary position embedding. x: [B, H, S, D], positions: [B, S]."""
    d = x.shape[-1]
    freqs = 1.0 / (theta**(jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[:, None, :, None].astype(jnp.float32) * freqs  # B1SF
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def _constrain_activations(x: jax.Array, mesh: Optional[Mesh],
                           context_parallel: bool = False) -> jax.Array:
    """Pin activation shardings.  Without this XLA propagates *param*
    shardings (embed→fsdp) into activations and emits involuntary-
    rematerialization repartitions.

    Default: batch over (data, fsdp).  Context-parallel (ring attention):
    batch over data only, *sequence* over fsdp — the ring rotates K/V shards
    along that axis.  Constraints are skipped when the dim is not divisible
    (e.g. tiny eval batches).
    """
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    d_dcn = mesh.shape.get('dcn', 1)
    d_data = mesh.shape.get('data', 1)
    d_fsdp = mesh.shape.get('fsdp', 1)
    if context_parallel:
        d_batch = d_dcn * d_data
        batch_axes = (('dcn', 'data')
                      if x.shape[0] % max(d_batch, 1) == 0 else None)
        seq_axis = 'fsdp' if x.shape[1] % max(d_fsdp, 1) == 0 else None
        spec = P(batch_axes, seq_axis, *([None] * (x.ndim - 2)))
    else:
        d_expert = mesh.shape.get('expert', 1)
        divisor = max(d_dcn * d_data * d_fsdp * d_expert, 1)
        if x.shape[0] % divisor != 0:
            return x
        spec = P(('dcn', 'data', 'fsdp', 'expert'),
                 *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _decode_kv_block(cfg: LlamaConfig, mesh: Optional[Mesh]) -> Optional[int]:
    """`attn_lib.decode_kv_block` for this model's cache leaves."""
    return attn_lib.decode_kv_block(cfg.n_kv_heads, cfg.head_dim,
                                    cfg.max_seq_len, cfg.dtype, mesh)


class OneHotEmbed(nn.Embed):
    """Embedding lookup as a one-hot matmul.

    A gather from a vocab-sharded table ('vocab' -> tensor axis) forces XLA
    to replicate-then-repartition the table ("involuntary full
    rematerialization").  A one-hot matmul instead contracts over the
    sharded vocab axis on the MXU and lowers to a clean psum.  Used when a
    mesh with tensor parallelism is present; plain gather otherwise (the
    matmul costs B*S*V*D FLOPs, wasteful single-chip).
    """

    def __call__(self, inputs: jax.Array) -> jax.Array:
        onehot = jax.nn.one_hot(inputs, self.num_embeddings,
                                dtype=self.dtype)
        return jnp.dot(onehot, self.embedding.astype(self.dtype))


class RMSNorm(nn.Module):
    eps: float
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param(
            'scale', nn.with_logical_partitioning(nn.initializers.ones,
                                                  ('embed',)),
            (x.shape[-1],), self.param_dtype)
        x32 = x.astype(jnp.float32)
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        out = x32 * jax.lax.rsqrt(var + self.eps)
        return (out * scale.astype(jnp.float32)).astype(self.dtype)


class Attention(nn.Module):
    cfg: LlamaConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 decode: bool = False,
                 page_table: Optional[jax.Array] = None,
                 live: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.cfg
        dense = lambda name, heads, logical: nn.DenseGeneral(  # noqa: E731
            features=(heads, cfg.head_dim), axis=-1, use_bias=False,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), logical),
            name=name)
        q = dense('q_proj', cfg.n_heads, ('embed', 'heads', 'kv'))(x)
        k = dense('k_proj', cfg.n_kv_heads, ('embed', 'heads', 'kv'))(x)
        v = dense('v_proj', cfg.n_kv_heads, ('embed', 'heads', 'kv'))(x)
        # [B, S, H, D] -> [B, H, S, D]
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
        # As the kernel and its backward pass read them (KEEP_GROUPS).
        q, k, v = (checkpoint_name(t, name) for t, name in
                   zip((q, k, v), KEEP_GROUPS['qkv']))

        if decode and page_table is not None:
            k, v, attn_out = self._paged_attend(q, k, v, positions,
                                                page_table)
        elif decode:
            k, v, attn_out = self._decode_attend(q, k, v, positions, live)
        else:
            attn_out = self._attend(q, k, v)
        out = attn_out.transpose(0, 2, 1, 3)  # [B, S, H, D]
        return nn.DenseGeneral(
            features=cfg.dim, axis=(-2, -1), use_bias=False,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ('heads', 'kv', 'embed')),
            name='o_proj')(out)

    def _attend(self, q, k, v):
        cfg = self.cfg
        if cfg.attention_impl == 'ring':
            from skypilot_tpu.parallel import ring_attention as ring
            assert self.mesh is not None, 'ring attention needs a mesh'
            return ring.ring_attention(q, k, v, mesh=self.mesh, causal=True)
        if cfg.attention_impl == 'flash':
            return attn_lib.flash_attention_on_mesh(q, k, v, self.mesh)
        return attn_lib.mha_reference(q, k, v, causal=True)

    def _decode_attend(self, q, k, v, positions, live=None):
        """Decode with a KV cache (serving path), driven entirely by the
        caller-supplied per-slot `positions` [B, S] — there is no shared
        index, so a continuous-batching engine can run heterogeneous slot
        lengths in one batch (each slot writes at its own position).
        Against an existing cache, S == 1 is the decode step and S > 1
        is a CHUNK of a long prompt's prefill: the chunk's K/V land at
        their absolute positions and q attends over the full cache
        (earlier chunks + itself), so prompts longer than any single
        dispatch accumulate chunk by chunk.  `live` [B] bool, where the
        engine gives it, says which rows of the decode step hold a
        request: the others read nothing and get zeros (their row is
        still written, at a position nothing reads).

        Invariant that makes bucket-padded prefill safe: every step
        attends only k_pos <= q_pos, writes at q_pos, and inserts
        overwrite a slot's whole cache — so padding garbage always lives
        at k_pos > q_pos and is masked until overwritten.
        """
        cfg = self.cfg
        is_init = not self.has_variable('cache', 'k')
        max_len = cfg.max_seq_len
        b = q.shape[0]
        ck = self.variable('cache', 'k', jnp.zeros,
                           (b, cfg.n_kv_heads, max_len, cfg.head_dim),
                           cfg.dtype)
        cv = self.variable('cache', 'v', jnp.zeros,
                           (b, cfg.n_kv_heads, max_len, cfg.head_dim),
                           cfg.dtype)
        # Write incoming k/v on BOTH the init and steady-state paths: the
        # standard prefill pattern is a first apply(decode=True) over the
        # full prompt, which must land the prompt's K/V in the cache (a
        # silently-empty cache would make later decode steps attend to
        # zeros).
        if is_init:
            # Prefill fast path: the cache was just created, prompts are
            # left-aligned so the prompt occupies cache[:S].  Attend
            # causal over the prompt itself — O(S^2), not O(S * max_len).
            ck.value = jax.lax.dynamic_update_slice(
                ck.value, k, (0, 0, 0, 0))
            cv.value = jax.lax.dynamic_update_slice(
                cv.value, v, (0, 0, 0, 0))
            return k, v, attn_lib.mha_reference(q, k, v, causal=True)
        if q.shape[2] > 1:
            # Chunked prefill (S > 1 against an existing cache): one
            # fixed-size chunk of a long prompt lands at its absolute
            # positions, then attends over the whole cache — earlier
            # chunks' K/V plus itself, causally.  Position-scatter (not
            # dynamic_update_slice, which CLAMPS the start index and
            # would silently overwrite earlier rows if a padded chunk
            # ran past max_len; out-of-range scatter updates drop).
            b_col = jnp.arange(b)[:, None]                     # [B, 1]
            ck.value = ck.value.at[b_col, :, positions, :].set(
                k.transpose(0, 2, 1, 3))
            cv.value = cv.value.at[b_col, :, positions, :].set(
                v.transpose(0, 2, 1, 3))
        else:
            # Steady state (S == 1 per slot): scatter-write each slot's
            # k/v at its own position.  A true scatter (not a one-hot
            # blend — that reads+writes the whole cache and
            # double-buffers it as an HLO temp inside the decode scan,
            # ~2x cache HBM; scatter updates one row in place under
            # donation).
            pos = positions[:, 0]                               # [B]
            if _decode_kv_block(cfg, self.mesh) is None:
                b_idx = jnp.arange(b)
                ck.value = ck.value.at[b_idx, :, pos, :].set(k[:, :, 0, :])
                cv.value = cv.value.at[b_idx, :, pos, :].set(v[:, :, 0, :])
            else:
                # The kernel's operands are row-major [B, Hkv, S, D].
                # The scatter above, whose window is a position's heads,
                # makes the TPU compiler lay the cache out position-
                # major and copy each leaf whole in front of every
                # kernel call (a scratch compile shows it, whatever
                # layout the program's arguments are given); rows of D
                # scattered over (slot x head, position) leave the
                # cache as the kernel reads it.
                h_kv = cfg.n_kv_heads
                bh_idx = jnp.arange(b * h_kv)
                bh_pos = jnp.repeat(pos, h_kv)

                def write(cache, row):
                    flat = cache.reshape(b * h_kv, max_len, cfg.head_dim)
                    flat = flat.at[bh_idx, bh_pos, :].set(
                        row.reshape(b * h_kv, cfg.head_dim))
                    return flat.reshape(cache.shape)

                ck.value = write(ck.value, k)
                cv.value = write(cv.value, v)
            # The one-row step reads a slot up to the row just written
            # (a Pallas kernel bounded by the lengths on one TPU device,
            # this same mask through XLA elsewhere), and nothing of a
            # row that holds no request: a length of zero.
            lens = pos + 1 if live is None else jnp.where(live, pos + 1, 0)
            return ck.value, cv.value, attn_lib.decode_attention(
                q, ck.value, cv.value, lens, self.mesh)
        k_all, v_all = ck.value, cv.value
        k_pos = jnp.arange(max_len)[None, :]
        out = attn_lib.mha_reference(
            q, k_all, v_all, causal=True,
            segment_positions=positions,
            kv_positions=jnp.broadcast_to(k_pos, (b, max_len)))
        return k_all, v_all, out

    def _paged_attend(self, q, k, v, positions, page_table):
        """Decode against a PAGED cache: the cache variables hold the
        whole engine's page pool [n_pages, n_kv_heads, page_size, D]
        and ``page_table`` [B, pages_per_slot] maps each slot's logical
        page index -> physical page, so a slot's sequence lives in
        whatever pages the host allocator handed it — shared prefix
        pages included.  Each step scatter-writes S rows into the
        slot's OWN pages (always slot-owned: shared pages end at the
        match boundary and writes only happen past it), then gathers
        the slot's pages back into position order and attends exactly
        like the dense path — same shapes, same masks, so greedy
        outputs are token-identical to the unpaged engine.

        S == 1 is the steady-state decode step; S > 1 is speculative
        VERIFY: k drafted tokens plus the committed last token score in
        one dispatch, each row position-scattered into its page exactly
        like the chunked-prefill path, attending causally over the
        gathered pages (earlier draft rows included — all writes land
        before the gather).  Rejected draft rows leave K/V garbage at
        positions past the accepted length; the causal mask keeps it
        unread until the accepted stream overwrites it, the same
        invariant that makes bucket-padded prefill safe.

        When the pool is int8 (``kv_quant.QuantPages``), rows are
        quantized at scatter time (one absmax scale per position) and
        dequantized inside the gather — the attention matmul itself is
        unchanged.  The pool shards over its kv-heads dim under tensor
        parallelism; page ids index the unsharded dim 0, so gathers and
        scatters stay local to each chip's head shard.
        """
        cfg = self.cfg
        if not self.has_variable('cache', 'k'):
            raise ValueError(
                'paged attention is the steady-state decode path: the '
                'engine supplies the page pool as the cache')
        ck = self.variable('cache', 'k', jnp.zeros, (), cfg.dtype)
        cv = self.variable('cache', 'v', jnp.zeros, (), cfg.dtype)
        quant = isinstance(ck.value, kv_quant.QuantPages)
        kd = ck.value.data if quant else ck.value
        ps = kd.shape[2]
        b = q.shape[0]
        n_logical = page_table.shape[1] * ps
        page_ids = jnp.take_along_axis(page_table, positions // ps,
                                       axis=1)                # [B, S]
        off = positions % ps                                  # [B, S]

        # Write this step's K/V rows at (page, in-page offset).
        # Distinct live slots never share their write pages (allocator
        # invariant); inactive slots all point at the trash page —
        # duplicate-index garbage the masks below keep unread.
        def _scatter(pool, rows):
            rows = rows.transpose(0, 2, 1, 3)     # [B, S, H, D]
            if quant:
                qd, s = kv_quant.quantize_kv(rows)
                return kv_quant.QuantPages(
                    pool.data.at[page_ids, :, off, :].set(qd),
                    pool.scale.at[page_ids, :, off].set(s))
            return pool.at[page_ids, :, off, :].set(rows)

        ck.value = _scatter(ck.value, k)
        cv.value = _scatter(cv.value, v)

        def _gather(pool):
            if quant:
                g = kv_quant.dequantize_kv(
                    pool.data[page_table], pool.scale[page_table],
                    cfg.dtype)                   # [B, P, H, ps, D]
            else:
                g = pool[page_table]             # [B, P, H, ps, D]
            g = g.transpose(0, 2, 1, 3, 4)       # [B, H, P, ps, D]
            return g.reshape(b, g.shape[1], n_logical, g.shape[4])

        k_all, v_all = _gather(ck.value), _gather(cv.value)
        k_pos = jnp.arange(n_logical)[None, :]
        out = attn_lib.mha_reference(
            q, k_all, v_all, causal=True,
            segment_positions=positions,
            kv_positions=jnp.broadcast_to(k_pos, (b, n_logical)))
        return k_all, v_all, out


class MLP(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        dense = lambda name, feat, logical: nn.Dense(  # noqa: E731
            feat, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), logical), name=name)
        gate = dense('gate_proj', cfg.ffn_dim, ('embed', 'mlp'))(x)
        up = dense('up_proj', cfg.ffn_dim, ('embed', 'mlp'))(x)
        gate, up = (checkpoint_name(t, name) for t, name in
                    zip((gate, up), KEEP_GROUPS['gate_up']))
        return dense('down_proj', cfg.dim, ('mlp', 'embed'))(
            nn.silu(gate) * up)


class Block(nn.Module):
    cfg: LlamaConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x: jax.Array, positions: jax.Array,
                 decode: bool = False,
                 page_table: Optional[jax.Array] = None,
                 live: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.cfg
        cp = cfg.attention_impl == 'ring'
        x = _constrain_activations(x, self.mesh, cp)
        x = x + Attention(cfg, self.mesh, name='attn')(
            RMSNorm(cfg.norm_eps, cfg.dtype, cfg.param_dtype,
                    name='attn_norm')(x), positions, decode, page_table,
            live)
        x = checkpoint_name(x, KEEP_GROUPS['stream'][0])
        if cfg.n_experts > 0:
            from skypilot_tpu.models.moe import MoEMLP
            mlp = MoEMLP(dim=cfg.dim, ffn_dim=cfg.ffn_dim,
                         n_experts=cfg.n_experts, top_k=cfg.moe_top_k,
                         capacity_factor=cfg.moe_capacity_factor,
                         dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         mesh=self.mesh, name='moe_mlp')
        else:
            mlp = MLP(cfg, name='mlp')
        x = x + mlp(
            RMSNorm(cfg.norm_eps, cfg.dtype, cfg.param_dtype,
                    name='mlp_norm')(x))
        return _constrain_activations(x, self.mesh, cp)


class Llama(nn.Module):
    cfg: LlamaConfig
    mesh: Optional[Mesh] = None

    def served(self) -> Served:
        # The one-row step's attention reads nothing of a row that `live`
        # says holds no request (ops/attention.py decode_attention: a
        # length of zero).
        return Served(decode_takes_live=True,
                      decode_kv_block=_decode_kv_block(self.cfg, self.mesh))

    @nn.compact
    def __call__(self, tokens: jax.Array,
                 positions: Optional[jax.Array] = None,
                 decode: bool = False,
                 page_table: Optional[jax.Array] = None,
                 lengths: Optional[jax.Array] = None,
                 live: Optional[jax.Array] = None,
                 to_logits: bool = True) -> jax.Array:
        # `lengths` (each row's valid positions in a padded prefill) is
        # the engine's to pass and a recurrent layer's to need: here
        # padding lives at masked positions (_decode_attend) and it is
        # not read.  `to_logits=False` is `hidden_and_head`.
        del lengths
        cfg = self.cfg
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1])[None, :], tokens.shape)
        tensor_parallel = (self.mesh is not None
                           and self.mesh.shape.get('tensor', 1) > 1)
        embed_cls = OneHotEmbed if tensor_parallel else nn.Embed
        embed = embed_cls(
            cfg.vocab_size, cfg.dim, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=1.0), ('vocab', 'embed')),
            name='embed')
        x = embed(tokens)
        blocks = [Block] * cfg.n_layers
        if cfg.remat and not decode:
            plan = keep_plan(cfg, self.mesh, *tokens.shape)
            blocks = [nn.remat(
                Block, static_argnums=(3,),  # (self, x, positions, decode)
                policy=plan.policy(i)) for i in range(cfg.n_layers)]
        # Keep the historical 3-arg call where there is nothing more to
        # pass (the remat wrapper's static_argnums indexing depends on
        # it).
        more = ()
        if live is not None:
            more = (page_table, live)
        elif page_table is not None:
            more = (page_table,)
        for i, block in enumerate(blocks):
            x = block(cfg, self.mesh, name=f'layer_{i}')(
                x, positions, decode, *more)
        x = RMSNorm(cfg.norm_eps, cfg.dtype, cfg.param_dtype,
                    name='final_norm')(x)
        if cfg.tie_embeddings:
            if not to_logits:
                return x, embed.embedding, True
            logits = embed.attend(x)
        else:
            head = nn.Dense(
                cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), ('embed', 'vocab')),
                name='lm_head')
            if not to_logits:
                kernel = head.variables['params']['kernel']
                return x, nn.meta.unbox(kernel), False
            logits = head(x)
        return logits.astype(jnp.float32)

    def hidden_and_head(self, tokens: jax.Array):
        """Read by the train step (train/trainer.py), which then takes
        the head and the loss a chunk of rows at a time and never holds
        the logits whole (train/loss.py): the state after the final norm
        [B, S, D], the head's weights as the parameter tree holds them,
        and whether those are the embedding table [V, D] (tied) and not
        a kernel [D, V]."""
        return self(tokens, to_logits=False)


def init_params(model: Llama, rng: jax.Array, batch: int = 1,
                seq: Optional[int] = None):
    cfg = model.cfg
    seq = seq or min(cfg.max_seq_len, 128)
    tokens = jnp.zeros((batch, seq), jnp.int32)
    # Without per-block remat: the parameters are the same, and an eager
    # jax.checkpoint leaves every array it traced over alive in JAX's
    # trace cache — under the tensor-parallel engine a whole unsharded
    # copy stayed on device 0 (four-chip run, PR 22).
    plain = model.clone(cfg=dataclasses.replace(cfg, remat=False))
    return plain.init(rng, tokens)
