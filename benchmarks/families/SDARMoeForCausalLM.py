"""The SDAR-MoE family: a GQA block with per-head q/k norms, a dropless
expert layer (softmax router, no shared expert) in every block, an untied
head, generating by diffusion over blocks of positions
(`skypilot_tpu.models.sdar_moe.SDARMoE`).

Sizes, seeded weights, the program's module, the plain reference and the
cost of the serving step, as `benchmarks/families/__init__.py` asks of a
family.  A configuration of this family is a stage of a pipeline: the file's
`num_hidden_layers` is what is held, every layer whole with all its
experts, the whole vocabulary (this chip stands for the first stage's
embedding and the last stage's head).  There is no `train_model`: a
training mix on this family ends through `families.need`.

**The serving step is a pass over a block a slot**, not a token a slot:
`decode_step_cost` counts ONE PASS (`live_slots x block` rows), and the
harness's `decode_step_ms` (`jit_decode` over `serve.steps_per_call`) then
reads a pass, `decode_roofline_pct` a pass's share.  `reference(...).hidden`
returns at index t the state from which position t + 1's token was taken
under the configuration's `sequential` order (`reference/sdar_moe_ref.py`
says how, and why an order that the tokens do not fix could not be
checked this way).

Every matrix is normal(0, 1/fan_in) (the embedding normal(0, 1)), drawn in
float32 from a key folded from (seed, layer, leaf) and cast to the type the
configuration is run in; every norm's scale is 1 but the queries' per-head
norm, `QUERY_SCALE`, so that a head's scores have a spread of about 3 and
it attends to a few positions of hundreds (PERF.md section 6, PR 35: with
scale 1 a head returns nearly the mean of all values, the same vector at
every position).  Here that matters twice: every masked position of every
in-flight block enters the stack as the SAME mask embedding (up to
`block x n_slots` rows of a pass), and only what attention brings from
each row's own context tells them apart before the first router reads
them.  The branches keep their fan-in scale (each about as large as the
stream), so a masked row's router input is mostly its context.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from benchmarks.harness.weights import normal, seed_key
from benchmarks.reference import sdar_moe_ref

# The scale of the queries' per-head norm (the docstring says why).
QUERY_SCALE = 3.0

# `--rehearse`: tiny widths, control flow only.
REHEARSAL = {
    'hidden_size': 64,
    'num_hidden_layers': 2,
    'num_attention_heads': 4,
    'num_key_value_heads': 2,
    'head_dim': 16,
    'moe_intermediate_size': 32,
    'num_experts': 8,
    'num_experts_per_tok': 2,
    'vocab_size': 256,
    'mask_token_id': 255,
    'published': {'num_hidden_layers': 16},
}


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of one configuration file, by the published names."""
    hidden: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    experts: int                # all held: the router's width
    top_k: int
    expert_ffn: int
    vocab: int
    eps: float
    rope_theta: float
    # Generation by blocks (the configuration's `generation`).
    block: int
    steps: int
    mask_id: int
    remasking: str
    threshold: float
    # Experts a pass reaches, as a share of what even routing would reach:
    # read on the chip and kept in the configuration file (`routing`), 1
    # where nothing was read.
    touched_over_even: float = 1.0

    @classmethod
    def from_config(cls, cfg: dict) -> 'Dims':
        if cfg.get('tie_word_embeddings') or cfg.get('attention_bias') or \
                cfg.get('rope_scaling') or cfg.get('use_sliding_window') or \
                cfg.get('mlp_only_layers'):
            raise ValueError('only the form the cell runs is handled: '
                             'untied, no biases, no long-context scaling, '
                             'no window, an expert layer in every block')
        if not (cfg['norm_topk_prob'] and cfg['hidden_act'] == 'silu' and
                cfg.get('decoder_sparse_step', 1) == 1):
            raise ValueError('normalised top-k weights, SiLU and an expert '
                             'layer in every block are not optional here')
        gen = cfg.get('generation', {})
        return cls(
            hidden=cfg['hidden_size'], layers=cfg['num_hidden_layers'],
            heads=cfg['num_attention_heads'],
            kv_heads=cfg['num_key_value_heads'], head_dim=cfg['head_dim'],
            experts=cfg['num_experts'], top_k=cfg['num_experts_per_tok'],
            expert_ffn=cfg['moe_intermediate_size'],
            vocab=cfg['vocab_size'], eps=cfg['rms_norm_eps'],
            rope_theta=float(cfg['rope_theta']),
            block=int(gen.get('block_length', 4)),
            steps=int(gen.get('denoising_steps', 4)),
            mask_id=int(cfg['mask_token_id']),
            remasking=gen.get('remasking', 'low_confidence_static'),
            threshold=float(gen.get('confidence_threshold', 0.9)),
            touched_over_even=cfg.get('routing', {}).get(
                'touched_over_even', 1.0))

    @property
    def held(self) -> int:
        return self.experts

    @property
    def held_ids(self) -> Tuple[int, ...]:
        return tuple(range(self.experts))

    def expert_params(self) -> int:
        return 3 * self.hidden * self.expert_ffn

    def fixed_params(self) -> int:
        """What of a layer every row multiplies: the projections, the four
        norms and the router."""
        d, hd = self.hidden, self.head_dim
        return (2 * d * self.heads * hd + 2 * d * self.kv_heads * hd +
                2 * hd + 2 * d + d * self.experts)

    def layer_params(self) -> int:
        return self.fixed_params() + self.experts * self.expert_params()

    def num_params(self) -> int:
        """What is held here: the stage's layers, the embedding, the final
        norm and the head."""
        return (self.layers * self.layer_params() +
                2 * self.vocab * self.hidden + self.hidden)

    def kv_bytes_per_position(self, itemsize: int = 2) -> int:
        return self.layers * 2 * self.kv_heads * self.head_dim * itemsize


def dims(config: dict) -> Dims:
    return Dims.from_config(config)


def layer_weights(key: jax.Array, dims: Dims, layer, dtype) -> dict:
    """One block's weights, in the program's layout (`layer` may be
    traced: one program then makes every layer)."""
    d, hd, f, e = dims.hidden, dims.head_dim, dims.expert_ffn, dims.experts
    keys = jax.random.split(jax.random.fold_in(key, layer + 1), 8)
    ones = jnp.ones((d,), dtype)
    return {
        'attn_norm': {'scale': ones}, 'moe_norm': {'scale': ones},
        'attn': {
            'q_proj': {'kernel': normal(keys[0], (d, dims.heads, hd), d,
                                        dtype)},
            'k_proj': {'kernel': normal(keys[1], (d, dims.kv_heads, hd), d,
                                        dtype)},
            'v_proj': {'kernel': normal(keys[2], (d, dims.kv_heads, hd), d,
                                        dtype)},
            'q_norm': {'scale': jnp.full((hd,), QUERY_SCALE, dtype)},
            'k_norm': {'scale': jnp.ones((hd,), dtype)},
            'o_proj': {'kernel': normal(keys[3], (dims.heads, hd, d),
                                        dims.heads * hd, dtype)},
        },
        'moe': {
            'router': normal(keys[4], (d, e), d, dtype),
            'w_gate': normal(keys[5], (e, d, f), d, dtype),
            'w_up': normal(keys[6], (e, d, f), d, dtype),
            'w_down': normal(keys[7], (e, f, d), f, dtype),
        },
    }


def outer_weights(key: jax.Array, dims: Dims, dtype) -> dict:
    """Embedding, final norm and output head."""
    k_embed, k_head = jax.random.split(jax.random.fold_in(key, 0))
    return {
        'embed': {'embedding': jax.random.normal(
            k_embed, (dims.vocab, dims.hidden), jnp.float32).astype(dtype)},
        'final_norm': {'scale': jnp.ones((dims.hidden,), dtype)},
        'lm_head': {'kernel': normal(k_head, (dims.hidden, dims.vocab),
                                      dims.hidden, dtype)},
    }


def make_params(key: jax.Array, dims: Dims, dtype) -> dict:
    """The whole tree; call it under `jax.jit` (one program, on the device)."""
    tree = outer_weights(key, dims, dtype)
    for i in range(dims.layers):
        tree[f'layer_{i}'] = layer_weights(key, dims, i, dtype)
    return tree


def serve_model(dims: Dims, config: dict, dtype):
    """The module `DecodeEngine` is handed; it declares the block length
    and the schedule the engine serves it by.  A checkout whose program
    has no such model (the parent of the PR that brought it, with these
    benchmark files laid over it) ends here, at once and with the reason."""
    try:
        from skypilot_tpu.models.sdar_moe import SDARMoE, SDARMoEConfig
    except ImportError as e:
        raise SystemExit(
            f'benchmark: this checkout cannot run configuration '
            f'{config.get("name")!r}: {e} (generation by blocks came with '
            f'skypilot_tpu/models/sdar_moe.py and the engine\'s passes '
            f'over blocks)')
    return SDARMoE(SDARMoEConfig(
        vocab_size=dims.vocab, dim=dims.hidden, n_layers=dims.layers,
        n_heads=dims.heads, n_kv_heads=dims.kv_heads,
        head_dim=dims.head_dim, n_experts=dims.experts,
        held_experts=dims.held_ids, experts_per_token=dims.top_k,
        expert_dim=dims.expert_ffn, rope_theta=dims.rope_theta,
        norm_eps=dims.eps, max_seq_len=config['serve']['max_seq_len'],
        block_length=dims.block, mask_id=dims.mask_id,
        remasking=dims.remasking, denoising_steps=dims.steps,
        confidence_threshold=dims.threshold, dtype=dtype,
        param_dtype=dtype))


def reference(dims: Dims, seed: int, dtype, precision: str = 'float32'):
    """The plain reference over weights made again from the seed."""
    key = seed_key(seed)
    # The layer's index is traced: one program makes every layer's weights.
    # A layer and the outer weights stay in the type they are served in
    # (the reference casts a weight where it multiplies it): they have to
    # fit beside the engine.
    layer = jax.jit(lambda i: layer_weights(key, dims, i, dtype))
    outer = jax.jit(lambda: outer_weights(key, dims, dtype))
    return sdar_moe_ref.LayerwiseModel(dims, layer, outer, precision)


def touched_experts(dims: Dims, tokens: float) -> float:
    """Experts that at least one of `tokens` rows reaches, a layer, if
    every expert is as likely as another."""
    return dims.experts * (1.0 - (1.0 - dims.top_k / dims.experts) ** tokens)


def least_touched_experts(dims: Dims, tokens: float) -> float:
    """The same at the least (`touched_over_even`: the share of even
    routing's count that the chip read at a full batch)."""
    return dims.touched_over_even * touched_experts(dims, tokens)


def decode_step_cost(dims: Dims, live_slots: float, live_positions: float,
                     itemsize: int = 2) -> dict:
    """ONE PASS over the blocks of `live_slots` requests whose contexts sum
    to `live_positions`, at the least: `live_slots x block` rows.  Every
    weight outside the experts once (the embedding is a lookup and not
    counted, the head is: logits are taken at every row); the experts that
    at least one row reaches, a layer (`least_touched_experts`); K and V
    of the live positions in every layer, read once for the block's rows;
    two operations a multiply-add: a row multiplies the fixed weights, its
    `top_k` experts, and its scores and weighted sum against its slot's
    positions."""
    rows = live_slots * dims.block
    fixed = dims.layers * dims.fixed_params() + dims.vocab * dims.hidden
    touched = dims.layers * least_touched_experts(dims, rows)
    return {
        'bytes': (fixed + touched * dims.expert_params()) * itemsize +
        live_positions * dims.kv_bytes_per_position(itemsize),
        'flops': 2.0 * rows * (fixed + dims.layers * dims.top_k *
                               dims.expert_params()) +
        4.0 * dims.layers * dims.heads * dims.head_dim * dims.block *
        live_positions,
    }


def train_flops_per_token(dims: Dims, seq_len: int) -> float:
    """Forward and backward of what is held here, recompute not counted: 6
    for each parameter a token multiplies (of the experts, its `top_k`) and
    6 for each multiply-add of attention at the mean context.  No training
    cell runs this family (there is no `train_model`); the count is what
    the contract of a family asks."""
    multiplied = (dims.layers * (dims.fixed_params() +
                                 dims.top_k * dims.expert_params()) +
                  dims.vocab * dims.hidden)
    return (6.0 * multiplied +
            6.0 * dims.layers * dims.heads * 2 * dims.head_dim *
            seq_len / 2.0)
