"""The openPangu-Ultra-MoE family: multi-head latent attention (MLA) with
sandwich norms in every block, leading dense SwiGLU layers, then a
dropless expert layer with a shared expert, an untied head
(`skypilot_tpu.models.openpangu_moe.OpenPanguMoE`).

Sizes, seeded weights, the program's module, the plain reference and the
decode step's cost, as `benchmarks/families/__init__.py` asks of a family,
and the latent decode kernel's cost for its share of the roofline.  A
configuration of this family is one chip's share of an expert-parallel
group: the file's `n_routed_experts` and `vocab_size` are what is HELD
(the first of the published ones), `published` gives the whole, the router
keeps its published width.  There is no `train_model`: a training mix on
this family ends through `families.need`.

Every matrix is normal(0, 1/fan_in) (the embedding normal(0, 1)), drawn
in float32 from a key folded from (seed, layer, leaf) and cast to the type
the configuration is run in.  Two of the norms' scales are not 1, and both
are there so that GREEDY DECODING FROM UNTRAINED WEIGHTS MIXES as text from
a trained model does, because the decode step follows the routing (PERF.md
section 6, PR 30 and PR 35):

* the two norms BEHIND a sublayer (`sandwich_norm`) have scale
  `BRANCH_SCALE`, so each branch is 0.3 of the stream.  A norm behind the
  sublayer undoes any scale of its output projection, so the branch's gain
  sits on that norm and the projection keeps its fan-in scale;
* the norm of the queries' bottleneck has scale `QUERY_SCALE`, so a head's
  scores have a spread of about 3 and it attends to a few positions of
  thousands.  With scale 1 the scores' spread is 1 and a head returns
  nearly the mean of all values: the same vector for every token of a
  sequence, which an untrained router reads as a fixed preference.

Read on the chip (PERF.md section 6, PR 35): with the residual-scaled
1/sqrt(2 x 61) behind the sublayers and flat attention, which ISSUE 35
proposed, a token's successor is nearly a function of the token, some
sequences of a wave fall into cycles (9 to 34 distinct tokens in 512), a
cycle's few tokens fix that sequence's routing, and a wave's step time
lay anywhere in 2% by its 32 sequences (held share 6.2% on one seed, 7.7%
on another).  With these two scales every sequence has 481-507 distinct
tokens in 512, the held experts get 6.19-6.26% of the pairs (6.25 is
even), and six waves' step times lie within 0.6%.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from benchmarks.harness.weights import normal, seed_key
from benchmarks.reference import openpangu_moe_ref

# The scales of the seeded norms that are not 1 (the docstring says why).
BRANCH_SCALE = 0.3
QUERY_SCALE = 3.0

# `--rehearse`: tiny widths, control flow only.
REHEARSAL = {
    'hidden_size': 64,
    'num_hidden_layers': 3,
    'first_k_dense_replace': 1,
    'num_attention_heads': 4,
    'num_key_value_heads': 4,
    'q_lora_rank': 32,
    'kv_lora_rank': 32,
    'qk_nope_head_dim': 16,
    'qk_rope_head_dim': 8,
    'v_head_dim': 16,
    'intermediate_size': 128,
    'moe_intermediate_size': 32,
    'n_routed_experts': 4,
    'num_experts_per_tok': 2,
    'vocab_size': 256,
    'published': {'num_hidden_layers': 61, 'first_k_dense_replace': 3,
                  'n_routed_experts': 16, 'vocab_size': 2048,
                  'num_nextn_predict_layers': 1},
}


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of one configuration file, by the published names."""
    hidden: int
    layers: int
    dense_layers: int           # leading layers with a dense FFN
    heads: int
    q_rank: int
    kv_rank: int                # the latent's width
    nope: int
    rope: int
    v_dim: int
    dense_ffn: int
    experts: int                # the router's width: all of them
    held: int                   # experts held here: ids 0 .. held - 1
    top_k: int
    expert_ffn: int
    shared: int
    scaling: float
    vocab: int                  # the held slice
    eps: float
    rope_theta: float
    # Held experts a decode step reaches, as a share of what even routing
    # would reach: read on the chip and kept in the configuration file
    # (`routing`), 1 where nothing was read.
    touched_over_even: float = 1.0

    @classmethod
    def from_config(cls, cfg: dict) -> 'Dims':
        if cfg.get('tie_word_embeddings') or cfg.get('attention_bias') or \
                cfg.get('num_nextn_predict_layers') or \
                cfg.get('rope_scaling'):
            raise ValueError('only the form the cell runs is handled: '
                             'untied, no biases, no long-context scaling, '
                             'no multi-token-prediction layer')
        if not (cfg['sandwich_norm'] and cfg['norm_topk_prob'] and
                cfg['hidden_act'] == 'silu'):
            raise ValueError('sandwich norms, normalised top-k weights and '
                             'SiLU are not optional here')
        if cfg['num_key_value_heads'] != cfg['num_attention_heads']:
            raise ValueError('latent attention has as many key heads as '
                             'query heads')
        return cls(
            hidden=cfg['hidden_size'], layers=cfg['num_hidden_layers'],
            dense_layers=cfg['first_k_dense_replace'],
            heads=cfg['num_attention_heads'], q_rank=cfg['q_lora_rank'],
            kv_rank=cfg['kv_lora_rank'], nope=cfg['qk_nope_head_dim'],
            rope=cfg['qk_rope_head_dim'], v_dim=cfg['v_head_dim'],
            dense_ffn=cfg['intermediate_size'],
            experts=cfg['published']['n_routed_experts'],
            held=cfg['n_routed_experts'], top_k=cfg['num_experts_per_tok'],
            expert_ffn=cfg['moe_intermediate_size'],
            shared=cfg['n_shared_experts'],
            scaling=float(cfg['routed_scaling_factor']),
            vocab=cfg['vocab_size'], eps=cfg['rms_norm_eps'],
            rope_theta=float(cfg['rope_theta']),
            touched_over_even=cfg.get('routing', {}).get(
                'touched_over_even', 1.0))

    @property
    def held_ids(self) -> Tuple[int, ...]:
        return tuple(range(self.held))

    @property
    def expert_layers(self) -> int:
        return self.layers - self.dense_layers

    def expert_params(self) -> int:
        return 3 * self.hidden * self.expert_ffn

    def attention_params(self) -> int:
        d, h = self.hidden, self.heads
        return (d * self.q_rank + self.q_rank +
                self.q_rank * h * (self.nope + self.rope) +
                d * (self.kv_rank + self.rope) + self.kv_rank +
                self.kv_rank * h * (self.nope + self.v_dim) +
                h * self.v_dim * d)

    def fixed_ffn_params(self, i: int) -> int:
        """What of layer i's FFN every token multiplies: the dense layer,
        or the router and the shared expert."""
        if i < self.dense_layers:
            return 3 * self.hidden * self.dense_ffn
        return (self.hidden * self.experts +
                self.shared * self.expert_params())

    def layer_params(self, i: int) -> int:
        routed = 0 if i < self.dense_layers else \
            self.held * self.expert_params()
        return (self.attention_params() + self.fixed_ffn_params(i) +
                routed + 4 * self.hidden)

    def num_params(self) -> int:
        """What is held here."""
        return (sum(self.layer_params(i) for i in range(self.layers)) +
                2 * self.vocab * self.hidden + self.hidden)

    def latent_bytes_per_position(self, itemsize: int = 2) -> int:
        """What the cache holds a position: the latent and its rotated
        part, every layer."""
        return self.layers * (self.kv_rank + self.rope) * itemsize


def dims(config: dict) -> Dims:
    return Dims.from_config(config)


def layer_weights(key: jax.Array, dims: Dims, layer, dtype,
                  dense: bool = None) -> dict:
    """One block's weights, in the program's layout.  The two kinds of
    layer are different trees: `dense` says which where `layer` is traced
    (one program then makes every layer of a kind), and is read from the
    layer's place where `layer` is a Python int."""
    d, h = dims.hidden, dims.heads
    if dense is None:
        dense = layer < dims.dense_layers
    keys = jax.random.split(jax.random.fold_in(key, layer + 1), 16)
    ones = jnp.ones((d,), dtype)
    behind = jnp.full((d,), BRANCH_SCALE, dtype)
    tree = {
        'attn_norm': {'scale': ones}, 'attn_post_norm': {'scale': behind},
        'ffn_norm': {'scale': ones}, 'ffn_post_norm': {'scale': behind},
        'attn': {
            'q_a': {'kernel': normal(keys[0], (d, dims.q_rank), d, dtype)},
            'q_norm': {'scale': jnp.full((dims.q_rank,), QUERY_SCALE, dtype)},
            'q_b': {'kernel': normal(
                keys[1], (dims.q_rank, h, dims.nope + dims.rope),
                dims.q_rank, dtype)},
            'kv_a': {'kernel': normal(
                keys[2], (d, dims.kv_rank + dims.rope), d, dtype)},
            'kv_norm': {'scale': jnp.ones((dims.kv_rank,), dtype)},
            'kv_b': normal(keys[3], (dims.kv_rank, h,
                                     dims.nope + dims.v_dim),
                           dims.kv_rank, dtype),
            'o_proj': {'kernel': normal(keys[4], (h, dims.v_dim, d),
                                        h * dims.v_dim, dtype)},
        },
    }
    if dense:
        f = dims.dense_ffn
        tree['mlp'] = {
            'gate_proj': {'kernel': normal(keys[5], (d, f), d, dtype)},
            'up_proj': {'kernel': normal(keys[6], (d, f), d, dtype)},
            'down_proj': {'kernel': normal(keys[7], (f, d), f, dtype)},
        }
        return tree
    f, held, shared = dims.expert_ffn, dims.held, dims.shared * \
        dims.expert_ffn
    tree['moe'] = {
        'router': normal(keys[8], (d, dims.experts), d, dtype),
        'w_gate': normal(keys[9], (held, d, f), d, dtype),
        'w_up': normal(keys[10], (held, d, f), d, dtype),
        'w_down': normal(keys[11], (held, f, d), f, dtype),
        'shared_gate': {'kernel': normal(keys[12], (d, shared), d, dtype)},
        'shared_up': {'kernel': normal(keys[13], (d, shared), d, dtype)},
        'shared_down': {'kernel': normal(keys[14], (shared, d), shared,
                                         dtype)},
    }
    return tree


def outer_weights(key: jax.Array, dims: Dims, dtype) -> dict:
    """Embedding, final norm and output head, of the held vocabulary."""
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
    """The module `DecodeEngine` is handed.  A checkout whose program
    has no such model (the parent of the PR that brought it, with these
    benchmark files laid over it) ends here, at once and with the reason."""
    try:
        from skypilot_tpu.models.openpangu_moe import (OpenPanguMoE,
                                                       OpenPanguMoEConfig)
    except ImportError as e:
        raise SystemExit(
            f'benchmark: this checkout cannot run configuration '
            f'{config.get("name")!r}: {e} (latent attention and its cache '
            f'came with skypilot_tpu/models/openpangu_moe.py)')
    return OpenPanguMoE(OpenPanguMoEConfig(
        vocab_size=dims.vocab, dim=dims.hidden, n_layers=dims.layers,
        n_dense_layers=dims.dense_layers, n_heads=dims.heads,
        q_rank=dims.q_rank, kv_rank=dims.kv_rank, nope_dim=dims.nope,
        rope_dim=dims.rope, v_dim=dims.v_dim, ffn_dim=dims.dense_ffn,
        n_experts=dims.experts, held_experts=dims.held_ids,
        experts_per_token=dims.top_k, expert_dim=dims.expert_ffn,
        n_shared_experts=dims.shared, routed_scaling=dims.scaling,
        rope_theta=dims.rope_theta, norm_eps=dims.eps,
        max_seq_len=config['serve']['max_seq_len'], dtype=dtype,
        param_dtype=dtype))


def reference(dims: Dims, seed: int, dtype, precision: str = 'float32'):
    """The plain reference over weights made again from the seed."""
    key = seed_key(seed)
    # The layer's index is traced: one program a kind of layer makes every
    # layer's weights (a program of random draws is slow to compile).
    # A layer stays in the type it is served in (the reference casts a
    # weight where it multiplies it): it has to fit beside the engine.
    of_kind = {dense: jax.jit(lambda i, dense=dense: layer_weights(
        key, dims, i, dtype, dense)) for dense in (True, False)}
    outer = jax.jit(lambda: jax.tree.map(
        lambda a: a.astype(jnp.float32), outer_weights(key, dims, dtype)))
    return openpangu_moe_ref.LayerwiseModel(
        dims, lambda i: of_kind[i < dims.dense_layers](i), outer, precision)


def touched_experts(dims: Dims, tokens: float) -> float:
    """Held experts that at least one of `tokens` tokens reaches, a
    layer, if every expert is as likely as another."""
    return dims.held * (1.0 - (1.0 - dims.top_k / dims.experts) ** tokens)


def least_touched_experts(dims: Dims, tokens: float) -> float:
    """The same at the least: uneven routing reaches fewer experts than
    even routing, by the share read on the chip at a full batch (fewer
    tokens lose less to unevenness, so the share keeps this a lower
    bound for them)."""
    return dims.touched_over_even * touched_experts(dims, tokens)


def latent_attention_cost(dims: Dims, live_slots: float,
                          live_positions: float, itemsize: int = 2) -> dict:
    """One layer's decode attention over the latent, a step, at the least
    (`ops/pallas/latent_decode_attention.py`): the latent and its rotated
    part of every live position read once, the heads' queries read and
    their sums written; a head's score against a position is a product of
    kv_rank + rope and its weighted sum one of kv_rank.  Two operations a
    multiply-add."""
    wide = dims.kv_rank + dims.rope
    return {
        'bytes': (wide * live_positions +
                  dims.heads * (wide + dims.kv_rank) * live_slots) * itemsize,
        'flops': 2.0 * dims.heads * (wide + dims.kv_rank) * live_positions,
    }


def decode_step_cost(dims: Dims, live_slots: float, live_positions: float,
                     itemsize: int = 2) -> dict:
    """One decode step for `live_slots` requests whose contexts sum to
    `live_positions`, at the least: every weight outside the routed
    experts once (the embedding is a lookup and not counted, the head's
    held slice is); an expert layer's held experts that at least one live
    token reaches (`least_touched_experts`: even routing's count times the
    share of it that the counter `skytpu_moe_experts_touched_total` read);
    the latent of the live positions in every layer, with the absorbed
    products against it (`latent_attention_cost`; the absorbed
    up-projections themselves are W_kvb's weights, counted with the
    rest).  Two operations a multiply-add."""
    fixed = (sum(dims.attention_params() + dims.fixed_ffn_params(i)
                 for i in range(dims.layers)) + dims.vocab * dims.hidden)
    touched = dims.expert_layers * least_touched_experts(dims, live_slots)
    routed_here = dims.expert_layers * live_slots * dims.top_k * (
        dims.held / dims.experts)
    core = latent_attention_cost(dims, live_slots, live_positions, itemsize)
    return {
        'bytes': (fixed + touched * dims.expert_params()) * itemsize +
        dims.layers * core['bytes'],
        'flops': 2.0 * fixed * live_slots +
        2.0 * routed_here * dims.expert_params() +
        dims.layers * core['flops'],
    }


def train_flops_per_token(dims: Dims, seq_len: int) -> float:
    """Forward and backward of what is held here, recompute not counted: 6
    for each parameter a token multiplies (of the routed experts, the
    top_k * held / experts that a token meets here on average) and 6 for
    each multiply-add of the expanded attention at the mean context (a
    head's score over nope + rope, its weighted sum over v).  No training
    cell runs this family (there is no `train_model`); the count is what
    the contract of a family asks."""
    multiplied = (sum(dims.attention_params() + dims.fixed_ffn_params(i)
                      for i in range(dims.layers)) +
                  dims.vocab * dims.hidden +
                  dims.expert_layers * dims.expert_params() *
                  dims.top_k * dims.held / dims.experts)
    return (6.0 * multiplied +
            6.0 * dims.layers * dims.heads *
            (dims.nope + dims.rope + dims.v_dim) * seq_len / 2.0)
