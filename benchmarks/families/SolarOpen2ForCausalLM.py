"""The Solar-Open2 family: a layer pattern of one gated NoPE grouped-query
softmax layer and three Kimi Delta Attention layers, a dropless expert
layer with a shared expert in every block, an untied head
(`skypilot_tpu.models.solar_open2.SolarOpen2`).

Sizes, seeded weights, the program's module, the plain reference and the
decode step's cost, as `benchmarks/families/__init__.py` asks of a family.
A configuration of this family is one chip's share of an expert-parallel
group: the file's `n_routed_experts` and `vocab_size` are what is HELD
(the first of the published ones), `published` gives the whole, the router
keeps its published width.  There is no `train_model`: a training mix on
this family ends through `families.need`.

Every matrix is normal(0, 1/fan_in) (the embedding normal(0, 1), norm
scales 1), drawn in float32 from a key folded from (seed, layer, leaf) and
cast to the type the configuration is run in.  The two mixing layers'
output projections are drawn at 1/sqrt(2 x published layers) of that
scale, the residual-scaled initialisation of deep decoders (`branch_fan`).
It is there for the routers: an untrained linear layer hands every token
nearly the same vector (its values and keys pass a SiLU and are positive
on average), an untrained router reads a constant in its input as a fixed
preference for some experts, and the 40 held ones then get 10 to 14% of
the pairs by the seed and a decode step 77 to 85 trips of the expert loop
(PERF.md section 6, PR 30).  A trained router is balanced for the inputs
it meets; with the constant a thirtieth of the stream every seed's
routing is near even, as a deployment's is.  The linear layer's decay is
set as its public code sets it: `A_log` = log uniform(1, 16) a head,
`dt_bias` the inverse softplus of exp(uniform(log 0.001, log 0.1)) a
channel, so that a channel forgets over tens to hundreds of positions.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from benchmarks.harness.weights import normal, seed_key
from benchmarks.reference import solar_open2_ref

# `--rehearse`: tiny widths, control flow only.
REHEARSAL = {
    'hidden_size': 64,
    'num_hidden_layers': 4,
    'num_attention_heads': 4,
    'num_key_value_heads': 2,
    'head_dim': 16,
    'linear_attn_config': {'short_conv_kernel_size': 4, 'head_dim': 16,
                           'num_heads': 4, 'num_kv_heads': None},
    'moe_intermediate_size': 32,
    'n_routed_experts': 4,
    'num_experts_per_tok': 2,
    'vocab_size': 256,
    'published': {'num_hidden_layers': 48, 'n_routed_experts': 16,
                  'vocab_size': 2048},
}


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of one configuration file, by the published names."""
    hidden: int
    layers: int
    softmax_layers: Tuple[int, ...]
    heads: int
    kv_heads: int
    head_dim: int
    lin_heads: int
    lin_head_dim: int
    conv: int
    experts: int                # the router's width: all of them
    held: int                   # experts held here: ids 0 .. held - 1
    top_k: int
    expert_ffn: int
    shared: int
    scaling: float
    vocab: int                  # the held slice
    eps: float
    depth: int                  # the published model's layers
    # Held experts a decode step reaches, as a share of what even routing
    # would reach: read on the chip and kept in the configuration file
    # (`routing`), 1 where nothing was read.
    touched_over_even: float = 1.0

    @classmethod
    def from_config(cls, cfg: dict) -> 'Dims':
        if cfg.get('tie_word_embeddings') or cfg.get('use_rope') or \
                cfg.get('kda_use_full_proj') or cfg['first_k_dense_replace']:
            raise ValueError('only the published Solar-Open2 form is handled: '
                             'untied, no rotary, low-rank decay, no dense '
                             'layer')
        if not (cfg['use_gqa_gate'] and cfg['kda_allow_neg_eigval'] and
                cfg['norm_topk_prob']):
            raise ValueError('the gate, beta in (0, 2) and normalised '
                             'top-k weights are not optional here')
        lin = cfg['linear_attn_config']
        layers = cfg['num_hidden_layers']
        return cls(
            hidden=cfg['hidden_size'], layers=layers,
            softmax_layers=tuple(i for i in cfg['gqa_layers'] if i < layers),
            heads=cfg['num_attention_heads'],
            kv_heads=cfg['num_key_value_heads'], head_dim=cfg['head_dim'],
            lin_heads=lin['num_heads'], lin_head_dim=lin['head_dim'],
            conv=lin['short_conv_kernel_size'],
            experts=cfg['published']['n_routed_experts'],
            held=cfg['n_routed_experts'], top_k=cfg['num_experts_per_tok'],
            expert_ffn=cfg['moe_intermediate_size'],
            shared=cfg['n_shared_experts'],
            scaling=float(cfg['routed_scaling_factor']),
            vocab=cfg['vocab_size'], eps=cfg['rms_norm_eps'],
            depth=cfg['published']['num_hidden_layers'],
            touched_over_even=cfg.get('routing', {}).get(
                'touched_over_even', 1.0))

    @property
    def held_ids(self) -> Tuple[int, ...]:
        return tuple(range(self.held))

    @property
    def branch_fan(self) -> int:
        """What its fan-in is multiplied by for a mixing layer's output
        projection: two branches a layer of the published depth."""
        return 2 * self.depth

    @property
    def rank(self) -> int:
        """Low-rank width of the decay and gate projections."""
        return self.lin_head_dim

    def expert_params(self) -> int:
        return 3 * self.hidden * self.expert_ffn

    def mix_params(self, i: int) -> int:
        d = self.hidden
        if i in self.softmax_layers:
            return (3 * d * self.heads * self.head_dim +       # q, gate, o
                    2 * d * self.kv_heads * self.head_dim)
        wide = self.lin_heads * self.lin_head_dim
        return (4 * d * wide + 2 * (d * self.rank + self.rank * wide) +
                d * self.lin_heads + 3 * wide * self.conv +
                self.lin_heads + wide + self.lin_head_dim)

    def layer_params(self, i: int) -> int:
        return (self.mix_params(i) + self.hidden * self.experts +
                (self.held + self.shared) * self.expert_params() +
                2 * self.hidden)

    def num_params(self) -> int:
        """What is held here."""
        return (sum(self.layer_params(i) for i in range(self.layers)) +
                2 * self.vocab * self.hidden + self.hidden)

    def state_bytes_per_slot(self) -> int:
        """One slot's recurrent state: a float32 matrix a head and the
        convolution's taps (q, k and v, bfloat16), over the linear
        layers."""
        wide = self.lin_heads * self.lin_head_dim
        n_lin = self.layers - len(self.softmax_layers)
        return n_lin * (wide * self.lin_head_dim * 4 +
                        (self.conv - 1) * 3 * wide * 2)

    def kv_bytes_per_position(self, itemsize: int = 2) -> int:
        return (2 * len(self.softmax_layers) * self.kv_heads *
                self.head_dim * itemsize)


def dims(config: dict) -> Dims:
    return Dims.from_config(config)


def _softmax_weights(keys, dims: Dims, dtype) -> dict:
    d, h, kv, hd = dims.hidden, dims.heads, dims.kv_heads, dims.head_dim
    return {'attn': {
        'q_proj': {'kernel': normal(keys[0], (d, h, hd), d, dtype)},
        'k_proj': {'kernel': normal(keys[1], (d, kv, hd), d, dtype)},
        'v_proj': {'kernel': normal(keys[2], (d, kv, hd), d, dtype)},
        'g_proj': {'kernel': normal(keys[3], (d, h, hd), d, dtype)},
        'o_proj': {'kernel': normal(keys[4], (h, hd, d),
                                   h * hd * dims.branch_fan, dtype)},
    }}


def _linear_weights(keys, dims: Dims, dtype) -> dict:
    d, h, hd, r = dims.hidden, dims.lin_heads, dims.lin_head_dim, dims.rank
    a = jax.random.uniform(keys[12], (h,), jnp.float32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(keys[13], (h, hd), jnp.float32,
                                    math.log(0.001), math.log(0.1)))
    out = {
        'o_proj': {'kernel': normal(keys[3], (h, hd, d),
                                   h * hd * dims.branch_fan, dtype)},
        'f_a': {'kernel': normal(keys[4], (d, r), d, dtype)},
        'f_b': {'kernel': normal(keys[5], (r, h, hd), r, dtype)},
        'g_a': {'kernel': normal(keys[6], (d, r), d, dtype)},
        'g_b': {'kernel': normal(keys[7], (r, h, hd), r, dtype)},
        'b_proj': {'kernel': normal(keys[8], (d, h), d, dtype)},
        'A_log': jnp.log(a).astype(dtype),
        'dt_bias': (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        'o_norm': jnp.ones((hd,), dtype),
    }
    for j, n in enumerate('qkv'):
        out[f'{n}_proj'] = {'kernel': normal(keys[j], (d, h, hd), d, dtype)}
        out[f'{n}_conv'] = normal(keys[9 + j], (dims.conv, h, hd), dims.conv,
                                  dtype)
    return {'kda': out}


def layer_weights(key: jax.Array, dims: Dims, layer, dtype,
                  softmax: bool = None) -> dict:
    """One block's weights, in the program's layout.  The two kinds of
    layer are different trees: `softmax` says which where `layer` is
    traced (one program then makes every layer of a kind), and is read
    from the pattern where `layer` is a Python int."""
    d, f, held = dims.hidden, dims.expert_ffn, dims.held
    if softmax is None:
        softmax = layer in dims.softmax_layers
    keys = jax.random.split(jax.random.fold_in(key, layer + 1), 24)
    mix = (_softmax_weights if softmax else _linear_weights)(
        keys[:14], dims, dtype)
    ones = jnp.ones((d,), dtype)
    shared = dims.shared * f
    return {
        'mix_norm': {'scale': ones}, 'moe_norm': {'scale': ones}, **mix,
        'moe': {
            'router': normal(keys[14], (d, dims.experts), d, dtype),
            'w_gate': normal(keys[15], (held, d, f), d, dtype),
            'w_up': normal(keys[16], (held, d, f), d, dtype),
            'w_down': normal(keys[17], (held, f, d), f, dtype),
            'shared_gate': {'kernel': normal(keys[18], (d, shared), d,
                                             dtype)},
            'shared_up': {'kernel': normal(keys[19], (d, shared), d, dtype)},
            'shared_down': {'kernel': normal(keys[20], (shared, d), shared,
                                             dtype)},
        },
    }


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
    """The module `DecodeEngine` is handed."""
    from skypilot_tpu.models.solar_open2 import SolarOpen2, SolarOpen2Config
    return SolarOpen2(SolarOpen2Config(
        vocab_size=dims.vocab, dim=dims.hidden, n_layers=dims.layers,
        gqa_layers=dims.softmax_layers, n_heads=dims.heads,
        n_kv_heads=dims.kv_heads, head_dim=dims.head_dim,
        kda_heads=dims.lin_heads, kda_head_dim=dims.lin_head_dim,
        kda_conv=dims.conv, kda_rank=dims.rank, n_experts=dims.experts,
        held_experts=dims.held_ids, experts_per_token=dims.top_k,
        expert_dim=dims.expert_ffn, n_shared_experts=dims.shared,
        routed_scaling=dims.scaling, norm_eps=dims.eps,
        max_seq_len=config['serve']['max_seq_len'], dtype=dtype,
        param_dtype=dtype))


def _to_f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def reference(dims: Dims, seed: int, dtype, precision: str = 'float32'):
    """The plain reference over weights made again from the seed."""
    key = seed_key(seed)
    # The layer's index is traced: one program a kind of layer makes every
    # layer's weights (a program of random draws is slow to compile).
    of_kind = {softmax: jax.jit(lambda i, softmax=softmax: _to_f32(
        layer_weights(key, dims, i, dtype, softmax)))
        for softmax in (True, False)}
    outer = jax.jit(lambda: _to_f32(outer_weights(key, dims, dtype)))
    return solar_open2_ref.LayerwiseModel(
        dims, lambda i: of_kind[i in dims.softmax_layers](i), outer,
        precision)


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


def decode_step_cost(dims: Dims, live_slots: float, live_positions: float,
                     itemsize: int = 2) -> dict:
    """One decode step for `live_slots` requests whose contexts sum to
    `live_positions`, at the least: every weight outside the routed
    experts once (the embedding is a lookup and not counted, the head's
    held slice is); a layer's held experts that at least one live token
    reaches (`least_touched_experts`: even routing's count times the share
    of it that the counter `skytpu_moe_experts_touched_total` read); every live
    slot's recurrent state read and written; K and V of the live
    positions in the softmax layers.  Two operations a multiply-add."""
    d = dims.hidden
    fixed = (sum(dims.mix_params(i) + d * dims.experts +
                 dims.shared * dims.expert_params()
                 for i in range(dims.layers)) + dims.vocab * d)
    touched = dims.layers * least_touched_experts(dims, live_slots)
    routed_here = dims.layers * live_slots * dims.top_k * (
        dims.held / dims.experts)
    n_lin = dims.layers - len(dims.softmax_layers)
    state = dims.lin_heads * dims.lin_head_dim * dims.lin_head_dim
    return {
        'bytes': (fixed + touched * dims.expert_params()) * itemsize +
        2.0 * dims.state_bytes_per_slot() * live_slots +
        dims.kv_bytes_per_position(itemsize) * live_positions,
        'flops': 2.0 * fixed * live_slots +
        2.0 * routed_here * dims.expert_params() +
        8.0 * n_lin * state * live_slots +
        4.0 * len(dims.softmax_layers) * dims.heads * dims.head_dim *
        live_positions,
    }


def train_flops_per_token(dims: Dims, seq_len: int) -> float:
    """Forward and backward of what is held here, recompute not counted: 6
    for each parameter a token multiplies (of the routed experts, the
    top_k * held / experts that a token meets here on average), 12 for
    each score of the softmax layers at the mean context, and the linear
    layers' state products (four [dk x dv] products a head and token,
    forward).  No training cell runs this family yet (there is no
    `train_model`); the count is here for the day one does."""
    d = dims.hidden
    multiplied = (sum(dims.mix_params(i) + d * dims.experts
                      for i in range(dims.layers)) + dims.vocab * d +
                  dims.layers * dims.expert_params() * (
                      dims.shared + dims.top_k * dims.held / dims.experts))
    n_lin = dims.layers - len(dims.softmax_layers)
    state = dims.lin_heads * dims.lin_head_dim * dims.lin_head_dim
    return (6.0 * multiplied +
            12.0 * len(dims.softmax_layers) * dims.heads * dims.head_dim *
            seq_len / 2.0 + 3.0 * 8.0 * n_lin * state)
