"""The Granite-4.0-H family without routed experts: Mamba-2 layers beside
a few NoPE grouped-query attention layers, a shared SwiGLU in every block,
four scalar multipliers, a tied head
(`skypilot_tpu.models.granite_hybrid.GraniteHybrid`).

Sizes, seeded weights, the program's module, the plain reference and the
decode step's cost, as `benchmarks/families/__init__.py` asks of a family.
Nothing of the model is cut: a configuration of this family is the whole
model on one chip.  There is no `train_model`: a training mix on this
family ends through `families.need`.

Every matrix is normal(0, 1/fan_in), norm scales 1, drawn in float32 from
a key folded from (seed, layer, leaf) and cast to the type the
configuration is run in.  The Mamba-2 mixer's own parameters are set as
its public code sets them: `A_log` = log uniform(1, 16) a head, `dt_bias`
the inverse softplus of exp(uniform(log 0.001, log 0.1)) a head, `D` = 1,
the convolution's bias uniform(-1/2, 1/2) (a depthwise Conv1d of 4 taps).

The embedding is normal(0, `EMBED_STD`^2) and NOT normal(0, 1).  The table
is also the head, and the stream starts as 12 times a row of it: at a
standard deviation of 1 the last token's own row is 12 of the final
stream's 12.1 a channel, its logit stands 90 standard deviations above the
others', and greedy decoding from untrained weights repeats its first
token for ever, so that the reference agrees with any program, the int8
control included, by a margin no rounding crosses (read on the chip at
1, 0.1 and 0.03: one token a row for ever; at 0.01 some rows still settle
on one).  A trained table is small (the multiplier of 12 is there because
it is); at `EMBED_STD` the row a token brings is a fortieth of what the 80
branches add, no logit stands out by construction, and the served tokens
wander over the vocabulary as a deployment's do (190 of 192 distinct a
row; the readings are in PERF.md section 6, PR 43).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from benchmarks.harness.weights import normal, seed_key
from benchmarks.reference import granite_hybrid_ref

# The embedding's standard deviation (the module docstring says why).
EMBED_STD = 0.003

# `--rehearse`: tiny widths, control flow only.
REHEARSAL = {
    'hidden_size': 64,
    'num_hidden_layers': 4,
    'layer_types': ['mamba', 'attention', 'mamba', 'mamba'],
    'num_attention_heads': 4,
    'num_key_value_heads': 2,
    'shared_intermediate_size': 128,
    'intermediate_size': 128,
    'mamba_n_heads': 4,
    'mamba_d_head': 16,
    'mamba_d_state': 16,
    'mamba_expand': 1,
    'mamba_chunk_size': 8,
    'vocab_size': 256,
}


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of one configuration file, by the published names."""
    hidden: int
    layers: int
    attention_layers: Tuple[int, ...]
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    conv: int
    chunk: int
    embedding_multiplier: float
    attention_multiplier: float
    residual_multiplier: float
    logits_scaling: float
    vocab: int
    eps: float

    @classmethod
    def from_config(cls, cfg: dict) -> 'Dims':
        if (cfg.get('num_local_experts') or cfg.get('num_experts_per_tok')
                or not cfg.get('tie_word_embeddings')
                or cfg.get('position_embedding_type') != 'nope'
                or cfg.get('attention_bias') or cfg.get('mamba_proj_bias')
                or not cfg.get('mamba_conv_bias')
                or cfg.get('mamba_n_groups') != 1
                or cfg.get('normalization_function') != 'rmsnorm'
                or cfg.get('hidden_act') != 'silu'):
            raise ValueError('only the published Granite-4.0-H dense form is '
                             'handled: no routed experts, a tied head, no '
                             'positional term, one group of B and C, a bias '
                             'on the convolution alone, RMSNorm, SiLU')
        if cfg['mamba_n_heads'] * cfg['mamba_d_head'] != \
                cfg['mamba_expand'] * cfg['hidden_size']:
            raise ValueError('mamba_n_heads x mamba_d_head is not '
                             'mamba_expand x hidden_size')
        kinds = cfg['layer_types']
        if len(kinds) != cfg['num_hidden_layers'] or \
                set(kinds) - {'mamba', 'attention'}:
            raise ValueError('layer_types names every layer mamba or '
                             'attention')
        return cls(
            hidden=cfg['hidden_size'], layers=cfg['num_hidden_layers'],
            attention_layers=tuple(i for i, kind in enumerate(kinds)
                                   if kind == 'attention'),
            heads=cfg['num_attention_heads'],
            kv_heads=cfg['num_key_value_heads'],
            head_dim=cfg['hidden_size'] // cfg['num_attention_heads'],
            ffn=cfg['shared_intermediate_size'],
            ssm_heads=cfg['mamba_n_heads'], ssm_head_dim=cfg['mamba_d_head'],
            ssm_state=cfg['mamba_d_state'], conv=cfg['mamba_d_conv'],
            chunk=cfg['mamba_chunk_size'],
            embedding_multiplier=float(cfg['embedding_multiplier']),
            attention_multiplier=float(cfg['attention_multiplier']),
            residual_multiplier=float(cfg['residual_multiplier']),
            logits_scaling=float(cfg['logits_scaling']),
            vocab=cfg['vocab_size'], eps=cfg['rms_norm_eps'])

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_width(self) -> int:
        return self.ssm_inner + 2 * self.ssm_state

    def mixer_matrices(self, i: int) -> int:
        """The mixer's parameters that a token multiplies."""
        d = self.hidden
        if i in self.attention_layers:
            return 2 * d * self.head_dim * (self.heads + self.kv_heads)
        return (d * (self.ssm_inner + self.conv_width + self.ssm_heads) +
                self.ssm_inner * d)

    def mixer_params(self, i: int) -> int:
        if i in self.attention_layers:
            return self.mixer_matrices(i)
        return (self.mixer_matrices(i) + self.conv_width * (self.conv + 1) +
                3 * self.ssm_heads + self.ssm_inner)

    def layer_params(self, i: int) -> int:
        return self.mixer_params(i) + 3 * self.hidden * self.ffn + \
            2 * self.hidden

    def num_params(self) -> int:
        return (sum(self.layer_params(i) for i in range(self.layers)) +
                self.vocab * self.hidden + self.hidden)

    def matmul_params(self) -> int:
        """What a decode step streams through the matrix unit: every
        matrix, the table once as the head (the lookup multiplies
        nothing)."""
        return (sum(self.mixer_matrices(i) + 3 * self.hidden * self.ffn
                    for i in range(self.layers)) + self.vocab * self.hidden)

    @property
    def ssm_layers(self) -> int:
        return self.layers - len(self.attention_layers)

    def head_state_bytes(self) -> int:
        """One layer's float32 state a slot."""
        return self.ssm_inner * self.ssm_state * 4

    def state_bytes_per_slot(self) -> int:
        """One slot's recurrent state: a float32 matrix a head and the
        convolution's taps (bfloat16), over the Mamba layers."""
        return self.ssm_layers * (self.head_state_bytes() +
                                  (self.conv - 1) * self.conv_width * 2)

    def kv_bytes_per_position(self, itemsize: int = 2) -> int:
        return (2 * len(self.attention_layers) * self.kv_heads *
                self.head_dim * itemsize)


def dims(config: dict) -> Dims:
    return Dims.from_config(config)


def _attention_weights(keys, dims: Dims, dtype) -> dict:
    d, h, kv, hd = dims.hidden, dims.heads, dims.kv_heads, dims.head_dim
    return {'attn': {
        'q_proj': {'kernel': normal(keys[0], (d, h, hd), d, dtype)},
        'k_proj': {'kernel': normal(keys[1], (d, kv, hd), d, dtype)},
        'v_proj': {'kernel': normal(keys[2], (d, kv, hd), d, dtype)},
        'o_proj': {'kernel': normal(keys[3], (h, hd, d), h * hd, dtype)},
    }}


def _mamba_weights(keys, dims: Dims, dtype) -> dict:
    d, h, inner, wide = (dims.hidden, dims.ssm_heads, dims.ssm_inner,
                         dims.conv_width)
    a = jax.random.uniform(keys[4], (h,), jnp.float32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(keys[5], (h,), jnp.float32,
                                    math.log(0.001), math.log(0.1)))
    return {'mamba': {
        'in_proj': {'kernel': normal(keys[0], (d, inner + wide + h), d,
                                     dtype)},
        'conv_w': normal(keys[1], (dims.conv, wide), dims.conv, dtype),
        'conv_b': jax.random.uniform(keys[2], (wide,), jnp.float32, -0.5,
                                     0.5).astype(dtype),
        'A_log': jnp.log(a).astype(dtype),
        'dt_bias': (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        'D': jnp.ones((h,), dtype),
        'norm': jnp.ones((inner,), dtype),
        'out_proj': {'kernel': normal(keys[3], (inner, d), inner, dtype)},
    }}


def layer_weights(key: jax.Array, dims: Dims, layer, dtype,
                  attention: bool = None) -> dict:
    """One block's weights, in the program's layout.  The two kinds of
    layer are different trees: `attention` says which where `layer` is
    traced (one program then makes every layer of a kind), and is read
    from the pattern where `layer` is a Python int."""
    d, f = dims.hidden, dims.ffn
    if attention is None:
        attention = layer in dims.attention_layers
    keys = jax.random.split(jax.random.fold_in(key, layer + 1), 8)
    mix = (_attention_weights if attention else _mamba_weights)(
        keys[:6], dims, dtype)
    ones = jnp.ones((d,), dtype)
    return {
        'mix_norm': {'scale': ones}, 'ffn_norm': {'scale': ones}, **mix,
        'ffn': {'gate_up': {'kernel': normal(keys[6], (d, 2 * f), d, dtype)},
                'down': {'kernel': normal(keys[7], (f, d), f, dtype)}},
    }


def outer_weights(key: jax.Array, dims: Dims, dtype) -> dict:
    """The table (embedding and head) and the final norm."""
    return {
        'embed': {'embedding': (jax.random.normal(
            jax.random.fold_in(key, 0), (dims.vocab, dims.hidden),
            jnp.float32) * EMBED_STD).astype(dtype)},
        'final_norm': {'scale': jnp.ones((dims.hidden,), dtype)},
    }


# Layers drawn under one `lax.map` in `make_params`: their stacked weights
# are the program's temporaries (1.2 GB at the published sizes, under a
# prefill's 2.5), beside the 6.4 GB it returns.
_LAYERS_A_LOOP = 8


def make_params(key: jax.Array, dims: Dims, dtype) -> dict:
    """The whole tree; call it under `jax.jit` (one program, on the device).
    The layers of a kind are drawn `_LAYERS_A_LOOP` at a time under a
    `lax.map` over their indices (the index is folded into the key, traced
    or not): 40 layers' draws written out one after the other took the
    compiler 115 s (my chip run, PR 43), the loops take it 15.  A loop
    starts when the layers of the one before it have left its stack (the
    barrier), so that one stack is live at a time."""
    tree = outer_weights(key, dims, dtype)
    done = None
    for attention in (True, False):
        index = [i for i in range(dims.layers)
                 if (i in dims.attention_layers) == attention]
        for lo in range(0, len(index), _LAYERS_A_LOOP):
            some = index[lo:lo + _LAYERS_A_LOOP]
            at = jnp.asarray(some)
            if done is not None:
                at, made = jax.lax.optimization_barrier(
                    (at, {i: tree[f'layer_{i}'] for i in done}))
                tree.update({f'layer_{i}': w for i, w in made.items()})
            stacked = jax.lax.map(
                lambda i, attention=attention: layer_weights(
                    key, dims, i, dtype, attention), at)
            for n, i in enumerate(some):
                tree[f'layer_{i}'] = jax.tree.map(lambda a, n=n: a[n],
                                                  stacked)
            done = some
    return tree


def serve_model(dims: Dims, config: dict, dtype):
    """The module `DecodeEngine` is handed."""
    from skypilot_tpu.models.granite_hybrid import (GraniteHybrid,
                                                    GraniteHybridConfig)
    return GraniteHybrid(GraniteHybridConfig(
        vocab_size=dims.vocab, dim=dims.hidden, n_layers=dims.layers,
        attention_layers=dims.attention_layers, n_heads=dims.heads,
        n_kv_heads=dims.kv_heads, head_dim=dims.head_dim, ffn_dim=dims.ffn,
        ssm_heads=dims.ssm_heads, ssm_head_dim=dims.ssm_head_dim,
        ssm_state=dims.ssm_state, ssm_conv=dims.conv, ssm_chunk=dims.chunk,
        embedding_multiplier=dims.embedding_multiplier,
        attention_multiplier=dims.attention_multiplier,
        residual_multiplier=dims.residual_multiplier,
        logits_scaling=dims.logits_scaling, norm_eps=dims.eps,
        max_seq_len=config['serve']['max_seq_len'], dtype=dtype,
        param_dtype=dtype))


def _to_f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def reference(dims: Dims, seed: int, dtype, precision: str = 'float32'):
    """The plain reference over weights made again from the seed."""
    key = seed_key(seed)
    # The layer's index is traced: one program a kind of layer makes every
    # layer's weights (a program of random draws is slow to compile).
    of_kind = {attention: jax.jit(lambda i, attention=attention: _to_f32(
        layer_weights(key, dims, i, dtype, attention)))
        for attention in (True, False)}
    outer = jax.jit(lambda: _to_f32(outer_weights(key, dims, dtype)))
    return granite_hybrid_ref.LayerwiseModel(
        dims, lambda i: of_kind[i in dims.attention_layers](i), outer,
        precision)


def ssm_state_cost(dims: Dims, live_slots: float) -> dict:
    """One call of the state kernel (a Mamba layer's decode step) for
    `live_slots` requests, at the least: each live slot's float32 state
    read once and written once, the columns and rows that come with it
    (`dA`, `dt x` and `D x` a channel, `B` and `C`) and `y`; five
    operations an element of state (two products and a sum for the
    update, a product and a sum for `y`)."""
    state = dims.ssm_inner * dims.ssm_state
    return {
        'bytes': live_slots * (2.0 * dims.head_state_bytes() +
                               4 * (4 * dims.ssm_inner + 2 * dims.ssm_state)),
        'flops': 5.0 * state * live_slots,
    }


def decode_step_cost(dims: Dims, live_slots: float, live_positions: float,
                     itemsize: int = 2) -> dict:
    """One decode step for `live_slots` requests whose contexts sum to
    `live_positions`, at the least: every matrix once (the table once, as
    the head: the embedding is a lookup and not counted again); every live
    slot's recurrent state and taps read and written; K and V of the live
    positions in the attention layers.  Two operations a multiply-add."""
    state = dims.ssm_inner * dims.ssm_state
    return {
        'bytes': dims.matmul_params() * itemsize +
        2.0 * dims.state_bytes_per_slot() * live_slots +
        dims.kv_bytes_per_position(itemsize) * live_positions,
        'flops': 2.0 * dims.matmul_params() * live_slots +
        5.0 * dims.ssm_layers * state * live_slots +
        4.0 * len(dims.attention_layers) * dims.heads * dims.head_dim *
        live_positions,
    }


def train_flops_per_token(dims: Dims, seq_len: int) -> float:
    """Forward and backward, recompute not counted: 6 for each parameter a
    token multiplies, 12 for each score of the attention layers at the
    mean context, and the Mamba layers' state products forward and
    backward.  No training cell runs this family (there is no
    `train_model`); the count is here for the day one does."""
    state = dims.ssm_inner * dims.ssm_state
    return (6.0 * dims.matmul_params() +
            12.0 * len(dims.attention_layers) * dims.heads * dims.head_dim *
            seq_len / 2.0 + 3.0 * 5.0 * dims.ssm_layers * state)
