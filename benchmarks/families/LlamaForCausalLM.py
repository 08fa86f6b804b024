"""The Llama family: dense pre-norm blocks, grouped-query attention, SwiGLU,
an untied head (`skypilot_tpu.models.llama.Llama`; the Yi checkpoints).

Sizes, seeded weights, the program's modules, the plain reference and the
step's cost, as `benchmarks/families/__init__.py` asks of a family.  Every
leaf is normal(0, 1/fan_in) (the embedding normal(0, 1), the norm scales
1), drawn in float32 from a key folded from (seed, layer, leaf) and then
cast to the type the configuration is run in, so that the reference can
make the same weights again from the seed, one layer at a time, without
taking anything from the program.  The tree has the layout of `Llama`'s
parameters.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from benchmarks.harness.weights import normal, seed_key
from benchmarks.reference import llama_ref, train_ref

_LEAVES = ('q', 'k', 'v', 'o', 'gate', 'up', 'down')

# `--rehearse`: tiny widths, control flow only.
REHEARSAL = {
    'hidden_size': 64,
    'num_hidden_layers': 2,
    'num_attention_heads': 4,
    'num_key_value_heads': 2,
    'head_dim': 16,
    'intermediate_size': 128,
    'vocab_size': 256,
}


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of one configuration file, by the published names."""
    hidden: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    rope_theta: float
    eps: float

    @classmethod
    def from_config(cls, cfg: dict) -> 'Dims':
        if cfg.get('tie_word_embeddings'):
            raise ValueError('tied embeddings are not handled here')
        return cls(hidden=cfg['hidden_size'], layers=cfg['num_hidden_layers'],
                   heads=cfg['num_attention_heads'],
                   kv_heads=cfg['num_key_value_heads'],
                   head_dim=cfg['head_dim'], ffn=cfg['intermediate_size'],
                   vocab=cfg['vocab_size'], rope_theta=cfg['rope_theta'],
                   eps=cfg['rms_norm_eps'])

    def layer_params(self) -> int:
        d, hd = self.hidden, self.head_dim
        return (2 * d * self.heads * hd + 2 * d * self.kv_heads * hd +
                3 * d * self.ffn + 2 * d)

    def num_params(self) -> int:
        return (self.layers * self.layer_params() +
                2 * self.vocab * self.hidden + self.hidden)

    def matmul_params(self) -> int:
        """Parameters that a token multiplies: all but the embedding
        table (a lookup) and the norm scales."""
        return (self.layers * (self.layer_params() - 2 * self.hidden) +
                self.vocab * self.hidden)

    def kv_bytes_per_position(self, itemsize: int = 2) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim * itemsize


def dims(config: dict) -> Dims:
    return Dims.from_config(config)


def layer_weights(key: jax.Array, dims: Dims, layer: int, dtype) -> dict:
    """One block's weights, in the program's layout."""
    d, h, kv, hd, f = (dims.hidden, dims.heads, dims.kv_heads, dims.head_dim,
                       dims.ffn)
    keys = dict(zip(_LEAVES, jax.random.split(
        jax.random.fold_in(key, layer + 1), len(_LEAVES))))
    ones = jnp.ones((d,), dtype)
    return {
        'attn_norm': {'scale': ones},
        'attn': {
            'q_proj': {'kernel': normal(keys['q'], (d, h, hd), d, dtype)},
            'k_proj': {'kernel': normal(keys['k'], (d, kv, hd), d, dtype)},
            'v_proj': {'kernel': normal(keys['v'], (d, kv, hd), d, dtype)},
            'o_proj': {'kernel': normal(keys['o'], (h, hd, d), h * hd,
                                        dtype)},
        },
        'mlp_norm': {'scale': ones},
        'mlp': {
            'gate_proj': {'kernel': normal(keys['gate'], (d, f), d, dtype)},
            'up_proj': {'kernel': normal(keys['up'], (d, f), d, dtype)},
            'down_proj': {'kernel': normal(keys['down'], (f, d), f, dtype)},
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


def _program_config(dims: Dims, **kw):
    from skypilot_tpu.models.llama import LlamaConfig
    lcfg = LlamaConfig(
        vocab_size=dims.vocab, dim=dims.hidden, n_layers=dims.layers,
        n_heads=dims.heads, n_kv_heads=dims.kv_heads, ffn_dim=dims.ffn,
        rope_theta=dims.rope_theta, norm_eps=dims.eps, tie_embeddings=False,
        **kw)
    if lcfg.head_dim != dims.head_dim:
        raise SystemExit('head_dim is not hidden_size / heads: the program '
                         'cannot express this configuration')
    return lcfg


def serve_model(dims: Dims, config: dict, dtype):
    """The module `DecodeEngine` is handed."""
    from skypilot_tpu.models.llama import Llama
    return Llama(_program_config(
        dims, max_seq_len=config['serve']['max_seq_len'], dtype=dtype,
        param_dtype=dtype))


def train_model(dims: Dims, config: dict, mesh, seq_len: int):
    """The module `Trainer` is handed."""
    from skypilot_tpu.models.llama import Llama
    return Llama(_program_config(
        dims, max_seq_len=seq_len,
        attention_impl=config['train']['attention_impl']), mesh)


class Reference(llama_ref.LayerwiseModel):
    """The plain reference over weights made again from the seed."""

    def __init__(self, dims: Dims, seed: int, dtype, precision: str):
        key = seed_key(seed)
        # The layer's index is traced: one program makes every layer's
        # weights.
        layer = jax.jit(lambda i: llama_ref.to_f32(
            layer_weights(key, dims, i, dtype)))
        outer = jax.jit(lambda: llama_ref.to_f32(
            outer_weights(key, dims, dtype)))
        super().__init__(dims, layer, outer, precision)
        self._seed, self._precision = seed, precision

    def first_steps(self, batches, opt: dict, devices) -> dict:
        return train_ref.first_steps(
            self.dims, self._seed, batches, opt, devices, self._precision,
            layer_weights=layer_weights, outer_weights=outer_weights)


def reference(dims: Dims, seed: int, dtype, precision: str = 'float32'):
    return Reference(dims, seed, dtype, precision)


def decode_step_cost(dims: Dims, live_slots: float, live_positions: float,
                     itemsize: int = 2) -> dict:
    """One decode step for `live_slots` requests whose contexts sum to
    `live_positions`: the weights once, K and V of the live positions once
    (not the `max_seq_len` the program may read), two operations for each
    multiply-add."""
    n = dims.matmul_params()
    return {
        'bytes': n * itemsize +
        dims.kv_bytes_per_position(itemsize) * live_positions,
        'flops': 2.0 * n * live_slots +
        4.0 * dims.layers * dims.heads * dims.head_dim * live_positions,
    }


def train_flops_per_token(dims: Dims, seq_len: int) -> float:
    """Forward and backward, recompute not counted: 6 for each parameter a
    token multiplies, and 12 L d s for attention's two products."""
    return (6.0 * dims.matmul_params() +
            12.0 * dims.layers * dims.hidden * seq_len)
