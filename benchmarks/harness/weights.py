"""Seeded weights for the Llama-shaped configurations, made by the benchmark.

The benchmark makes the weights and hands them to the program, so that the
plain reference can make the same ones again from the seed, one layer at a
time, without taking anything from the program.  Every leaf is
normal(0, 1/fan_in) (the embedding normal(0, 1), the norm scales 1), drawn in
float32 from a key folded from (seed, layer, leaf) and then cast to the type
the configuration is run in.  The tree has the layout of
`skypilot_tpu.models.llama.Llama`'s parameters.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

_LEAVES = ('q', 'k', 'v', 'o', 'gate', 'up', 'down')


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


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative whole number (the driver's seeds pass
    2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _normal(key, shape, fan_in, dtype):
    w = jax.random.normal(key, shape, jnp.float32) * (1.0 / math.sqrt(fan_in))
    return w.astype(dtype)


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
            'q_proj': {'kernel': _normal(keys['q'], (d, h, hd), d, dtype)},
            'k_proj': {'kernel': _normal(keys['k'], (d, kv, hd), d, dtype)},
            'v_proj': {'kernel': _normal(keys['v'], (d, kv, hd), d, dtype)},
            'o_proj': {'kernel': _normal(keys['o'], (h, hd, d), h * hd,
                                         dtype)},
        },
        'mlp_norm': {'scale': ones},
        'mlp': {
            'gate_proj': {'kernel': _normal(keys['gate'], (d, f), d, dtype)},
            'up_proj': {'kernel': _normal(keys['up'], (d, f), d, dtype)},
            'down_proj': {'kernel': _normal(keys['down'], (f, d), f, dtype)},
        },
    }


def outer_weights(key: jax.Array, dims: Dims, dtype) -> dict:
    """Embedding, final norm and output head."""
    k_embed, k_head = jax.random.split(jax.random.fold_in(key, 0))
    return {
        'embed': {'embedding': jax.random.normal(
            k_embed, (dims.vocab, dims.hidden), jnp.float32).astype(dtype)},
        'final_norm': {'scale': jnp.ones((dims.hidden,), dtype)},
        'lm_head': {'kernel': _normal(k_head, (dims.hidden, dims.vocab),
                                      dims.hidden, dtype)},
    }


def make_params(key: jax.Array, dims: Dims, dtype) -> dict:
    """The whole tree; call it under `jax.jit` (one program, on the device)."""
    tree = outer_weights(key, dims, dtype)
    for i in range(dims.layers):
        tree[f'layer_{i}'] = layer_weights(key, dims, i, dtype)
    return tree
