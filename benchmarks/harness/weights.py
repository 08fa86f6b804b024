"""What every family's seeded weights share: the key, the draw, the names.

The benchmark makes the weights and hands them to the program, so that the
plain reference can make the same ones again from the seed, one layer at a
time, without taking anything from the program.  The trees themselves are
the families' (`benchmarks/families/<architecture>.py`).
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative whole number (the driver's seeds pass
    2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def normal(key, shape, fan_in, dtype):
    """normal(0, 1/fan_in), drawn in float32 and then cast."""
    w = jax.random.normal(key, shape, jnp.float32) * (1.0 / math.sqrt(fan_in))
    return w.astype(dtype)


def flat(tree, prefix='') -> Dict[str, object]:
    """The leaves of a tree of dicts by their path, `a/b/c`."""
    out = {}
    for k in sorted(tree):
        v = tree[k]
        name = f'{prefix}/{k}' if prefix else k
        out.update(flat(v, name) if isinstance(v, dict) else {name: v})
    return out
