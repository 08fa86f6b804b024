"""`correct` for a served model: the served tokens against the plain
reference, and the control that has to fail.

For each sampled request the reference runs once over the prompt with its
served tokens.  At each served position the number compared is how far the
served token's logit lies below the reference's best (0 where the served
token is the reference's own choice); the run's number is the widest such
gap.  The control puts a lower-precision reference in the program's place:
at the same positions, the gap of the token that it puts first.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

Sample = Tuple[Sequence[int], Sequence[int]]      # (prompt, served tokens)


def _pack(samples: List[Sample], longest: int, n_out: int):
    """Rows padded to the mix's longest request (one shape for every run,
    so the reference's programs are compiled once), and for each row the
    positions whose logits predict its served tokens."""
    width = -(-longest // 128) * 128
    rows = np.zeros((len(samples), width), np.int32)
    at = np.zeros((len(samples), n_out), np.int32)
    served = np.zeros((len(samples), n_out), np.int32)
    valid = np.zeros((len(samples), n_out), bool)
    for i, (p, t) in enumerate(samples):
        rows[i, :len(p) + len(t)] = list(p) + list(t)
        at[i, :len(t)] = len(p) - 1 + np.arange(len(t))
        served[i, :len(t)] = t
        valid[i, :len(t)] = True
    return rows, at, served, valid


def reference_logits(family, dims, seed: int, dtype, samples: List[Sample],
                     shape: Tuple[int, int], precision: str = 'float32'):
    """Logits [N, T, vocab] at the served positions, with (served, valid).
    `shape` is the mix's (longest prompt + answer, longest answer)."""
    rows, at, served, valid = _pack(samples, *shape)
    model = family.reference(dims, seed, dtype, precision)
    hidden = model.hidden(jnp.asarray(rows))
    picked = jnp.take_along_axis(hidden, jnp.asarray(at)[:, :, None], axis=1)
    return np.asarray(model.logits_at(picked)), served, valid


def gaps_below_best(logits: np.ndarray, tokens: np.ndarray,
                    valid: np.ndarray) -> np.ndarray:
    """best logit - the token's logit, at every valid position."""
    best = logits.max(axis=-1)
    own = np.take_along_axis(logits, tokens[:, :, None], axis=-1)[:, :, 0]
    return (best - own)[valid]


def served_gap(family, dims, seed: int, dtype, samples: List[Sample],
               shape: Tuple[int, int], control: str = None) -> dict:
    """The run's number, and with `control` the same number for the
    reference in that lower precision put in the program's place."""
    logits, served, valid = reference_logits(family, dims, seed, dtype,
                                             samples, shape)
    gaps = gaps_below_best(logits, served, valid)
    out = {'widest_gap': float(gaps.max()), 'mean_gap': float(gaps.mean()),
           'positions': int(valid.sum()),
           'finite': bool(np.isfinite(logits[valid]).all()),
           'off_best': int((gaps > 0).sum())}
    if control:
        low, _, _ = reference_logits(family, dims, seed, dtype, samples,
                                     shape, control)
        gaps = gaps_below_best(logits, low.argmax(axis=-1).astype(np.int32),
                               valid)
        out['control'] = {'widest_gap': float(gaps.max()),
                          'mean_gap': float(gaps.mean()),
                          'off_best': int((gaps > 0).sum())}
    return out
