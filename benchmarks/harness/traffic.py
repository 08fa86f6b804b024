"""One general traffic generator, driven by a mix's data file and the seed.

Every seed gets the same multiset of lengths and of gaps between arrivals
(the distribution's quantiles on an even grid), in another order, and other
token ids: so the work offered in a run does not change with the seed, only
its order does.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Planned:
    """One request of a plan: when it is due (seconds from the window's
    opening; negative in the lead-in) and what it asks for."""
    due: float
    prompt: List[int]
    max_new: int


BLOCK = 24


def _quantile_grid(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _permute(vals: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """The seed's order: the sorted values are dealt round-robin into blocks
    of about BLOCK, each block and the blocks' order are shuffled.  Every
    stretch of a run so holds an even sample of the whole distribution, and
    a window's count and work hardly change with the seed."""
    n_blocks = max(1, len(vals) // BLOCK)
    blocks = [rng.permutation(np.sort(vals)[b::n_blocks])
              for b in range(n_blocks)]
    return np.concatenate([blocks[i] for i in rng.permutation(n_blocks)])


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """`n` whole lengths from a `{"dist": ...}` entry, seed-permuted."""
    dist = spec['dist']
    if dist == 'fixed':
        return np.full(n, int(spec['value']), np.int64)
    if dist != 'lognormal':
        raise ValueError(f'unknown length distribution {dist!r}')
    z = np.array([_NORMAL.inv_cdf(q) for q in _quantile_grid(n)])
    vals = np.exp(math.log(spec['median']) + spec['sigma'] * z)
    vals = np.clip(np.rint(vals), spec['min'], spec['max']).astype(np.int64)
    return _permute(vals, rng)


def gaps(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """`n` gaps between arrivals with mean 1/rate, seed-permuted."""
    rate = float(spec['rate_per_s'])
    q = _quantile_grid(n)
    process = spec['process']
    if process == 'poisson':
        vals = -np.log1p(-q)
    elif process == 'gamma':
        from scipy import stats  # only a mix that asks for it needs scipy
        shape = 1.0 / float(spec['cv']) ** 2
        vals = stats.gamma.ppf(q, shape)
    else:
        raise ValueError(f'unknown arrival process {process!r}')
    vals = vals / vals.mean() / rate
    return _permute(vals, rng)


def plan_requests(mix: dict, seed: int, seconds: float,
                  vocab: int) -> List[Planned]:
    """The whole run's requests for a serving mix (`open_loop`, `backlog`)."""
    rng = np.random.default_rng(int(seed))
    lead_in = float(mix['lead_in_s'])
    if mix['kind'] == 'open_loop':
        # A little past the window's end, so that its last seconds are as
        # loaded as its first.
        span = lead_in + seconds + float(mix.get('tail_s', 2.0))
        n = int(math.ceil(span * mix['arrivals']['rate_per_s']))
        due = np.cumsum(gaps(mix['arrivals'], n, rng)) - lead_in
    elif mix['kind'] == 'backlog':
        n = int(mix['backlog_requests'])
        due = np.full(n, -lead_in)
    else:
        raise ValueError(f'{mix["kind"]!r} is not a serving mix')
    prompts = lengths(mix['prompt_tokens'], n, rng)
    outputs = lengths(mix['output_tokens'], n, rng)
    return [Planned(float(due[i]),
                    rng.integers(0, vocab, int(prompts[i])).tolist(),
                    int(outputs[i])) for i in range(n)]


def train_batches(mix: dict, seed: int, vocab: int, rows: int):
    """Endless iterator of [rows, seq_len] int32 batches, every row drawn
    anew from the seed."""
    rng = np.random.default_rng(int(seed))
    seq = int(mix['seq_len'])
    while True:
        yield rng.integers(0, vocab, (rows, seq), dtype=np.int32)
