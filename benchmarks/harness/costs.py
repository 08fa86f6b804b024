"""What the algorithm needs, from shapes: the yardstick's own arithmetic."""
from __future__ import annotations

from benchmarks.harness.weights import Dims


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


def least_seconds(cost: dict, peaks: dict) -> dict:
    by_bytes = cost['bytes'] / peaks['hbm_bytes_per_s']
    by_flops = cost['flops'] / peaks['bf16_flops_per_s']
    return {'seconds': max(by_bytes, by_flops),
            'bound': 'memory' if by_bytes >= by_flops else 'compute'}


def train_flops_per_token(dims: Dims, seq_len: int) -> float:
    """Forward and backward, recompute not counted: 6 for each parameter a
    token multiplies, and 12 L d s for attention's two products."""
    return (6.0 * dims.matmul_params() +
            12.0 * dims.layers * dims.hidden * seq_len)
