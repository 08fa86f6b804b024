"""The least time a step's bytes and operations allow on a device.  The
counts themselves are the family's (`decode_step_cost`,
`train_flops_per_token` in `benchmarks/families/<architecture>.py`)."""
from __future__ import annotations


def least_seconds(cost: dict, peaks: dict) -> dict:
    by_bytes = cost['bytes'] / peaks['hbm_bytes_per_s']
    by_flops = cost['flops'] / peaks['bf16_flops_per_s']
    return {'seconds': max(by_bytes, by_flops),
            'bound': 'memory' if by_bytes >= by_flops else 'compute'}
