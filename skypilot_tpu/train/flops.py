"""Model-FLOP accounting shared by bench.py and the trainer's MFU
gauges, so the benchmark and the live skytpu_train_mfu_percent series
report the same quantity.

MFU here is *model* FLOPs utilization: achieved model FLOPs/s (6N dense
fwd+bwd plus the causal-attention term) over the chip's peak bf16
throughput.  Hardware-neutral — the reference's published v6e numbers
reduce to the same measure (see bench.py's baseline derivation).
"""
from __future__ import annotations

from typing import Optional

PEAK_BF16_TFLOPS = {
    'v5litepod': 197.0,
    'v5e': 197.0,
    'v6e': 918.0,
    'v5p': 459.0,
    'v4': 275.0,
    # Nominal, so the accounting runs in the CPU tests; never a device
    # number.  Goes with ROADMAP A0(b).
    'cpu': 1.0,
}


def chip_kind() -> str:
    """Normalized device-kind name of the first local device.  A TPU
    this table does not know is an error, not a 1 TFLOP/s 'cpu'."""
    import jax
    dev = jax.devices()[0]
    kind = dev.device_kind.lower().replace(' ', '')
    for name in PEAK_BF16_TFLOPS:
        if name in kind:
            return name
    if 'lite' in kind:      # 'TPU v5 lite'
        return 'v5litepod'
    if dev.platform == 'tpu':
        raise ValueError(
            f'unknown TPU device_kind {dev.device_kind!r}: add its peak '
            f'to PEAK_BF16_TFLOPS')
    return 'cpu'


def train_flops_per_token(n_params: int, n_layers: int, dim: int,
                          seq_len: int) -> float:
    """fwd+bwd model FLOPs per trained token: 6N dense + causal
    attention term."""
    return 6 * n_params + 6 * n_layers * seq_len * dim


def estimate_mfu(tokens_per_s: float, n_params: int, n_layers: int,
                 dim: int, seq_len: int, n_chips: int = 1,
                 kind: Optional[str] = None) -> float:
    """Achieved model TFLOP/s as % of the slice's peak bf16 TFLOP/s.

    Returns 0.0 on unrecognized hardware rather than a bogus ratio."""
    kind = kind or chip_kind()
    peak = PEAK_BF16_TFLOPS.get(kind)
    if not peak or tokens_per_s <= 0:
        return 0.0
    achieved_tflops = (tokens_per_s *
                       train_flops_per_token(n_params, n_layers, dim,
                                             seq_len) / 1e12)
    return 100.0 * achieved_tflops / (peak * max(1, n_chips))


def train_hbm_bytes_per_token(n_params: int, tokens_per_step: int,
                              param_bytes: int = 2,
                              opt_state_bytes: int = 8) -> float:
    """Modeled HBM traffic per trained token: the trainer twin of the
    decode cost model's bytes/token gauge (perf/cost_model.py).

    One optimizer step streams the weight tree through HBM a fixed
    number of times — forward read + backward read (2x params), the
    gradient write (1x), and the Adam moment read-modify-write (2x the
    f32 m/v pair) — all amortized over the step's token count.
    Activation traffic is recompute-dominated under remat and omitted;
    this is a floor, matching the decode model's roofline role."""
    if tokens_per_step <= 0:
        return 0.0
    step_bytes = n_params * (3 * param_bytes + 2 * opt_state_bytes)
    return step_bytes / tokens_per_step


def train_arith_intensity(n_params: int, n_layers: int, dim: int,
                          seq_len: int, tokens_per_step: int,
                          param_bytes: int = 2,
                          opt_state_bytes: int = 8) -> float:
    """FLOPs per modeled HBM byte for one train step."""
    bytes_per_token = train_hbm_bytes_per_token(
        n_params, tokens_per_step, param_bytes, opt_state_bytes)
    if bytes_per_token <= 0:
        return 0.0
    return train_flops_per_token(n_params, n_layers, dim,
                                 seq_len) / bytes_per_token
