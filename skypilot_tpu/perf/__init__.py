"""Device-level performance observability (PR 17).

The quantities that actually bound decode throughput (HBM bytes per
token, arithmetic intensity, compile behavior) are invisible to the
host-side request plumbing (histograms, spans).  This package supplies
the measurement substrate:

- `cost_model`: the program's one module of cost arithmetic.  A table
  of chip peaks, the trainer's model-FLOP count behind
  skytpu_train_mfu_percent, and a STATIC per-dispatch cost model for
  the decode engine (FLOPs + HBM bytes from the model config, batch
  occupancy and KV geometry, including the page dtype).  Computed
  host-side on the engine loop thread: zero added device syncs,
  enforced by tests.
- `compile_telemetry`: jax.monitoring hooks feeding
  skytpu_engine_xla_compile_{total,seconds} plus the runtime recompile
  sentinel: any compile after engine warmup records a flight-recorder
  instant event (`perf.recompile`) with the traced shapes, and
  SKYTPU_STRICT_RECOMPILE=1 turns it into a hard failure (the runtime
  twin of the static recompile-hazard rule).
- `profiler`: on-demand jax.profiler capture behind /debug/profile
  with bounded on-disk retention.
"""
from skypilot_tpu.perf import compile_telemetry
from skypilot_tpu.perf import cost_model
from skypilot_tpu.perf import profiler

__all__ = ['compile_telemetry', 'cost_model', 'profiler']
