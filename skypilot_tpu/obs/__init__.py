"""Fleet telemetry plane: durable time-series store + SLO burn-rate
alerting + the query API `skytpu top` renders.

Every other signal in the system is scrape-time and in-memory — the
metrics registry resets with the process, the flight recorder is a
ring, the perf gauges are instantaneous.  This package is the layer
that can answer *trend* questions across the fleet ("is the SLO
burning?", "which pool's p95 moved in the last half hour?"), built
from the controller's existing federated-LB scrapes:

- ``store``    — counter-reset-aware downsampling of successive scrapes
  into a retention-bounded time-series table behind the pluggable
  state backend (sqlite + Postgres through the PR 15 dialect layer);
- ``alerts``   — declarative SLO rules evaluated as multi-window burn
  rates over the store, firing/clearing durable alert rows with
  hysteresis and recording flight-recorder instants;
- ``top``      — the terminal fleet view over the same query API;
- ``goodput``  — the training goodput plane (ISSUE 20): a durable
  wall-clock ledger (productive vs badput categories, summing across
  preemptions/recoveries) plus per-host step-time straggler skew,
  feeding `train_rules` and `skytpu jobs top`.

The fleetsim chaos run ingests sim-time telemetry through the same
code path, so the canonical storm's alert timeline is test-pinned
(tests/test_fleetsim.py).
"""
from skypilot_tpu.obs.alerts import AlertEngine
from skypilot_tpu.obs.alerts import AlertRule
from skypilot_tpu.obs.alerts import BurnWindows
from skypilot_tpu.obs.alerts import default_rules
from skypilot_tpu.obs.alerts import train_rules
from skypilot_tpu.obs.goodput import GoodputLedger
from skypilot_tpu.obs.goodput import PhaseRecorder
from skypilot_tpu.obs.goodput import evaluate_stragglers
from skypilot_tpu.obs.goodput import step_time_skew
from skypilot_tpu.obs.goodput import train_obs_tick
from skypilot_tpu.obs.store import Downsampler
from skypilot_tpu.obs.store import TelemetryStore

__all__ = [
    'AlertEngine',
    'AlertRule',
    'BurnWindows',
    'default_rules',
    'train_rules',
    'GoodputLedger',
    'PhaseRecorder',
    'evaluate_stragglers',
    'step_time_skew',
    'train_obs_tick',
    'Downsampler',
    'TelemetryStore',
]
