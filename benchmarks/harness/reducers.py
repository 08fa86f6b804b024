"""The readers of per-layer metrics, found by the name in a metric's file.

`benchmarks/layer_metrics/<metric>.json` holds `{"reducer": <name>,
"args": {...}}`.  A reducer is a function here, or `reduce(ctx, **args)` in
a `<metric>.py` beside the file where a new one is needed.  It takes what
this run's window left behind (`ctx`) and returns a number, or None where it
finds nothing to read; the harness then leaves the metric out.

`ctx` keys: `samples` (name -> list, the client's readings), `spans` (name ->
list of ms, the flight recorder's), `counters` (name -> after - before),
`trace` (harness.trace's plain data or None), `trace_span` (seconds from the
opening), `records`, `memory_peak_bytes`, `family` (the configuration's
module of `benchmarks/families/`), `dims`, `config`, `mix`, `peaks`, `chips`,
`values` (metrics already reduced in this run), `seconds`.
"""
from __future__ import annotations

import importlib.util
import os
import statistics
from typing import Optional

from benchmarks.harness import costs, manifest, trace as trace_lib


def percentile(values, q: float) -> Optional[float]:
    """The q-th percentile (0-100), linear between order statistics."""
    vals = sorted(values)
    if not vals:
        return None
    pos = (len(vals) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def sample_percentile(ctx, series: str, q: float):
    return percentile(ctx['samples'].get(series, []), q)


def span_percentile(ctx, span: str, q: float):
    return percentile(ctx['spans'].get(span, []), q)


def counter_delta(ctx, counter: str):
    return ctx['counters'].get(counter)


def memory_peak_gb(ctx):
    peak = ctx.get('memory_peak_bytes')
    return None if peak is None else peak / 1e9


def trace_module_ms(ctx, pattern: str, divide_by: Optional[str] = None):
    if not ctx.get('trace'):
        return None
    durs = trace_lib.module_durations_ms(ctx['trace'], pattern)
    if not durs:
        return None
    div = ctx['config']['serve'][divide_by] if divide_by else 1
    return statistics.median(durs) / div


def trace_module_share_pct(ctx, pattern: str):
    if not ctx.get('trace'):
        return None
    b = trace_lib.busy(ctx['trace'])
    if not b['busy_s']:
        return None
    durs = trace_lib.module_durations_ms(ctx['trace'], pattern)
    return 100.0 * sum(durs) / 1e3 / b['planes'] / b['busy_s']


def trace_idle_pct(ctx):
    if not ctx.get('trace'):
        return None
    b = trace_lib.busy(ctx['trace'])
    if not b['window_s']:
        return None
    return 100.0 * (1.0 - b['busy_s'] / b['window_s'])


def trace_exposed_collectives_pct(ctx):
    if not ctx.get('trace'):
        return None
    b = trace_lib.busy(ctx['trace'])
    if not b['window_s']:
        return None
    return 100.0 * trace_lib.exposed_collective_s(ctx['trace']) / b['window_s']


def live_load(records, span) -> dict:
    """Time-averaged live slots and live cache positions over `span`
    (seconds from the opening), from the client's own stamps: a request
    decodes from its first token to its last, and its context grows evenly
    from its prompt to prompt + tokens."""
    t0, t1 = span
    slots = positions = 0.0
    for r in records:
        if r.first is None or r.last is None or r.last <= r.first:
            continue
        a, b = max(r.first, t0), min(r.last, t1)
        if b <= a:
            continue
        grow = len(r.tokens) / (r.last - r.first)
        mid = (a + b) / 2.0 - r.first
        slots += (b - a)
        positions += (b - a) * (len(r.plan.prompt) + grow * mid)
    return {'slots': slots / (t1 - t0), 'positions': positions / (t1 - t0)}


def slot_occupancy_pct(ctx):
    load = live_load(ctx['records'], (0.0, ctx['seconds']))
    return 100.0 * load['slots'] / ctx['config']['serve']['n_slots']


def decode_roofline_pct(ctx, step_metric: str):
    step_ms = ctx['values'].get(step_metric)
    if not step_ms or not ctx.get('trace_span') or not ctx.get('peaks'):
        return None
    load = live_load(ctx['records'], ctx['trace_span'])
    least = costs.least_seconds(
        ctx['family'].decode_step_cost(ctx['dims'], load['slots'],
                                       load['positions']),
        ctx['peaks'])
    print(f'decode_roofline_pct: bound by {least["bound"]}; live slots '
          f'{load["slots"]:.2f}, live positions {load["positions"]:.0f}, '
          f'least {least["seconds"] * 1e3:.4f} ms of {step_ms:.4f} ms')
    return 100.0 * least['seconds'] * 1e3 / step_ms


def train_mfu_pct(ctx):
    rate = ctx['samples'].get('train_tokens_per_s')
    if not rate or not ctx.get('peaks'):
        return None
    flops = ctx['family'].train_flops_per_token(ctx['dims'],
                                                ctx['mix']['seq_len'])
    return 100.0 * flops * rate[0] / (
        ctx['chips'] * ctx['peaks']['bf16_flops_per_s'])


def reduce_metric(name: str, ctx) -> Optional[float]:
    spec = manifest.reducer_spec(name)
    own = os.path.join(manifest.reducer_dir(name), f'{name}.py')
    if os.path.exists(own):
        mod_spec = importlib.util.spec_from_file_location(
            f'layer_metric_{name.replace(".", "_")}', own)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        fn = mod.reduce
    else:
        fn = globals()[spec['reducer']]
    return fn(ctx, **spec.get('args', {}))
