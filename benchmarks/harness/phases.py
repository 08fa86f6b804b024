"""The program's own phases in a profiler trace, and the device's idle
time told by them.

The program wraps what its loop threads do in `tracing.phase(<name>)`
(`skypilot_tpu/server/tracing.py`), which during a profiler session is a
host event on the clock of the device lines.  `harness.trace.extract`
keeps host events as `["<thread line>:<event name>", start_ns, dur_ns]`,
drops those under 20 us and stops at 200,000; the readers here find the
phases by name, return None where a trace holds none (a program from
before the phases), and print how many host events the trace kept.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from benchmarks.harness import trace as trace_lib

PHASES = (
    'engine.loop.dispatch', 'engine.loop.fetch', 'engine.loop.emit',
    'engine.loop.admit', 'engine.loop.idle',
    'train.feed', 'train.dispatch', 'train.fetch', 'train.export',
    'train.checkpoint',
)
HOST_LIMIT = 200000         # harness.trace.extract's default host_limit


def phase_events(trace: dict) -> Dict[str, List[trace_lib.Interval]]:
    """{phase name: [(start_ns, end_ns), ...]} of the program's phases
    among the trace's host events."""
    out: Dict[str, List[trace_lib.Interval]] = {}
    for entry, start, dur in trace['host']:
        name = entry.rpartition(':')[2]     # a phase's name has no colon
        if name in PHASES:
            out.setdefault(name, []).append((start, start + dur))
    return out


def note_host_events(metric: str, trace: dict) -> None:
    kept = len(trace['host'])
    capped = ' (the cap: later host lines are missing)' \
        if kept >= HOST_LIMIT else ''
    print(f'{metric}: the trace kept {kept} host events{capped}')


def idle_intervals(trace: dict) -> List[List[trace_lib.Interval]]:
    """Per device plane, the parts of the trace's window in which no
    operation ran on that device."""
    window = [trace_lib.window_ns(trace)]
    out = []
    for lines in trace['device'].values():
        events = (lines.get(trace_lib.OPS_LINE) or
                  lines.get(trace_lib.MODULES_LINE) or [])
        out.append(trace_lib.subtract(window, trace_lib.union(
            (s, s + d) for _, s, d in events)))
    return out


def overlap_ns(a: List[trace_lib.Interval],
               b: List[trace_lib.Interval]) -> int:
    """Nanoseconds of merged `a` that merged `b` covers."""
    return trace_lib.total(a) - trace_lib.total(trace_lib.subtract(a, b))


def idle_by_phase(trace: dict) -> Optional[dict]:
    """The device's idle nanoseconds (summed over the planes), the part
    of them some phase overlaps, and that part by phase.  None where the
    trace holds no phase or the device was never idle."""
    phases = {name: trace_lib.union(spans)
              for name, spans in phase_events(trace).items()}
    if not phases:
        return None
    every = trace_lib.union(s for spans in phases.values() for s in spans)
    idle = attributed = 0
    by_phase = dict.fromkeys(phases, 0)
    for gaps in idle_intervals(trace):
        idle += trace_lib.total(gaps)
        attributed += overlap_ns(gaps, every)
        for name, spans in phases.items():
            by_phase[name] += overlap_ns(gaps, spans)
    if not idle:
        return None
    return {'idle_ns': idle, 'attributed_ns': attributed,
            'by_phase': by_phase}


def idle_attributed_pct(metric: str, ctx: dict) -> Optional[float]:
    """The share of the device's idle time in which the program was in
    one of its phases: the reader of both `idle_attributed_pct.*`."""
    trace = ctx.get('trace')
    if not trace:
        return None
    note_host_events(metric, trace)
    told = idle_by_phase(trace)
    if told is None:
        return None
    split = ', '.join(f'{name} {ns / 1e9:.6f}' for name, ns in sorted(
        told['by_phase'].items(), key=lambda kv: -kv[1]))
    print(f'{metric}: device idle {told["idle_ns"] / 1e9:.6f} s over '
          f'{len(trace["device"])} plane(s), in a phase '
          f'{told["attributed_ns"] / 1e9:.6f} s; idle seconds by phase: '
          f'{split}')
    return 100.0 * told['attributed_ns'] / told['idle_ns']
