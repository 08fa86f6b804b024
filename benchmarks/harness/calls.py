"""The engine's own ledger of device time, call by call, and the window
on its clock.

The program records one `engine.call` span a fetched decode call under
the flight recorder's request `engine-loop`
(`skypilot_tpu/server/tracing.py SPAN_HELP`): from the previous fetch's
return to this one's, with the programs dispatched in between
(`carried`), the fetch's wait (`waited_s`) and `bound`: `device` where the
fetch waited, so that the span is the device's time for the call and what
it carried, `host` where the call was done before the host asked.  The
readers of `decode_step_ms.loop`, `prefill_share_pct.loop`,
`prefill_call_ms.backlog` and `host_bound_calls_pct` share what is here.

The spans are on the recorder's clock, the window on the client's.  A
measured request's `engine.first_token` instant is stamped at the fetch
that carried the token and the client's `first` when its thread read it,
at most a poll (1 ms) and a few emits later: the median over (up to 64
of) the window's requests of `ts - first` is the wall time of the
window's opening, good to a millisecond or two of a 30 s window.  A call
belongs to a window if its interval overlaps it, and seconds are clipped
at the window's edges.  A program without the span (the parent of the PR
that brought it) gives None everywhere.

A hold of the loop thread most often begins inside a fetch, where the
thread waits with the interpreter's lock released: that fetch returns when
the lock comes back, reads `device` and is as long as the hold, and it is
the call behind it, dispatched before the hold and long done, that reads
`host`.  So a device-bound call directly before a host-bound one is no
device time either: `load` says which calls are (`device`), and the
readers count the others together.

The device runs what a call carried first and the decode call last.  What
the decode call took is read off the nearest call that is device time and
carried nothing: the one AFTER it for preference (it holds the call's own
slots, those just admitted among them, 8 steps on), else the one before
it.  The window's median would do in a cell whose slots are always full;
in `chat-steady`, where a step is 4.4-5.5 ms by what is live, it read the
traced seconds' prefill share 0.5 and 2.4 points above the trace's own
where the call after reads 0.0 and 0.5 (my chip runs, PR 39).
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Tuple

from benchmarks.harness.reducers import percentile

LOOP_RID = 'engine-loop'
PLACED_BY = 64              # requests that place the window's opening
_KEY = 'engine_calls'       # where `load` keeps its answer in ctx


def opening_wall(records) -> Optional[float]:
    """The wall time at which the client's clock read 0."""
    from skypilot_tpu.server import tracing
    timed = [r for r in records if r.rid and r.first is not None]
    step = max(1, len(timed) // PLACED_BY)
    offsets = []
    for r in timed[::step]:
        for ev in tracing.events_for(r.rid):
            if ev['name'] == 'engine.first_token':
                offsets.append(ev['ts'] - r.first)
                break
    return statistics.median(offsets) if offsets else None


def load(ctx) -> Optional[List[dict]]:
    """The retained `engine.call` spans in order, times in seconds from
    the window's opening: `start`, `end`, `s` (the interval), the span's
    attributes, `device`: whether the interval is device time (`bound` =
    `device`, and not the call before a host-bound one), and `front`: the
    seconds of it in front of its decode call (0 where it carried
    nothing; None where no call alone says what a decode call takes).
    None where there is no span or no request to place the opening by."""
    if _KEY in ctx:
        return ctx[_KEY]
    from skypilot_tpu.server import tracing
    events = [e for e in tracing.events_for(LOOP_RID)
              if e['name'] == 'engine.call']
    opened = opening_wall(ctx.get('records') or ()) if events else None
    calls = None
    if events and opened is not None:
        calls = [dict(e['attrs'], start=e['ts'] - opened,
                      end=e['ts'] - opened + e['dur_ms'] / 1e3,
                      s=e['dur_ms'] / 1e3) for e in events]
        for c, after in zip(calls, calls[1:] + [{'bound': 'device'}]):
            c['device'] = c['bound'] == after['bound'] == 'device'
        _fronts(calls)
        if calls[0]['seq'] > 0 and calls[0]['start'] > 0.0:
            print(f'engine.call: the ring kept calls from '
                  f'{calls[0]["start"]:.2f} s on only (seq '
                  f'{calls[0]["seq"]}): part of the window was evicted')
    ctx[_KEY] = calls
    return calls


def _fronts(calls: List[dict]) -> None:
    """`front` of every call: its interval less what the nearest call
    alone took, the one after it for preference."""
    after, seen = [], None
    for c in reversed(calls):
        after.append(seen)
        if c['device'] and not c['carried']:
            seen = c['s']
    seen = None
    for c, nxt in zip(calls, reversed(after)):
        took = nxt if nxt is not None else seen
        c['front'] = (0.0 if not c['carried'] else
                      None if took is None else c['s'] - min(took, c['s']))
        if c['device'] and not c['carried']:
            seen = c['s']


def overlapping(calls: List[dict], lo: float, hi: float) -> List[dict]:
    return [c for c in calls if c['end'] > lo and c['start'] < hi]


def clipped(a: float, b: float, lo: float, hi: float) -> float:
    return max(0.0, min(b, hi) - max(a, lo))


def alone(calls: List[dict]) -> List[dict]:
    """The calls whose interval is one decode call's device time."""
    return [c for c in calls if c['device'] and not c['carried']]


def kinds(call: dict) -> str:
    return '+'.join(sorted({p['kind'] for p in call['carried']}))


def split(calls: List[dict], lo: float, hi: float) -> Dict[str, object]:
    """The seconds of [lo, hi) by what the calls that are device time say:
    `device_s` all of them, `by_kind` the part in front of the decode
    call by the kinds carried (`prefill`, `chunk`, `chunk+prefill`, ...),
    `prefill_s` those of the calls that carried a prefill or a chunk,
    `host_s` the intervals that were no device time."""
    by_kind: Dict[str, float] = {}
    device_s = prefill_s = host_s = 0.0
    for c in overlapping(calls, lo, hi):
        if not c['device']:
            host_s += clipped(c['start'], c['end'], lo, hi)
            continue
        device_s += clipped(c['start'], c['end'], lo, hi)
        if not c['front']:
            continue
        front = clipped(c['start'], c['start'] + c['front'], lo, hi)
        by_kind[kinds(c)] = by_kind.get(kinds(c), 0.0) + front
        if any(p['kind'] in ('prefill', 'chunk') for p in c['carried']):
            prefill_s += front
    return {'device_s': device_s, 'by_kind': by_kind,
            'prefill_s': prefill_s, 'host_s': host_s}


# ----- the two clocks ----------------------------------------------------------
def clock_offset(calls: List[dict], fetches: List[Tuple[int, int]],
                 agree_s: float = 1e-4) -> Optional[dict]:
    """Pair the trace's `engine.loop.fetch` phase events (start_ns,
    end_ns on the profiler's clock, in order) with the calls: every call
    ends where the fetch phase it closed ends, and that phase lasts what
    the call's `waited_s` says.  Calls a few ms apart wait alike, but not
    to the microsecond: the pairing is the shift of the one sequence
    along the other at which the waits' median disagreement is least,
    and there is none where that is above `agree_s` (a trace drops host
    events under 20 us, so a fetch that found its call done can be
    missing, and the pairs behind it are then one off).  Returns the
    profiler's seconds less the window's at the paired ends (`offset_s`:
    the median; `spread_s`: the middle half's width, which one offset
    between two clocks keeps at microseconds), the first paired call's
    index and the disagreement."""
    waits = [c['waited_s'] for c in calls]
    durs = [(b - a) / 1e9 for a, b in fetches]
    if not durs or len(waits) < len(durs):
        return None
    miss, shift = min(
        (statistics.median(abs(waits[j + shift] - d)
                           for j, d in enumerate(durs)), shift)
        for shift in range(len(waits) - len(durs) + 1))
    if miss > agree_s:
        return None
    offsets = sorted(end / 1e9 - calls[j + shift]['end']
                     for j, (_, end) in enumerate(fetches))
    return {'offset_s': statistics.median(offsets),
            'spread_s': (percentile(offsets, 75) - percentile(offsets, 25)),
            'first': shift, 'pairs': len(durs), 'wait_miss_s': miss}
