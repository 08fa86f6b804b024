"""Reduction of a profiler trace to numbers: busy union, idle gaps,
operation self time by name, program durations, exposed collectives.

A trace is first cut down to plain data, `{"device": {plane: {line:
[[name, start_ns, dur_ns], ...]}}, "host": [[name, start_ns, dur_ns], ...]}`,
so that the arithmetic below can be tested on a small recorded trace and
does not depend on the profiler's file format.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Tuple

OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'
_COLLECTIVE = re.compile(
    r'^(all-gather|reduce-scatter|all-reduce|all-to-all|collective-permute)')
_SUFFIX = re.compile(r'[.\-_]?\d+$')

Interval = Tuple[int, int]


def extract(trace_dir: str, host_limit: int = 200000) -> dict:
    """Plain data from the newest `.xplane.pb` under `trace_dir`."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')))
    if not paths:
        raise FileNotFoundError(f'no .xplane.pb under {trace_dir}')
    data = ProfileData.from_file(paths[-1])
    out = {'device': {}, 'host': []}
    for plane in data.planes:
        if plane.name.startswith('/device:TPU:'):
            lines = {}
            for line in plane.lines:
                lines[line.name] = [[ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)]
                                    for ev in line.events]
            out['device'][plane.name] = lines
        elif plane.name.startswith('/host:'):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 20000 and len(out['host']) < host_limit:
                        out['host'].append([f'{line.name}:{ev.name}',
                                            int(ev.start_ns),
                                            int(ev.duration_ns)])
    return out


def head(trace: dict, n: int) -> dict:
    """The first `n` events of every line, and the host events that fall
    among them: small enough to keep beside the tests."""
    device = {plane: {line: events[:n] for line, events in lines.items()}
              for plane, lines in trace['device'].items()}
    lo, hi = window_ns({'device': device})
    host = [ev for ev in trace['host'] if lo <= ev[1] <= hi][:n]
    return {'device': device, 'host': host}


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted intervals."""
    merged: List[List[int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def total(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The part of merged `a` that merged `b` does not cover."""
    out, j = [], 0
    for start, end in a:
        cur = start
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return out


def _spans(events) -> List[Interval]:
    return [(s, s + d) for _, s, d in events]


def self_times(events) -> List[Tuple[str, int, bool]]:
    """(name, self_ns, is_leaf) for each event of one line: its duration
    less what the events nested inside it cover."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [ev[2] for ev in events]
    leaf = [True] * len(events)
    stack: List[int] = []
    for i in order:
        _, start, dur = events[i]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            own[stack[-1]] -= dur
            leaf[stack[-1]] = False
        stack.append(i)
    return [(events[i][0], max(own[i], 0), leaf[i])
            for i in range(len(events))]


def op_group(name: str) -> str:
    """`fusion.123` -> `fusion`: the ledger's breakdown groups by this."""
    name = name.lstrip('%').split(' ')[0]
    return _SUFFIX.sub('', name) or name


def window_ns(trace: dict) -> Interval:
    starts, ends = [], []
    for lines in trace['device'].values():
        for events in lines.values():
            for _, s, d in events:
                starts.append(s)
                ends.append(s + d)
    if not starts:
        return (0, 0)
    return (min(starts), max(ends))


def _busy_lines(lines: dict) -> List:
    """The events whose union is "an operation ran": the operations' line,
    or the programs' where a trace has no line of operations."""
    return lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []


def busy(trace: dict) -> Dict[str, float]:
    """busy_s averaged over the device planes, and window_s."""
    lo, hi = window_ns(trace)
    per_plane = [total(union(_spans(_busy_lines(lines))))
                 for lines in trace['device'].values()]
    n = max(len(per_plane), 1)
    return {'busy_s': sum(per_plane) / n / 1e9, 'window_s': (hi - lo) / 1e9,
            'planes': len(per_plane)}


def idle_gaps(trace: dict, top: int = 10) -> List[List]:
    """The longest gaps with no operation on a device, each named by the
    host event that overlaps it most."""
    lo, hi = window_ns(trace)
    gaps = []
    for lines in trace['device'].values():
        gaps += subtract([(lo, hi)], union(_spans(_busy_lines(lines))))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for start, end in gaps[:top]:
        best, best_overlap = 'host__not_annotated', 0
        for name, s, d in trace['host']:
            overlap = min(end, s + d) - max(start, s)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        out.append([best, (end - start) / 1e9])
    return out


def op_seconds(trace: dict, top: int = 10) -> List[List]:
    """Self time of the device operations by group, over all planes,
    averaged over the planes; the `top` largest."""
    acc: Dict[str, int] = {}
    for lines in trace['device'].values():
        for name, own, _ in self_times(lines.get(OPS_LINE, [])):
            key = op_group(name)
            acc[key] = acc.get(key, 0) + own
    n = max(len(trace['device']), 1)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / n / 1e9] for k, v in ranked]


def module_durations_ms(trace: dict, pattern: str) -> List[float]:
    """Device durations of the programs whose name matches, all planes."""
    rx = re.compile(pattern)
    return [d / 1e6 for lines in trace['device'].values()
            for name, _, d in lines.get(MODULES_LINE, []) if rx.search(name)]


def module_names(trace: dict) -> Dict[str, int]:
    names: Dict[str, int] = {}
    for lines in trace['device'].values():
        for name, _, _ in lines.get(MODULES_LINE, []):
            names[name] = names.get(name, 0) + 1
    return names


def exposed_collective_s(trace: dict) -> float:
    """Seconds, averaged over the planes, in which a collective operation
    was running on a device and no other operation was."""
    per_plane = []
    for lines in trace['device'].values():
        events = lines.get(OPS_LINE, [])
        st = self_times(events)
        coll, other = [], []
        for (name, s, d), (_, _, leaf) in zip(events, st):
            if _COLLECTIVE.match(name.lstrip('%')):
                coll.append((s, s + d))
            elif leaf:
                other.append((s, s + d))
        per_plane.append(total(subtract(union(coll), union(other))))
    return sum(per_plane) / max(len(per_plane), 1) / 1e9
