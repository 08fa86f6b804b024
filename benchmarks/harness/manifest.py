"""`BENCHMARK.json` and the data files it names: loading, and the checks of
the contract that can be made without a chip."""
from __future__ import annotations

import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, 'benchmarks')
_NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
_UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')
SOURCES = ('device_trace', 'program_span', 'program_counter', 'host_clock')


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts), encoding='utf-8') as f:
        return json.load(f)


def manifest() -> dict:
    return load_json(ROOT, 'BENCHMARK.json')


def cell(man: dict, name: str) -> dict:
    for w in man['workloads']:
        if w['name'] == name:
            return w
    raise SystemExit(f'no workload {name!r} in BENCHMARK.json; it has '
                     f'{[w["name"] for w in man["workloads"]]}')


def config_of(man: dict, name: str) -> dict:
    for c in man['configs']:
        if c['name'] == name:
            return load_json(ROOT, c['file'])
    raise SystemExit(f'no configuration {name!r} in BENCHMARK.json')


def traffic_of(name: str) -> dict:
    return load_json(BENCH_DIR, 'traffic', f'{name}.json')


def peaks_for(device_kind: str) -> dict:
    table = load_json(BENCH_DIR, 'peaks.json')['devices']
    if device_kind not in table:
        raise SystemExit(f'device kind {device_kind!r} is not in '
                         f'benchmarks/peaks.json: no default is taken')
    return table[device_kind]


def metrics_of(man: dict, workload: str, group: str) -> list:
    """The metrics of `end_to_end` or `per_layer` that `workload` reports.
    A per-layer metric without `workloads` belongs to every cell that
    reports the end-to-end metric it moves."""
    e2e = [m for m in man['end_to_end']
           if workload in m.get('workloads', [workload])]
    if group == 'end_to_end':
        return e2e
    names = {m['name'] for m in e2e}
    return [m for m in man['per_layer']
            if (workload in m['workloads'] if 'workloads' in m
                else m['moves'] in names)]


def reducer_dir(metric: str) -> str:
    """`layer_metrics/` or `end_to_end/`: where the metric's reader is."""
    for group in ('layer_metrics', 'end_to_end'):
        if os.path.exists(os.path.join(BENCH_DIR, group, f'{metric}.json')):
            return os.path.join(BENCH_DIR, group)
    raise SystemExit(f'metric {metric!r} has no reader file')


def reducer_spec(metric: str) -> dict:
    return load_json(reducer_dir(metric), f'{metric}.json')


def problems(man: dict) -> list:
    """What the contract would refuse, as far as a file can show it."""
    bad = []
    def names(items):
        return [i['name'] for i in items]
    for group in ('configs', 'workloads', 'end_to_end', 'per_layer'):
        ns = names(man[group])
        bad += [f'{group}: duplicate {n}' for n in set(ns) if ns.count(n) > 1]
        bad += [f'{group}: bad name {n!r}' for n in ns if not _NAME.match(n)]
    cfgs = set(names(man['configs']))
    cells = {w['name']: w for w in man['workloads']}
    for w in man['workloads']:
        if w['config'] not in cfgs:
            bad.append(f'{w["name"]}: unknown config')
        if w['chips'] not in (1, 4) or not 1 <= len(w['why']) <= 200:
            bad.append(f'{w["name"]}: chips or why')
        if not os.path.exists(os.path.join(BENCH_DIR, 'traffic',
                                           w['traffic'] + '.json')):
            bad.append(f'{w["name"]}: no traffic file')
    if sum(w['chips'] == 4 for w in man['workloads']) > max(
            1, len(man['workloads']) // 4):
        bad.append('too many four-chip cells')
    for c in man['configs']:
        if not os.path.exists(os.path.join(ROOT, c['file'])):
            bad.append(f'{c["name"]}: no file')
        if not any(w['config'] == c['name'] for w in man['workloads']):
            bad.append(f'{c["name"]}: used by no cell')
    e2e = {m['name']: m for m in man['end_to_end']}
    if 'setup_s' not in e2e:
        bad.append('no setup_s')
    for m in man['end_to_end'] + man['per_layer']:
        if not _UNIT.match(m['unit']) or m['better'] not in ('lower', 'higher'):
            bad.append(f'{m["name"]}: unit or better')
        if m['source'] not in SOURCES:
            bad.append(f'{m["name"]}: source')
        for w in m.get('workloads', []):
            if w not in cells:
                bad.append(f'{m["name"]}: unknown workload {w}')
    for m in man['end_to_end']:
        if not 0 < m['bound'] <= 0.1 or m['source'] not in (
                'host_clock', 'device_trace'):
            bad.append(f'{m["name"]}: bound or source')
    for m in man['per_layer']:
        if m['moves'] not in e2e:
            bad.append(f'{m["name"]}: moves nothing end to end')
            continue
        moved = e2e[m['moves']].get('workloads', list(cells))
        for w in m.get('workloads', moved):
            if w not in moved:
                bad.append(f'{m["name"]}: {w} does not report {m["moves"]}')
        if not os.path.exists(os.path.join(BENCH_DIR, 'layer_metrics',
                                           m['name'] + '.json')):
            bad.append(f'{m["name"]}: no reader file')
    for w in cells:
        if len(metrics_of(man, w, 'end_to_end')) < 2:
            bad.append(f'{w}: fewer than two end-to-end metrics')
        if not metrics_of(man, w, 'per_layer'):
            bad.append(f'{w}: no per-layer metric')
    return bad
