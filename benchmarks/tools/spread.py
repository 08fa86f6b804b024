"""Medians and spreads of a set of runs, as the contract defines a spread:
the distance between the first and third quartile (`statistics.quantiles`,
n=4) as a share of the median.

    python3 benchmarks/tools/spread.py chiprun_out/c1_A*.txt [-- chiprun_out/c1_B*.txt]

Each file holds one run's output; its last line is the result.  With two
sets (split by `--`) it prints each set's spread, the wider of the two, and
how far the second median lies from the first.
"""
from __future__ import annotations

import json
import statistics
import sys


def last_line(path: str) -> dict:
    with open(path, encoding='utf-8') as f:
        lines = [ln for ln in f.read().splitlines() if ln.startswith('{')]
    return json.loads(lines[-1])


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def summarize(paths) -> dict:
    runs = [last_line(p) for p in paths]
    out = {}
    for name in runs[0]['metrics']:
        vals = [r['metrics'][name]['value'] for r in runs]
        out[name] = {'median': statistics.median(vals),
                     'spread': spread(vals) if len(vals) > 1 else None,
                     'values': vals}
    out['_runs'] = {'n': len(runs),
                    'correct': sum(bool(r['correct']) for r in runs),
                    'attempted': [r['attempted'] for r in runs],
                    'seeds': [r.get('seed') for r in runs]}
    return out


def main(argv) -> int:
    sets, cur = [], []
    for a in argv:
        if a == '--':
            sets.append(cur)
            cur = []
        else:
            cur.append(a)
    sets.append(cur)
    sums = [summarize(s) for s in sets if s]
    for i, s in enumerate(sums):
        print(f'set {i + 1}: {json.dumps(s["_runs"])}')
        for name, v in s.items():
            if name != '_runs':
                print(f'  {name}: median {v["median"]:.6g} spread '
                      f'{v["spread"] if v["spread"] is None else round(100 * v["spread"], 3)}% '
                      f'values {[round(x, 4) for x in v["values"]]}')
    if len(sums) == 2:
        for name in sums[0]:
            if name == '_runs':
                continue
            a, b = sums[0][name], sums[1][name]
            wider = max(a['spread'] or 0, b['spread'] or 0)
            print(f'both: {name}: wider spread {100 * wider:.3f}%, second '
                  f'median {100 * (b["median"] / a["median"] - 1):+.3f}% of '
                  f'the first, five times the wider {500 * wider:.2f}%')
    return 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
