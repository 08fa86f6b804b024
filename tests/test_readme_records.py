"""README figures have one source: `PERF_LEDGER.jsonl`, quoted by metric
name with "(ledger, PR n)" beside it.

The claim forms below are the ones the README once used for figures from
a benchmark that ran presets no cell runs, a tiny model on the CPU and a
simulator.  No record in the tree can back them, so the README may not
carry a number in any of them.
"""
import os
import re

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CLAIM_FORMS = {
    'headline': [r'[0-9.]+% MFU', r'[0-9.]+ out-tok/s',
                 r'TPOT [0-9.]+ ms'],
    'saturated_ttft': [r'saturated TTFT [0-9.]+ ms'],
    'recorder_overhead': [r'recorder overhead [0-9.]+%'],
    'prefix_hit_ttft': [r'prefix-hit TTFT [0-9.]+ ms'],
    'slo_ramp': [r'[0-9.]+ ms \(SLO-aware\) vs [0-9.]+ ms \(QPS-only\)'],
    'disagg': [r'\$[0-9.]+/1k SLO-met \(disagg[^)]*\) vs '
               r'\$[0-9.]+/1k \(monolithic\)'],
    'speculative': [r'[0-9.]+ out-tok/s \(speculative',
                    r'speculative TPOT [0-9.]+ ms',
                    r'draft acceptance [0-9.]+ \(repetitive\) vs '
                    r'[0-9.]+ \(random\)'],
    'fleet': [r'sustains [0-9]+ req/s at SLO with [0-9]+ virtual '
              r'replicas across [0-9]+ pools; recovers from a [0-9]+% '
              r'preemption storm in [0-9.]+ s'],
    'goodput': [r'lands at [0-9.]+% goodput \([0-9.]+ s downtime, '
                r'skew [0-9.]+ on host[0-9]+\)',
                r'measured at [0-9.]+ µs/step \([0-9.]+% of step time\)',
                r'to within 1% \([0-9.]+% measured\)'],
}


@pytest.fixture(scope='module')
def readme():
    with open(os.path.join(_ROOT, 'README.md'), encoding='utf-8') as f:
        # Collapse whitespace so markdown line wrapping cannot split a
        # claim ("350.9\nout-tok/s" still matches).
        return ' '.join(f.read().split())


@pytest.mark.parametrize('form', sorted(_CLAIM_FORMS))
def test_readme_makes_no_claim_without_a_record(readme, form):
    found = [m for pattern in _CLAIM_FORMS[form]
             for m in re.findall(pattern, readme)]
    assert not found, (
        f'README.md carries {found}: no record in the tree backs a '
        f'figure of this form; quote PERF_LEDGER.jsonl by metric name '
        f'with its origin ("ledger, PR n") instead')
