"""The trace reduction on a small recorded trace (the head of a v5e run of
`yi-coder-1.5b.fsdp4-4k`, cut to plain data by `harness.trace.extract` and
`head`) and on a hand-made one whose answers are known.  The recorded head
holds no collective, so the exposed share is held to the hand-made one."""
import json
import os

import pytest

from benchmarks.harness import trace as T

HAND = {'device': {'/device:TPU:0': {
    'XLA Ops': [['while.1', 0, 100], ['fusion.1', 0, 40],
                ['all-gather-start.1', 40, 5], ['fusion.2', 45, 30],
                ['all-gather-done.1', 75, 20], ['copy.3', 120, 10]],
    'XLA Modules': [['jit_step(1)', 0, 100], ['jit_other(2)', 120, 10]]}},
    'host': [['loop:wait', 100, 20], ['loop:short', 101, 2]]}


def test_busy_union_and_idle_gaps():
    b = T.busy(HAND)
    assert b['busy_s'] == pytest.approx(110e-9)
    assert b['window_s'] == pytest.approx(130e-9)
    assert T.idle_gaps(HAND) == [['loop:wait', pytest.approx(20e-9)]]


def test_operation_time_is_self_time_by_group():
    ops = dict(T.op_seconds(HAND))
    assert ops['fusion'] == pytest.approx(70e-9)
    assert ops['while'] == pytest.approx(5e-9)      # 100 less its children
    assert ops['all-gather-done'] == pytest.approx(20e-9)
    assert T.module_durations_ms(HAND, 'jit_step') == [pytest.approx(1e-4)]


def test_exposed_collectives_are_those_no_other_operation_covers():
    # start (40-45) and done (75-95) run while no fusion does; the while
    # that contains them is not a leaf and covers nothing.
    assert T.exposed_collective_s(HAND) == pytest.approx(25e-9)


def test_interval_arithmetic():
    assert T.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert T.subtract([(0, 10)], [(2, 3), (5, 20)]) == [(0, 2), (3, 5)]
    assert T.subtract([(0, 10)], []) == [(0, 10)]


RECORDED = os.path.join(os.path.dirname(__file__), 'data',
                        'trace_small.json')


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason='no recorded trace in this checkout')
def test_recorded_trace_reduces():
    with open(RECORDED, encoding='utf-8') as f:
        rec = json.load(f)
    trace, want = rec['trace'], rec['expected']
    b = T.busy(trace)
    assert 0 < b['busy_s'] <= b['window_s']
    assert b['busy_s'] == pytest.approx(want['busy_s'], rel=1e-9)
    assert T.exposed_collective_s(trace) == pytest.approx(
        want['exposed_collective_s'], rel=1e-9)
    top = T.op_seconds(trace, top=3)
    assert [k for k, _ in top] == want['top_ops']
    total_self = sum(v for _, v in T.op_seconds(trace, top=10**6))
    assert total_self <= b['busy_s'] * 1.0001       # self times do not overlap
