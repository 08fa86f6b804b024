"""Due-time accounting and percentiles of the open-loop generator, on a
scripted engine and a scripted clock."""
import queue

import pytest

from benchmarks.harness import loadgen, reducers, serve, traffic
from benchmarks.harness.traffic import Planned


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


class FakeEngine:
    """Every request gets its first token `first_s` after it was sent and
    one more every `gap_s`; the client is stalled for `stall` once."""

    def __init__(self, clock, first_s=0.05, gap_s=0.01):
        self.clock, self.first_s, self.gap_s = clock, first_s, gap_s
        self.live = []

    def submit(self, prompt, max_new, rid):
        handle = type('H', (), {})()
        handle.out = queue.Queue()
        self.live.append([handle, self.clock() + self.first_s, max_new, 0])
        return handle

    def pump(self):
        for item in self.live:
            handle, nxt, max_new, sent = item
            while sent < max_new and nxt <= self.clock():
                handle.out.put(7)
                sent += 1
                nxt += self.gap_s
            if sent == max_new and item[3] != max_new:
                handle.out.put(None)
            item[1], item[3] = nxt, sent


def drive(plan, seconds, stall_at=None, stall_s=0.0, drain=True):
    clock = Clock()
    eng = FakeEngine(clock)
    state = {'stalled': False}

    def sleep(dt):
        clock.sleep(dt)
        if stall_at is not None and not state['stalled'] and \
                clock() - 100.0 >= stall_at:
            state['stalled'] = True
            clock.sleep(stall_s)        # the client's thread loses the CPU
        eng.pump()
    return loadgen.drive(eng.submit, plan, seconds=seconds, traced=True,
                         drain=drain, drain_limit_s=5.0, clock=clock,
                         sleep=sleep)


def test_ttft_is_timed_from_due_not_from_sent():
    plan = [Planned(-0.5, [1], 4)] + [Planned(0.1 * i, [1], 4)
                                      for i in range(10)]
    out = drive(plan, seconds=1.0, stall_at=0.6, stall_s=0.3)
    recs = serve.measured({'kind': 'open_loop'}, out)
    assert len(recs) == 10                      # the lead-in's is not counted
    late = [r.late_ms for r in recs]
    assert max(late) == pytest.approx(300.0, abs=5.0)   # due 0.2: sent 0.5
    worst = max(recs, key=lambda r: r.late_ms)
    assert worst.ttft_ms == pytest.approx(worst.late_ms + 50.0, abs=5.0)
    on_time = min(recs, key=lambda r: r.late_ms)
    assert on_time.ttft_ms == pytest.approx(50.0, abs=5.0)
    assert all(r.done and len(r.tokens) == 4 for r in recs)
    assert all(r.tpot_ms == pytest.approx(10.0, abs=2.0) for r in recs)


def test_backlog_counts_tokens_inside_the_window_only():
    plan = [Planned(-0.2, [1], 50) for _ in range(4)]
    out = drive(plan, seconds=0.2, drain=False)
    # 4 requests, one token each 10 ms from 50 ms after -0.2 s: the window
    # [0, 0.2) holds about 20 of each request's tokens.
    assert 4 * 18 <= out.tokens_in_window <= 4 * 22
    # 4 requests, 100 tokens a second each, whatever the edges cut off.
    assert out.token_rate == pytest.approx(400.0, rel=0.02)
    assert serve.measured({'kind': 'backlog'}, out) == []   # none ended


@pytest.mark.parametrize('q,want', [(0, 1.0), (50, 2.5), (95, 3.85),
                                    (100, 4.0)])
def test_percentile(q, want):
    assert reducers.percentile([4.0, 1.0, 3.0, 2.0], q) == pytest.approx(want)
    assert reducers.percentile([], q) is None


def test_every_seed_offers_the_same_work_in_another_order():
    mix = {'kind': 'open_loop', 'lead_in_s': 1.0, 'tail_s': 1.0,
           'arrivals': {'process': 'poisson', 'rate_per_s': 20.0},
           'prompt_tokens': {'dist': 'lognormal', 'median': 64, 'sigma': 0.6,
                             'min': 8, 'max': 128},
           'output_tokens': {'dist': 'lognormal', 'median': 16, 'sigma': 0.5,
                             'min': 4, 'max': 64}}
    a = traffic.plan_requests(mix, 1, 5.0, 1000)
    b = traffic.plan_requests(mix, 2**31 + 5, 5.0, 1000)
    assert sorted(len(p.prompt) for p in a) == sorted(len(p.prompt) for p in b)
    assert sorted(p.max_new for p in a) == sorted(p.max_new for p in b)
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in b]
    assert a[0].prompt != b[0].prompt
    assert a[-1].due == pytest.approx(b[-1].due)
    assert len(a) == 140 and -1.0 < a[0].due < 0
    again = traffic.plan_requests(mix, 1, 5.0, 1000)
    assert [p.prompt for p in again] == [p.prompt for p in a]
