"""The readers of the engine's ledger of device time (ISSUE 39), on
hand-made `engine.call` spans whose answers are known: the median, the
subtraction, the window's edges, the call before a host-bound one, the
two clocks, and None where the program records no such span; through
`reduce_metric` as the harness calls them, and the manifest with the four
new entries."""
import types

import pytest

from benchmarks.harness import calls as calls_lib
from benchmarks.harness import manifest, reducers

NEW = ('decode_step_ms.loop', 'prefill_share_pct.loop',
       'prefill_call_ms.backlog', 'host_bound_calls_pct')
OPENED = 100.0              # the recorder's clock at the window's opening
PROFILER = 1234.5           # the profiler's clock less the window's


def prefill(bucket=512, rows=4, held=3):
    return {'kind': 'prefill', 'bucket': bucket, 'rows': rows, 'held': held}


# (start, end, carried, waited_s or None for nine tenths of the interval,
# bound) in seconds from the opening.  A decode call alone is 40 ms.
CALLS = [
    (-1.00, -0.20, [prefill(1024, 32, 32)], None, 'device'),    # before it
    (-0.20, 0.20, [prefill()], None, 'device'),     # 0.16 of its 0.36 inside
    (0.20, 0.24, [], None, 'device'),
    (0.24, 0.282, [], None, 'device'),
    (0.282, 0.32, [], None, 'device'),
    (0.32, 0.72, [prefill()], None, 'device'),      # one prefill: 0.36
    (0.72, 0.76, [], None, 'device'),
    (0.76, 1.26, [prefill(), {'kind': 'chunk', 'bucket': 512, 'rows': 1,
                              'held': 1}], None, 'device'),     # two: 0.46
    (1.26, 1.30, [], None, 'device'),
    (1.30, 3.30, [], 1.99, 'device'),       # a hold that began in its fetch
    (3.30, 3.3005, [], 0.0001, 'host'),     # done long before it was asked
    (3.3005, 3.34, [], None, 'device'),
    (3.34, 3.44, [{'kind': 'export', 'bucket': 0, 'rows': 1, 'held': 1}],
     None, 'device'),                       # cut by the close at 3.4
]
SECONDS = 3.4


def waited(i, start, end, given):
    return given if given is not None else round(
        0.9 * (end - start) + 1e-5 * i, 6)


@pytest.fixture
def recorded():
    """The spans above in the program's recorder, and the client's
    records that place the window."""
    from skypilot_tpu.server import tracing
    tracing.reset_for_tests()
    for i, (start, end, carried, wait, bound) in enumerate(CALLS):
        tracing.record_span(calls_lib.LOOP_RID, 'engine.call',
                            OPENED + start, OPENED + end, seq=i, steps=8,
                            live=4, carried=carried,
                            waited_s=waited(i, start, end, wait), bound=bound)
    records = []
    for j, first in enumerate((0.2, 0.72, 1.26, 3.3, 3.34)):
        tracing.record_instant(f'r{j}', 'engine.first_token',
                               OPENED + first - 0.0008 * (j % 2))
        records.append(types.SimpleNamespace(rid=f'r{j}', first=first))
    records.append(types.SimpleNamespace(rid=None, first=1.0))
    yield records
    tracing.reset_for_tests()


def ctx_of(records, **more):
    return dict({'records': records, 'seconds': SECONDS, 'values': {},
                 'trace': None, 'trace_span': None, 'spans': {},
                 'counters': {}, 'samples': {}}, **more)


def test_the_window_is_placed_on_the_recorders_clock(recorded):
    calls = calls_lib.load(ctx_of(recorded))
    assert [c['seq'] for c in calls] == list(range(len(CALLS)))
    # The median of `ts - first`: two of five read 0.8 ms early.
    assert calls[2]['start'] == pytest.approx(0.20, abs=1e-4)
    assert calls[2]['s'] == pytest.approx(0.04, abs=1e-6)
    # The call before the host-bound one is no device time either.
    assert [c['seq'] for c in calls if not c['device']] == [9, 10]
    # In front of the decode call: the interval less the call alone
    # AFTER it (call 7: less call 8's 40 ms), else the one before it
    # (the last call: less call 11's 39.5 ms).
    assert [round(c['front'], 4) for c in calls] == [
        0.76, 0.36, 0, 0, 0, 0.36, 0, 0.46, 0, 0, 0, 0, 0.0605]


def test_decode_step_is_the_median_of_the_calls_alone(recorded, capsys):
    ctx = ctx_of(recorded, trace_span=(0.2, 0.8),
                 values={'decode_step_ms': 4.95})
    assert reducers.reduce_metric('decode_step_ms.loop', ctx) == \
        pytest.approx(5.0, abs=2e-3)        # 40 ms over 8 steps
    out = capsys.readouterr().out
    assert '6 device-bound calls of the window carried nothing' in out
    # Calls 2, 3, 4 and 6 lie in the traced seconds; the hold does not.
    assert 'over 4 calls; the trace\'s decode_step_ms 4.9500' in out


def test_prefill_share_subtracts_the_decode_call_and_clips(recorded, capsys):
    ctx = ctx_of(recorded, trace_span=(0.2, 0.8),
                 values={'prefill_share_pct': 60.0})
    # In front of the decode call: 0.16 of call 1's 0.36 inside the
    # window, 0.36, 0.46; the export's 0.06 is device time and no prefill.
    # Device time: 0.2 + 0.12 + 0.4 + 0.04 + 0.5 + 0.04 + 0.0395 + 0.06;
    # the hold's 2.0 s and the call behind it are none.
    assert reducers.reduce_metric('prefill_share_pct.loop', ctx) == \
        pytest.approx(100 * 0.98 / 1.3995, abs=0.05)
    out = capsys.readouterr().out
    assert 'chunk+prefill 0.4600' in out and 'export 0.0600' in out
    assert 'prefill 0.5200' in out and 'host-bound 2.0005 s' in out
    # The traced seconds: call 5's 0.36 and 0.04 of call 7's front, of
    # 0.04 + 0.042 + 0.038 + 0.4 + 0.04 + 0.04.
    assert '66.667 % (0.4000 of 0.6000 s)' in out
    assert 'the trace\'s prefill_share_pct 60.000' in out


def test_prefill_call_is_the_interval_less_a_decode_call(recorded, capsys):
    assert reducers.reduce_metric(
        'prefill_call_ms.backlog', ctx_of(recorded)) == pytest.approx(
            360.0, abs=0.05)
    out = capsys.readouterr().out
    # Calls 1 and 5; the one with a chunk beside its prefill is left out.
    assert '2 calls carried prefill programs alone, 1.444 of them' in out
    assert 'b512_n4: 360.000 ms x 2' in out


def test_host_bound_counts_the_calls_found_done(recorded, capsys):
    assert reducers.reduce_metric(
        'host_bound_calls_pct', ctx_of(recorded)) == pytest.approx(
            100.0 / 11)                     # calls 1-11 ended in the window
    out = capsys.readouterr().out
    assert '1 of 11 calls found done' in out
    assert '2000.5 ms, from 1.300 s to 3.300 s' in out and 'seq 10' in out
    assert 'host-bound call 0.100 ms' in out


def test_no_span_reads_as_nothing(recorded):
    """The parent of the PR that brought the span, under its benchmark
    files: every reader gives None and raises nothing."""
    from skypilot_tpu.server import tracing
    tracing.reset_for_tests()
    for name in NEW:
        assert reducers.reduce_metric(name, ctx_of(recorded)) is None
    # Spans, but no traced request to place the window by.
    tracing.record_span(calls_lib.LOOP_RID, 'engine.call', 1.0, 2.0, seq=0,
                        steps=8, live=1, carried=[], waited_s=0.9,
                        bound='device')
    for name in NEW:
        assert reducers.reduce_metric(name, ctx_of([])) is None


def test_the_two_clocks_are_paired_by_the_waits(recorded, capsys):
    calls = calls_lib.load(ctx_of(recorded))
    ns = lambda s: int(round((s + PROFILER) * 1e9))     # noqa: E731
    fetches = [(ns(c['end'] - c['waited_s']) - 1500, ns(c['end']))
               for c in calls[2:9]]
    paired = calls_lib.clock_offset(calls, fetches)
    assert paired['first'] == 2 and paired['pairs'] == 7
    assert paired['offset_s'] == pytest.approx(PROFILER, abs=1e-6)
    assert paired['spread_s'] < 1e-6
    # Waits that belong to no run of calls pair with nothing.
    assert calls_lib.clock_offset(
        calls, [(ns(1.0), ns(1.0 + 0.0123 * k)) for k in (1, 2, 3)]) is None
    # In a traced run: a program 0.25 ms shorter than its call's interval
    # that ended 0.3 ms before the fetch returned.
    modules = [['jit_decode(123)', ns(c['start'] - 0.00005),
                int(round((c['s'] - 0.00025) * 1e9))] for c in calls[2:9]]
    trace = {'device': {'/device:TPU:0': {'XLA Modules': modules}},
             'host': [['loop/1:engine.loop.fetch', a, b - a]
                      for a, b in fetches]}
    ctx = ctx_of(recorded, trace=trace, trace_span=(0.2, 0.8))
    assert reducers.reduce_metric('decode_step_ms.loop', ctx) == \
        pytest.approx(5.0, abs=2e-3)
    out = capsys.readouterr().out
    assert '7 engine.loop.fetch events of the trace end where calls 2..' in out
    assert 'the window\'s 1234.500000 s' in out
    assert 'over 4 calls of the traced seconds' in out
    assert 'median 0.2500 ms' in out and 'median 0.3000 ms' in out


def test_the_manifest_holds_the_four_entries_at_its_end():
    man = manifest.manifest()
    assert manifest.problems(man) == []
    assert tuple(m['name'] for m in man['per_layer'][-4:]) == NEW
    serving = [w['name'] for w in man['workloads']
               if w['traffic'] != 'pretrain-4k']
    for m in man['per_layer'][-4:]:
        assert m['source'] == 'program_span' and m['better'] == 'lower'
        wanted = serving[1:] if m['name'] == 'prefill_call_ms.backlog' \
            else serving
        assert m['workloads'] == wanted
        assert m['moves'] == ('out_tokens_per_s' if m['name'] ==
                              'prefill_call_ms.backlog' else 'tpot_p50_ms')
        assert manifest.reducer_spec(m['name'])['reducer'] == 'reduce'
