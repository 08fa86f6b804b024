"""The engine's ledger of device time by call (ISSUE 39).

Every fetched decode call leaves one `engine.call` span under the
request id `engine-loop`: from the previous fetch's return (or its own
first program's dispatch, where the device was idle until then) to its
fetch's return, with the programs that rode in front of it (`carried`)
and whether the fetch waited (`bound`).  The spans of a busy engine tile
its time, a request's `engine.prefill` and `engine.first_token` name
their call, and three families carry the spans' sums.
"""
import time

import pytest

from skypilot_tpu.server import metrics
from skypilot_tpu.server import tracing

from test_phases import _engine, _family, tiny_engine_model  # noqa: F401


@pytest.fixture(autouse=True)
def _reset():
    metrics.reset_for_tests()
    tracing.reset_for_tests()
    yield
    metrics.reset_for_tests()
    tracing.reset_for_tests()


def _calls():
    from skypilot_tpu.inference.engine import LOOP_REQUEST_ID
    return [e for e in tracing.events_for(LOOP_REQUEST_ID)
            if e['name'] == 'engine.call']


def _end(e):
    return e['ts'] + e['dur_ms'] / 1e3


def _counted(calls):
    """The spans that are device time: the fetch waited, and so did the
    next one."""
    return [c for c, after in zip(calls, calls[1:] + [None])
            if c['attrs']['bound'] == 'device' and
            (after is None or after['attrs']['bound'] == 'device')]


def _span(rid, name):
    found = [e for e in tracing.events_for(rid) if e['name'] == name]
    assert len(found) == 1, (rid, name, found)
    return found[0]


def _serve(engine, path, tag, prompts, max_new=6):
    """`prompts` through `engine` by `path`, after a warm-up that
    compiles every program they use; returns the traced requests."""
    step = getattr(engine, path)
    warm = [engine.submit(p, 2) for p in prompts]
    for _ in range(2000):
        step()
        if all(r.finished_at is not None for r in warm):
            break
    engine.drain()
    engine._flush_loop_seconds(final=True)
    metrics.reset_for_tests()
    tracing.clear_for_tests()
    reqs = [engine.submit(p, max_new + i, request_id=f'{tag}-{i}')
            for i, p in enumerate(prompts)]
    for _ in range(2000):
        step()
        if all(r.finished_at is not None for r in reqs):
            break
    assert all(r.finished_at is not None for r in reqs)
    engine.drain()
    engine._flush_loop_seconds(final=True)
    return reqs


PROMPTS = [[1, 2, 3], [4, 5, 6, 7], list(range(1, 13)), [8, 9],
           list(range(2, 12)), [3, 1]]


def test_engine_call_is_registered_and_is_no_phase():
    assert 'engine.call' in tracing.SPAN_HELP
    # Phases stay out of the ring; a call is one event, and in it.
    assert not 'engine.call'.startswith('engine.loop.')
    help_ = metrics.help_registry()
    for family in ('skytpu_engine_device_seconds_total',
                   'skytpu_engine_decode_call_seconds',
                   'skytpu_engine_calls_total'):
        assert family in help_, family
    bounds = metrics.buckets_for('skytpu_engine_decode_call_seconds')
    assert bounds[0] <= 0.01 and bounds[-1] >= 1.0
    # A step's tenth shows below 150 ms.
    assert sum(0.01 <= b <= 0.15 for b in bounds) >= 10


def test_calls_of_a_busy_engine_tile_its_time(tiny_engine_model):
    """From the first dispatch to the last fetch, every call's span
    opens where the one before it closed."""
    engine = _engine(tiny_engine_model, steps_per_call=2,
                     prefill_buckets=(8, 16))
    reqs = _serve(engine, 'step_pipelined', 'tile', PROMPTS)
    calls = _calls()
    assert len(calls) >= 6
    seqs = [c['attrs']['seq'] for c in calls]
    assert seqs == list(range(seqs[0], seqs[0] + len(calls)))
    for prev, cur in zip(calls, calls[1:]):
        assert cur['ts'] == pytest.approx(_end(prev), abs=3e-6)
    # The engine was idle before: the first interval opens at the
    # dispatch of its first program, the first prefill group's.
    first = min(_span(r.request_id, 'engine.prefill')['ts'] for r in reqs)
    assert calls[0]['ts'] == pytest.approx(first, abs=3e-6)
    assert calls[0]['attrs']['carried'][0]['kind'] == 'prefill'
    whole = _end(calls[-1]) - calls[0]['ts']
    assert sum(c['dur_ms'] for c in calls) / 1e3 == pytest.approx(
        whole, abs=1e-4)
    for c in calls:
        a = c['attrs']
        assert a['steps'] == 2 and 1 <= a['live'] <= 2
        assert a['bound'] in ('device', 'host') and a['waited_s'] >= 0.0
        assert a['waited_s'] * 1e3 <= c['dur_ms'] + 1e-2


@pytest.mark.parametrize('path', ['step', 'step_pipelined'])
def test_carried_lists_the_prefill_groups_that_name_the_call(
        tiny_engine_model, path):
    """Each call's `carried` holds exactly the prefill groups whose
    requests' engine.prefill spans name its seq, and a first token names
    the call whose fetch carried it; on both engine paths."""
    engine = _engine(tiny_engine_model, steps_per_call=2,
                     prefill_buckets=(8, 16))
    reqs = _serve(engine, path, path, PROMPTS)
    calls = {c['attrs']['seq']: c for c in _calls()}
    named = {}                  # seq -> {(bucket, group's dispatch): rows}
    for r in reqs:
        p = _span(r.request_id, 'engine.prefill')
        groups = named.setdefault(p['attrs']['call'], {})
        key = (p['attrs']['bucket'], p['ts'])
        groups[key] = groups.get(key, 0) + 1
        assert p['attrs']['group'] >= groups[key]
        call = calls[p['attrs']['call']]
        # The prefill went out before the call's fetch (on the
        # pipelined path behind the call then in flight, so before the
        # interval in which the DEVICE ran it opens) ...
        assert p['ts'] <= _end(call)
        if path == 'step':
            assert call['ts'] - 3e-6 <= p['ts']
        # ... and its first token came with that call's fetch.
        tok = _span(r.request_id, 'engine.first_token')
        assert tok['attrs']['call'] == p['attrs']['call']
        assert _end(call) <= tok['ts'] + 3e-6
        later = calls.get(p['attrs']['call'] + 1)
        if later is not None:
            assert tok['ts'] <= _end(later)
    assert sum(len(g) for g in named.values()) >= 3     # several groups
    for seq, call in calls.items():
        carried = call['attrs']['carried']
        assert all(p['kind'] == 'prefill' for p in carried)
        assert sorted((p['bucket'], p['held']) for p in carried) == sorted(
            (bucket, n) for (bucket, _), n in named.get(seq, {}).items())
        for p in carried:
            assert p['rows'] == 1 << (p['held'] - 1).bit_length()
    if path == 'step':
        # Nothing is ever in flight at a dispatch: a span opens at its
        # own first program, after the last one closed.
        ordered = [calls[s] for s in sorted(calls)]
        for prev, cur in zip(ordered, ordered[1:]):
            assert cur['ts'] >= _end(prev) - 3e-6


def test_a_chunked_prompt_rides_as_chunks(tiny_engine_model):
    """A prompt beyond the largest bucket goes out a chunk an iteration:
    the calls carry `chunk` programs, the last of them at the bucket of
    what was left."""
    engine = _engine(tiny_engine_model, steps_per_call=2)
    _serve(engine, 'step_pipelined', 'chunked',
           [[1, 2, 3], list(range(1, 21))], max_new=8)     # 20 > 8
    carried = [p for c in _calls() for p in c['attrs']['carried']]
    chunks = [p for p in carried if p['kind'] == 'chunk']
    assert [p['bucket'] for p in chunks] == [8, 8, 8]
    assert [p['kind'] for p in carried].count('prefill') == 1


def test_the_families_sum_to_the_spans(tiny_engine_model):
    """device_seconds_total over its programs is the device-bound
    intervals (a call before a host-bound one is none), calls_total the
    spans, the histogram the device-bound calls that carried nothing."""
    engine = _engine(tiny_engine_model, steps_per_call=32,
                     prefill_buckets=(8, 16))        # calls of 5-10 ms
    _serve(engine, 'step_pipelined', 'sums', PROMPTS, max_new=60)
    calls = _calls()
    assert _family('skytpu_engine_calls_total') == len(calls)
    assert _family('skytpu_engine_calls_total', bound='device') == \
        sum(c['attrs']['bound'] == 'device' for c in calls)
    device = _counted(calls)
    assert device
    assert _family('skytpu_engine_device_seconds_total') == pytest.approx(
        sum(c['dur_ms'] for c in device) / 1e3, abs=1e-4)
    alone = [c for c in device if not c['attrs']['carried']]
    assert alone
    assert _family('skytpu_engine_decode_call_seconds_count') == len(alone)
    assert _family('skytpu_engine_decode_call_seconds_sum') == \
        pytest.approx(sum(c['dur_ms'] for c in alone) / 1e3, abs=1e-4)
    # A call that carried a prefill gives decode at most what the latest
    # call alone took, and the prefill the rest.
    riders = [c for c in device if c['attrs']['carried']]
    prefill = _family('skytpu_engine_device_seconds_total',
                      program='prefill')
    decode = _family('skytpu_engine_device_seconds_total', program='decode')
    assert 0.0 <= prefill <= sum(c['dur_ms'] for c in riders) / 1e3 + 1e-4
    assert decode >= sum(c['dur_ms'] for c in alone) / 1e3 - 1e-4
    assert prefill + decode == pytest.approx(
        sum(c['dur_ms'] for c in device) / 1e3, abs=1e-4)
    # Nothing is left unflushed.
    assert engine._device_s == {} and not any(engine._calls_n.values())
    assert engine._call_pending is None


def test_with_the_ring_off_the_families_still_move(tiny_engine_model,
                                                    monkeypatch):
    monkeypatch.setenv(tracing.RING_SIZE_ENV, '0')
    tracing.reset_for_tests()
    assert not tracing.enabled()
    engine = _engine(tiny_engine_model, steps_per_call=32)   # calls of ms
    _serve(engine, 'step_pipelined', 'off', PROMPTS[:3], max_new=60)
    assert _calls() == [] and tracing.recent_requests() == []
    assert _family('skytpu_engine_calls_total') >= 3
    assert _family('skytpu_engine_device_seconds_total') > 0.0


def test_a_fetch_that_finds_its_call_done_reads_host_bound(
        tiny_engine_model):
    """The loop thread held past a call in flight: the fetch returns at
    once, the span says `host`, and its seconds are no device time."""
    engine = _engine(tiny_engine_model, steps_per_call=32)
    warm = engine.submit([1, 2, 3], 4)
    while warm.finished_at is None:
        engine.step_pipelined()
    engine.drain()
    engine._flush_loop_seconds(final=True)
    metrics.reset_for_tests()
    tracing.clear_for_tests()
    req = engine.submit([1, 2, 3], 100)
    for _ in range(3):
        engine.step_pipelined()
    assert engine._inflight is not None
    held = engine._inflight[3][0]
    time.sleep(0.25)                      # the call ends meanwhile
    while req.finished_at is None:
        engine.step_pipelined()
    engine.drain()
    engine._flush_loop_seconds(final=True)
    ordered = _calls()
    calls = {c['attrs']['seq']: c for c in ordered}
    late = calls[held]
    assert late['attrs']['bound'] == 'host'
    assert late['attrs']['waited_s'] < 0.001 and late['dur_ms'] >= 250.0
    assert _family('skytpu_engine_calls_total', bound='host') >= 1
    # The calls around it waited for their 32 steps.
    assert sum(c['attrs']['bound'] == 'device' for c in ordered) >= 1
    # Neither it nor the call before it is device time.
    counted = _counted(ordered)
    assert late not in counted and calls.get(held - 1) not in counted
    device_s = sum(c['dur_ms'] for c in counted) / 1e3
    assert _family('skytpu_engine_device_seconds_total') == pytest.approx(
        device_s, abs=1e-4)
    assert device_s < sum(c['dur_ms'] for c in ordered) / 1e3 - 0.25


def test_an_interval_too_short_for_the_call_reads_host_bound(
        tiny_engine_model):
    """A hold that begins inside a fetch returns it late: that call
    reads `device` and is as long as the hold; the call behind it, whose
    fetch may cost more than a millisecond on a busy host, is told by its
    interval, which the device cannot have run a call in.  Neither is
    counted, and the yardstick is not left at the hold's length."""
    from skypilot_tpu.inference import engine as engine_mod
    engine = _engine(tiny_engine_model, steps_per_call=32)

    def closed(start, end, waited, carried=()):
        engine._call_end = start
        ph = tracing.phase('engine.loop.fetch')
        ph.end, ph.seconds = end, waited
        call = (engine._call_seq, list(carried), start, 2)
        engine._call_seq += 1
        engine._close_call(call, ph)

    closed(0.00, 0.10, 0.09)              # a call alone: 100 ms
    closed(0.10, 0.20, 0.09)
    closed(0.20, 3.20, 2.99)              # the hold, inside its fetch
    closed(3.20, 3.22, 0.004)             # done long ago; 4 ms to fetch it
    closed(3.22, 3.32, 0.09)
    closed(3.32, 3.42, 0.09)
    engine._flush_loop_seconds(final=True)
    bounds = [c['attrs']['bound'] for c in _calls()]
    assert bounds == ['device', 'device', 'device', 'host', 'device',
                      'device']
    assert _family('skytpu_engine_device_seconds_total') == pytest.approx(
        0.4, abs=1e-6)                    # not the hold, not the 20 ms
    assert _family('skytpu_engine_decode_call_seconds_count') == 4
    assert engine._decode_call_s == pytest.approx(0.1)
    # A hold that begins OUTSIDE a fetch: the host comes for a call 3 s
    # after the last fetch, finds it done, and takes 2 ms to fetch it.
    closed(3.42, 6.42, 0.002)
    closed(6.42, 6.52, 0.09)
    closed(6.52, 6.62, 0.09)
    engine._flush_loop_seconds(final=True)
    assert [c['attrs']['bound'] for c in _calls()][-3:] == [
        'host', 'device', 'device']
    assert _family('skytpu_engine_device_seconds_total') == pytest.approx(
        0.6, abs=1e-6)                    # the two calls behind it, no more
    # With something in front of it a call may well take that long.
    closed(6.62, 9.62, 0.002, carried=[{'kind': 'prefill', 'bucket': 8,
                                        'rows': 1, 'held': 1}])
    assert _calls()[-1]['attrs']['bound'] == 'device'
    # A yardstick left too long misjudges a call or two, and no more.
    engine._decode_call_s = 1.0
    for k in range(4):
        closed(9.62 + 0.1 * k, 9.72 + 0.1 * k, 0.09)
    assert [c['attrs']['bound'] for c in _calls()][-4:] == [
        'host', 'host', 'host', 'device']
    assert engine_mod._FETCH_AT_ONCE_S == 0.001
