"""The readers of the program's phases (ISSUE 25): on a hand-made trace
whose answers are known, on a small recorded one (cuts of v5e runs of
`yi-coder-1.5b-1chip.pretrain-4k` and `yi-coder-1.5b-chat.chat-steady`
around a loop boundary), through `reduce_metric` as the harness calls
them, and the manifest with the seven new entries."""
import json
import os

import pytest

from benchmarks.harness import manifest, phases, reducers

# One device, busy 0-100, 130-200 and 230-300; a step boundary in each gap.
HAND = {'device': {'/device:TPU:0': {
    'XLA Ops': [['fusion.1', 0, 100], ['fusion.2', 130, 70],
                ['fusion.3', 230, 70]],
    'XLA Modules': [['jit_step(1)', 0, 100], ['jit_step(1)', 130, 70],
                    ['jit_step(1)', 230, 70]]}},
    'host': [['main/1:train.fetch', 0, 105],        # ends 5 into gap one
             ['main/1:train.export', 105, 10],
             ['main/1:train.feed', 115, 5],
             ['main/1:train.dispatch', 120, 8],     # 2 of gap one unnamed
             ['main/1:train.fetch', 130, 75],       # 5 into gap two
             ['main/1:train.export', 205, 10],
             ['main/1:train.feed', 215, 5],
             ['main/1:train.dispatch', 220, 10],
             ['main/1:PjitFunction(step)', 120, 8],
             [':$trainer.py:200 run', 0, 300]]}
NEW = ('prefill_wait_p50_ms', 'first_token_ride_p50_ms',
       'loop_host_busy_pct', 'prewarm_s', 'train_host_per_step_ms',
       'idle_attributed_pct.serve', 'idle_attributed_pct.train')


def ctx_of(trace, **more):
    return dict({'trace': trace, 'spans': {}, 'counters': {}, 'samples': {},
                 'seconds': 30.0, 'values': {}}, **more)


def test_idle_is_told_by_phase():
    told = phases.idle_by_phase(HAND)
    assert told['idle_ns'] == 60                      # 100-130, 200-230
    assert told['attributed_ns'] == 58                # 128-130 is in none
    assert told['by_phase'] == {'train.fetch': 5 + 5,
                                'train.export': 20, 'train.feed': 10,
                                'train.dispatch': 8 + 10}
    assert reducers.reduce_metric(
        'idle_attributed_pct.train', ctx_of(HAND)) == pytest.approx(
            100 * 58 / 60)
    assert reducers.reduce_metric(
        'idle_attributed_pct.serve', ctx_of(HAND)) == pytest.approx(
            100 * 58 / 60)


def test_host_ms_a_step_leaves_the_wait_out():
    # feed 10 + dispatch 18 + export 20 ns over two dispatches.
    assert reducers.reduce_metric(
        'train_host_per_step_ms', ctx_of(HAND)) == pytest.approx(24e-6)


def test_a_trace_without_phases_reads_as_nothing(capsys):
    """The parent commit's program has no phases: every reader returns
    None and raises nothing, with and without a trace."""
    bare = {'device': HAND['device'],
            'host': [e for e in HAND['host'] if 'train.' not in e[0]]}
    for name in NEW[4:]:
        assert reducers.reduce_metric(name, ctx_of(bare)) is None
        assert reducers.reduce_metric(name, ctx_of(None)) is None
    assert 'kept 2 host events' in capsys.readouterr().out
    for name in NEW[:3]:
        assert reducers.reduce_metric(name, ctx_of(None)) is None
    never_idle = {'device': {'/device:TPU:0': {'XLA Ops': [['f', 0, 300]]}},
                  'host': HAND['host']}
    assert phases.idle_by_phase(never_idle) is None


def test_the_cap_on_host_events_is_said(capsys):
    full = {'device': {}, 'host': [['t:x', 0, 1]] * phases.HOST_LIMIT}
    phases.note_host_events('m', full)
    assert 'the cap' in capsys.readouterr().out
    phases.note_host_events('m', HAND)
    assert 'the cap' not in capsys.readouterr().out


def test_loop_busy_share_is_the_counter_over_the_window():
    ctx = ctx_of(None, counters={
        'skytpu_engine_loop_busy_seconds_total': 1.5,
        'skytpu_engine_loop_wait_seconds_total': 28.4})
    assert reducers.reduce_metric('loop_host_busy_pct', ctx) == 5.0


def test_the_two_parts_of_dispatch_read_their_spans():
    ctx = ctx_of(None, spans={'engine.prefill_wait': [80.0, 90.0, 100.0],
                              'engine.first_token_ride': [95.0, 105.0]})
    assert reducers.reduce_metric('prefill_wait_p50_ms', ctx) == 90.0
    assert reducers.reduce_metric('first_token_ride_p50_ms', ctx) == 100.0


def test_prewarm_seconds_come_from_the_setup_spans():
    from skypilot_tpu.server import tracing
    tracing.reset_for_tests()
    assert reducers.reduce_metric('prewarm_s', ctx_of(None)) is None
    rid = 'engine-setup'
    tracing.record_span(rid, 'engine.setup.compile', 1.0, 9.0, kind='decode')
    tracing.record_span(rid, 'engine.setup.layouts', 1.0, 12.0)
    tracing.record_span(rid, 'engine.setup.compile', 20.0, 30.5,
                        kind='prefill', bucket=512, rows=16)
    tracing.record_span(rid, 'engine.setup.compile', 30.5, 39.0,
                        kind='prefill', bucket=512, rows=8)
    tracing.record_span(rid, 'engine.setup.prewarm', 20.0, 39.5, programs=2)
    try:
        assert reducers.reduce_metric(
            'prewarm_s', ctx_of(None)) == pytest.approx(19.5)
    finally:
        tracing.reset_for_tests()


RECORDED = os.path.join(os.path.dirname(__file__), 'data',
                        'trace_phases.json')


@pytest.mark.parametrize('cell', ['train', 'serve'])
def test_recorded_trace_reduces(cell):
    with open(RECORDED, encoding='utf-8') as f:
        rec = json.load(f)[cell]
    trace, want = rec['trace'], rec['expected']
    events = phases.phase_events(trace)
    assert set(events) >= set(want['phases'])
    told = phases.idle_by_phase(trace)
    assert 0 < told['attributed_ns'] <= told['idle_ns']
    assert told['idle_ns'] == want['idle_ns']
    assert told['attributed_ns'] == want['attributed_ns']
    assert sum(told['by_phase'].values()) >= told['attributed_ns']
    got = reducers.reduce_metric(f'idle_attributed_pct.{cell}', ctx_of(trace))
    assert got == pytest.approx(want['idle_attributed_pct'], rel=1e-9)
    assert got <= 100.0
    if cell == 'train':
        assert reducers.reduce_metric(
            'train_host_per_step_ms', ctx_of(trace)) == pytest.approx(
                want['train_host_per_step_ms'], rel=1e-9)


def test_manifest_takes_the_new_entries():
    man = manifest.manifest()
    assert manifest.problems(man) == []
    by_name = {m['name']: m for m in man['per_layer']}
    assert [m['name'] for m in man['per_layer']][-len(NEW):] == list(NEW)
    for name in NEW:
        assert os.path.exists(os.path.join(
            manifest.BENCH_DIR, 'layer_metrics', f'{name}.json'))
        assert by_name[name]['source'] in ('program_span', 'program_counter')
    chat = {m['name'] for m in manifest.metrics_of(
        man, 'yi-coder-1.5b-chat.chat-steady', 'per_layer')}
    assert chat >= set(NEW) - {'train_host_per_step_ms',
                               'idle_attributed_pct.train'}
    train = {m['name'] for m in manifest.metrics_of(
        man, 'yi-coder-1.5b-1chip.pretrain-4k', 'per_layer')}
    assert {'train_host_per_step_ms', 'idle_attributed_pct.train'} <= train
    assert 'prewarm_s' not in train


def test_recorded_trace_shows_the_device_lines_trailing_the_host_lines():
    """Why the split of a few ms of idle by phase cannot be trusted
    (PERF.md, PR 25): in the recorded step boundary the next step's
    program starts on the device line more than a millisecond BEFORE
    the host line's Execute call that launches it."""
    with open(RECORDED, encoding='utf-8') as f:
        trace = json.load(f)['train']['trace']
    programs = trace['device']['/device:TPU:0']['XLA Modules']
    next_step_starts = max(s for _, s, _ in programs)
    launch = [s for name, s, _ in trace['host']
              if name.endswith(':PJRT_LoadedExecutable_Execute')]
    assert len(launch) == 1
    assert 1_000_000 < launch[0] - next_step_starts < 2_500_000
