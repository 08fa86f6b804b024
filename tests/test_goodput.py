"""Training goodput ledger + multi-host straggler detection (the
training twin of test_obs.py):

- ledger arithmetic on both state backends: additive upserts, the
  queue's downtime rollup, interval timeline rows;
- PhaseRecorder tiling: the categories partition elapsed time with no
  gaps and no overlaps BY CONSTRUCTION — property-tested across random
  begin/carve sequences with injected preemptions and controller
  restarts, including hostile over-carves;
- durable resume: the breakdown SUMS across recorder incarnations and
  ledger re-opens (what survives a preempted worker + restarted
  controller);
- the store's host sub-label through downsampling, per-host windowed
  quantiles, skew derivation, and the straggler/goodput_low alert
  rules' fire AND clear transitions on a planted slow host;
- badput-aware throughput: a slow fake checkpointer + stalling input
  iterator must NOT depress reported tokens/s (the trainer.py:219 fix);
- the trainer hot loop stays sync-free and recompile-free with the
  goodput instrumentation in it (counted, not assumed);
- `skytpu jobs top` snapshot/render, live and as a dead-job postmortem;
- the zero-hardware goodput sim (fleetsim/goodput_run.py).
"""
import math
import random
import time

import pytest

from pg_utils import make_backend_url_fixture
from skypilot_tpu.obs import alerts as obs_alerts
from skypilot_tpu.obs import goodput
from skypilot_tpu.obs import jobs_top
from skypilot_tpu.obs import store as obs_store
from skypilot_tpu.server import metrics as metrics_lib
from skypilot_tpu.server import tracing

backend_url = make_backend_url_fixture('goodput')

STEP = metrics_lib.TRAIN_STEP_FAMILY
T0 = 1_000_000.0


@pytest.fixture(autouse=True)
def _reset():
    from skypilot_tpu.perf import compile_telemetry
    metrics_lib.reset_for_tests()
    tracing.reset_for_tests()
    compile_telemetry.reset_for_tests()
    yield
    metrics_lib.reset_for_tests()
    tracing.reset_for_tests()
    compile_telemetry.reset_for_tests()


@pytest.fixture
def dsn(backend_url, tmp_path):
    return backend_url or str(tmp_path / 'goodput.db')


def _train_expo(step_counts, goodput_pct=None):
    """A worker's cumulative exposition: host-labeled step-time
    histogram (fast steps land in the 0.1s bucket, slow ones in the
    0.5s bucket) + the goodput gauge.  step_counts:
    {host: (fast_n, slow_n)}."""
    lines = []
    for host, (fast, slow) in sorted(step_counts.items()):
        lines += [
            f'{STEP}_bucket{{le="0.1",host="{host}"}} {fast}',
            f'{STEP}_bucket{{le="0.5",host="{host}"}} {fast + slow}',
            f'{STEP}_bucket{{le="+Inf",host="{host}"}} {fast + slow}',
        ]
    if goodput_pct is not None:
        lines.append(
            f'{metrics_lib.TRAIN_GOODPUT_FAMILY} {goodput_pct}')
    return '\n'.join(lines) + '\n'


# ---------------------------------------------------------------------------
# Ledger conformance (sqlite + Postgres via the backend fixture)
# ---------------------------------------------------------------------------
def test_ledger_additive_upsert_and_queries(dsn):
    led = goodput.GoodputLedger(dsn)
    led.add('7', goodput.PRODUCTIVE, 100.0, t0=T0, t1=T0 + 100)
    led.add('7', goodput.PRODUCTIVE, 50.0, t0=T0 + 110, t1=T0 + 160)
    led.add('7', goodput.CHECKPOINT_SAVE, 6.0)
    led.add('7', goodput.PREEMPTION_DOWNTIME, 4.0,
            t0=T0 + 100, t1=T0 + 104)
    led.add('7', goodput.RECOVERY_RELAUNCH, 6.0,
            t0=T0 + 104, t1=T0 + 110)
    led.add('8', goodput.PRODUCTIVE, 10.0)
    totals = led.totals('7')
    assert totals[goodput.PRODUCTIVE] == pytest.approx(150.0)
    assert totals[goodput.CHECKPOINT_SAVE] == pytest.approx(6.0)
    assert led.wall('7') == pytest.approx(166.0)
    assert led.goodput_pct('7') == pytest.approx(100 * 150 / 166.0)
    assert led.downtime_s('7') == pytest.approx(10.0)
    assert led.downtime_by_job() == {'7': pytest.approx(10.0)}
    assert led.jobs() == ['7', '8']
    # Interval rows come back in timeline order.
    ivs = led.intervals('7')
    assert [iv['category'] for iv in ivs] == [
        goodput.PRODUCTIVE, goodput.PREEMPTION_DOWNTIME,
        goodput.RECOVERY_RELAUNCH, goodput.PRODUCTIVE]
    assert led.intervals('7', goodput.PREEMPTION_DOWNTIME) == [
        {'category': goodput.PREEMPTION_DOWNTIME,
         't0': T0 + 100, 't1': T0 + 104}]
    # Hygiene: zero/negative durations are dropped, unknown categories
    # rejected, and a job with no rows has no goodput number (not 0%).
    led.add('7', goodput.PRODUCTIVE, 0.0)
    led.add('7', goodput.PRODUCTIVE, -3.0)
    assert led.wall('7') == pytest.approx(166.0)
    with pytest.raises(ValueError, match='unknown goodput category'):
        led.add('7', 'coffee_break', 1.0)
    assert led.goodput_pct('nope') is None
    assert led.downtime_s('nope') == 0.0


def test_ledger_durable_across_controller_restart(dsn):
    """A new ledger handle over the same backend (controller restart /
    `jobs top` after the job died) keeps accumulating — nothing lives
    in the process."""
    goodput.GoodputLedger(dsn).add('42', goodput.PRODUCTIVE, 30.0)
    reopened = goodput.GoodputLedger(dsn)
    reopened.add('42', goodput.PRODUCTIVE, 12.0)
    reopened.add('42', goodput.RECOVERY_RELAUNCH, 5.0)
    assert goodput.GoodputLedger(dsn).totals('42') == {
        goodput.PRODUCTIVE: pytest.approx(42.0),
        goodput.RECOVERY_RELAUNCH: pytest.approx(5.0)}


# ---------------------------------------------------------------------------
# PhaseRecorder tiling
# ---------------------------------------------------------------------------
def test_phase_recorder_deterministic_tiling(tmp_path):
    """A known phase sequence: totals and interval rows both tile the
    timeline exactly, carves re-attribute within their interval, and
    consecutive interval rows share boundary stamps."""
    led = goodput.GoodputLedger(str(tmp_path / 'l.db'))
    clock = [100.0]
    rec = goodput.PhaseRecorder(job='d', ledger=led,
                                clock=lambda: clock[0],
                                to_wall=lambda t: t)
    rec.begin(goodput.INIT_COMPILE)
    clock[0] += 30.0
    rec.begin(goodput.PRODUCTIVE)
    clock[0] += 50.0
    rec.carve(goodput.INPUT_STALL, 2.0)
    rec.begin(goodput.CHECKPOINT_SAVE)
    clock[0] += 4.0
    rec.begin(goodput.PRODUCTIVE)
    clock[0] += 16.0
    totals = rec.close()
    assert totals == {
        goodput.INIT_COMPILE: pytest.approx(30.0),
        goodput.PRODUCTIVE: pytest.approx(64.0),
        goodput.INPUT_STALL: pytest.approx(2.0),
        goodput.CHECKPOINT_SAVE: pytest.approx(4.0)}
    assert sum(totals.values()) == pytest.approx(100.0)
    assert led.totals('d') == {k: pytest.approx(v)
                               for k, v in totals.items()}
    ivs = led.intervals('d')
    assert ivs[0]['t0'] == pytest.approx(100.0)
    assert ivs[-1]['t1'] == pytest.approx(200.0)
    for a, b in zip(ivs, ivs[1:]):
        assert a['t1'] == pytest.approx(b['t0'], abs=1e-9)
    # Each interval carries a train.phase span in the flight recorder.
    spans = [e for e in tracing.events_for('job-d')
             if e['name'] == goodput.PHASE_SPAN]
    assert len(spans) == len(ivs)
    assert spans[1]['attrs']['category'] == goodput.PRODUCTIVE
    assert spans[1]['attrs']['input_stall_s'] == pytest.approx(2.0)


def test_phase_recorder_tiling_property_under_fuzz(tmp_path):
    """The acceptance property: across random phase sequences — with
    over-carves, zero-length intervals, preemptions mid-phase, and
    controller-written gap categories — every incarnation's totals sum
    to EXACTLY its elapsed time, the durable ledger sums to exactly
    the job's full wall-clock, and interval rows never overlap."""
    rng = random.Random(20)
    led = goodput.GoodputLedger(str(tmp_path / 'l.db'))
    clock = [1000.0]
    wall = 0.0
    worker_cats = (goodput.PRODUCTIVE, goodput.INIT_COMPILE,
                   goodput.CHECKPOINT_SAVE, goodput.CHECKPOINT_RESTORE)
    for incarnation in range(4):
        rec = goodput.PhaseRecorder(job='p', ledger=led,
                                    clock=lambda: clock[0],
                                    to_wall=lambda t: t)
        start = clock[0]
        rec.begin(goodput.INIT_COMPILE)
        for _ in range(40):
            op = rng.random()
            if op < 0.4:
                rec.begin(rng.choice(worker_cats))
            elif op < 0.8:
                clock[0] += rng.uniform(0.01, 5.0)
            else:
                # Hostile: carve more than the interval can hold — the
                # clamp must keep the tiling exact.
                rec.carve(goodput.INPUT_STALL, rng.uniform(0.01, 20.0))
        totals = rec.close()
        elapsed = clock[0] - start
        assert sum(totals.values()) == pytest.approx(elapsed,
                                                     abs=1e-9)
        assert all(v >= 0 for v in totals.values())
        wall += elapsed
        if incarnation < 3:
            # The controller fills the inter-incarnation gap.
            t_lost = clock[0]
            clock[0] += rng.uniform(0.5, 5.0)
            t_detect = clock[0]
            clock[0] += rng.uniform(0.5, 10.0)
            t_up = clock[0]
            led.add('p', goodput.PREEMPTION_DOWNTIME,
                    t_detect - t_lost, t0=t_lost, t1=t_detect)
            led.add('p', goodput.RECOVERY_RELAUNCH,
                    t_up - t_detect, t0=t_detect, t1=t_up)
            wall += t_up - t_lost
    # The durable sum across 4 incarnations + 3 recoveries is the
    # whole timeline (acceptance: within 1%; the sim clock makes it
    # exact to float precision here).
    assert led.wall('p') == pytest.approx(wall, rel=1e-9)
    ivs = led.intervals('p')
    assert ivs
    for a, b in zip(ivs, ivs[1:]):
        assert a['t1'] > a['t0']
        assert a['t1'] <= b['t0'] + 1e-9   # no overlaps, ever


def test_phase_recorder_live_views_do_not_close():
    clock = [0.0]
    rec = goodput.PhaseRecorder(clock=lambda: clock[0])
    rec.begin(goodput.INIT_COMPILE)
    clock[0] = 10.0
    rec.begin(goodput.PRODUCTIVE)
    clock[0] = 40.0
    rec.carve(goodput.INPUT_STALL, 5.0)
    snap = rec.snapshot()
    assert snap[goodput.PRODUCTIVE] == pytest.approx(25.0)
    assert snap[goodput.INPUT_STALL] == pytest.approx(5.0)
    assert rec.goodput_pct() == pytest.approx(100 * 25 / 40.0)
    assert rec.productive_s() == pytest.approx(25.0)
    # The open interval is still open: snapshots are side-effect-free
    # (only the CLOSED init interval has settled into totals).
    assert rec.category == goodput.PRODUCTIVE
    assert rec.totals == {goodput.INIT_COMPILE: pytest.approx(10.0)}
    clock[0] = 50.0
    assert rec.close()[goodput.PRODUCTIVE] == pytest.approx(35.0)


# ---------------------------------------------------------------------------
# Store: host sub-label through downsampling + skew derivation
# ---------------------------------------------------------------------------
def test_store_keeps_host_sublabel_and_derives_skew(dsn):
    store = obs_store.TelemetryStore(dsn, resolution=1.0)
    svc = 'job-9'
    store.ingest(svc, _train_expo({'h0': (10, 0), 'h1': (0, 5)}),
                 now=T0, leader_check=False)
    store.ingest(svc, _train_expo({'h0': (30, 0), 'h1': (0, 15)}),
                 now=T0 + 1, leader_check=False)
    by_host = store.histogram_window_by_replica(svc, STEP, T0, T0 + 2)
    assert set(by_host) == {'h0', 'h1'}
    # Per-host deltas, not lifetime counts.
    assert by_host['h0'][0.1] == pytest.approx(20.0)
    assert by_host['h1'][math.inf] == pytest.approx(10.0)
    skew = goodput.step_time_skew(store, svc, T0, T0 + 2)
    assert skew is not None
    assert skew['slow_host'] == 'h1'
    # Two hosts: median averages the pair, so skew = slow/median
    # (0.3 / 0.175) rather than slow/fast.
    assert skew['skew'] > 1.3
    assert set(skew['p50_by_host']) == {'h0', 'h1'}
    # One host has no skew (and must not read as 'balanced').
    store.ingest('solo', _train_expo({'h0': (10, 0)}), now=T0,
                 leader_check=False)
    store.ingest('solo', _train_expo({'h0': (20, 0)}), now=T0 + 1,
                 leader_check=False)
    assert goodput.step_time_skew(store, 'solo', T0, T0 + 2) is None
    # Derived-gauge write path + the ceiling query gauge_high burns on.
    store.put_gauge(svc, metrics_lib.TRAIN_STEP_SKEW_FAMILY, 2.5,
                    T0 + 1)
    store.put_gauge(svc, metrics_lib.TRAIN_STEP_SKEW_FAMILY, 1.0,
                    T0 + 2)
    assert store.gauge_max(svc, metrics_lib.TRAIN_STEP_SKEW_FAMILY,
                           T0, T0 + 3) == pytest.approx(2.5)


def test_straggler_and_goodput_alerts_fire_then_clear(dsn):
    """Controller ticks over a planted slow host + sagging goodput
    gauge: `straggler` and `goodput_low` fire; after the fleet
    equalizes and goodput recovers, both clear."""
    store = obs_store.TelemetryStore(dsn, resolution=1.0)
    svc = 'job-5'
    engine = obs_alerts.AlertEngine(
        store, svc, obs_alerts.train_rules(goodput_target_pct=80.0,
                                           skew_target=1.3),
        windows=obs_alerts.BurnWindows(fast=(2.0, 4.0),
                                       slow=(4.0, 8.0)))
    hosts = ['h0', 'h1', 'h2', 'h3']
    cum = {h: [0, 0] for h in hosts}

    def tick(i, slow_host, per_tick, gp):
        for h in hosts:
            cum[h][1 if h == slow_host else 0] += per_tick
        skew = goodput.train_obs_tick(
            store, svc,
            _train_expo({h: tuple(c) for h, c in cum.items()},
                        goodput_pct=gp),
            T0 + i, engine=engine)
        return skew

    last_skew = None
    for i in range(1, 13):
        last_skew = tick(i, 'h3', 5, gp=42.0) or last_skew
    assert last_skew is not None and last_skew['slow_host'] == 'h3'
    active = {a['rule'] for a in store.active_alerts(svc)}
    assert active == {'straggler', 'goodput_low'}
    # The derived skew is exported as the gauge the rule reads AND
    # rendered for /metrics scrapes.
    assert store.gauge_max(svc, metrics_lib.TRAIN_STEP_SKEW_FAMILY,
                           T0, T0 + 13) > 1.3
    assert metrics_lib.TRAIN_STEP_SKEW_FAMILY in metrics_lib.render()
    # Equalize: every host fast (high per-tick volume so the windowed
    # per-host p50s converge), goodput back over target.
    for i in range(13, 33):
        tick(i, slow_host=None, per_tick=40, gp=95.0)
    assert store.active_alerts(svc) == []
    # The transitions are durable history, not just absence.
    rules_cleared = {a['rule'] for a in store.alert_history(svc)
                     if a.get('cleared_at')}
    assert {'straggler', 'goodput_low'} <= rules_cleared


# ---------------------------------------------------------------------------
# Trainer integration (CPU jax; tiny model)
# ---------------------------------------------------------------------------
@pytest.fixture(scope='module')
def _tiny_train():
    import jax
    from skypilot_tpu.models.llama import LLAMA_CONFIGS, Llama
    from skypilot_tpu.parallel.mesh import MeshPlan, build_mesh
    mesh = build_mesh(MeshPlan(1, 8, 1))
    cfg = LLAMA_CONFIGS['tiny']
    rng = jax.random.PRNGKey(0)
    tokens = jax.random.randint(rng, (8, 32), 0, cfg.vocab_size)
    return Llama(cfg, mesh), mesh, rng, tokens


def _batches(tokens):
    while True:
        yield tokens


def test_trainer_badput_aware_throughput_with_slow_checkpointer(
        _tiny_train, tmp_path, monkeypatch):
    """The trainer.py:219 regression: a slow fake checkpointer + a
    stalling input iterator must not depress the reported tokens/s —
    throughput denominators exclude ledger-classified badput — and the
    classification lands durably, host-labeled, and gauge-exported."""
    import jax
    from skypilot_tpu.train.trainer import TrainConfig, Trainer
    model, mesh, rng, tokens = _tiny_train
    led = goodput.GoodputLedger(str(tmp_path / 'ledger.db'))
    rec = goodput.PhaseRecorder(job='77', ledger=led)
    t_init = time.perf_counter()
    trainer = Trainer(model, mesh, rng, tokens,
                      TrainConfig(warmup_steps=1, total_steps=10),
                      checkpoint_dir=str(tmp_path / 'ckpt'),
                      phases=rec, host='hA')
    ckpt_sleep = 0.25

    def slow_save():
        time.sleep(ckpt_sleep)
    monkeypatch.setattr(trainer, 'save_checkpoint', slow_save)

    stall_sleep = 0.01

    def stalling_batches():
        while True:
            time.sleep(stall_sleep)
            yield tokens

    wall0 = time.perf_counter()
    out = trainer.run(stalling_batches(), 8, checkpoint_every=2,
                      log_every=4)
    wall = time.perf_counter() - wall0
    tokens_seen = 8 * tokens.size
    wall_rate = tokens_seen / wall
    # 4 checkpoints x 0.25s of orbax-time excluded: reported rate must
    # sit well above the naive wall rate (the pre-fix number).
    assert out['tokens_per_s'] > 1.5 * wall_rate
    # ...and so must the exported gauge.
    expo = metrics_lib.render()
    assert 'skytpu_train_tokens_per_second' in expo
    assert metrics_lib.TRAIN_GOODPUT_FAMILY in expo
    assert (f'{metrics_lib.TRAIN_BADPUT_FAMILY}'
            f'{{category="{goodput.CHECKPOINT_SAVE}"}}') in expo
    # Per-host step-time histogram carries the host label.
    assert f'{STEP}_bucket' in expo and 'host="hA"' in expo
    # The durable breakdown: checkpoint time classified (4 x 0.25s),
    # input stalls carved, compile window non-productive, and the
    # whole init->end wall-clock tiled within 1%.
    totals = led.totals('77')
    assert totals[goodput.CHECKPOINT_SAVE] >= 4 * ckpt_sleep * 0.9
    assert totals[goodput.INPUT_STALL] >= 6 * stall_sleep * 0.5
    assert totals[goodput.INIT_COMPILE] > 0
    assert totals[goodput.PRODUCTIVE] > 0
    elapsed = time.perf_counter() - t_init
    # The final productive interval is still open (rolled, so its
    # seconds are flushed; the open remainder is ~0 at this instant).
    assert sum(totals.values()) == pytest.approx(
        sum(rec.snapshot().values()), rel=0.01)
    assert sum(totals.values()) <= elapsed
    assert sum(totals.values()) >= (wall0 - t_init + wall) * 0.99
    # Reported rate ~= tokens / productive seconds (the honest number).
    prod_rate = tokens_seen / max(
        sum(totals.values()) - sum(
            totals.get(c, 0.0) for c in goodput.BADPUT_CATEGORIES),
        1e-9)
    assert out['tokens_per_s'] == pytest.approx(prod_rate, rel=0.35)
    # Phase spans landed in the flight recorder under the job rid.
    spans = [e for e in tracing.events_for('job-77')
             if e['name'] == goodput.PHASE_SPAN]
    cats = {e['attrs']['category'] for e in spans}
    assert goodput.CHECKPOINT_SAVE in cats
    assert goodput.PRODUCTIVE in cats
    del jax  # imported for parity with sibling tests


def test_trainer_hot_loop_zero_syncs_zero_recompiles(_tiny_train,
                                                     monkeypatch):
    """Acceptance: the goodput instrumentation adds ZERO device syncs
    (exactly one jax.device_get per run, at the annotated end-of-run
    fetch; none per step) and zero XLA recompiles once warm."""
    import jax
    from skypilot_tpu.perf import compile_telemetry
    from skypilot_tpu.train.trainer import TrainConfig, Trainer
    model, mesh, rng, tokens = _tiny_train
    trainer = Trainer(model, mesh, rng, tokens,
                      TrainConfig(warmup_steps=1, total_steps=20),
                      host='h0')
    # Warm every program (state init compiled in __init__; step
    # compiles on first run call).
    trainer.run(_batches(tokens), 3)
    compile_telemetry.install()
    compile_telemetry.arm()

    real_get = jax.device_get
    calls = {'n': 0}

    def counting_get(x):
        calls['n'] += 1
        return real_get(x)
    monkeypatch.setattr(jax, 'device_get', counting_get)
    trainer.run(_batches(tokens), 12, log_every=4)
    monkeypatch.setattr(jax, 'device_get', real_get)
    # One fetch total: the end-of-run metrics read.  The per-step path
    # (phase stamps, stall carve, host-labeled histogram) syncs nothing.
    assert calls['n'] == 1
    # Zero post-warmup recompiles with the sentinel armed.
    assert not tracing.events_for(compile_telemetry.SENTINEL_REQUEST_ID)


def test_trainer_window_ends_after_the_log_fetch(_tiny_train, monkeypatch):
    """Found on the chip (PR 22): with a log_fn the throughput window
    was read before the boundary's fetch, so it held dispatch time only
    and the MFU gauge said 20,000%.  A fetch that takes 0.2 s (a device
    that takes 0.2 s a step) bounds the gauge from above."""
    import jax
    from skypilot_tpu.train.trainer import TrainConfig, Trainer
    model, mesh, rng, tokens = _tiny_train
    trainer = Trainer(model, mesh, rng, tokens,
                      TrainConfig(warmup_steps=1, total_steps=20),
                      host='h0')
    real_get = jax.device_get

    def slow_get(x):
        time.sleep(0.2)
        return real_get(x)
    monkeypatch.setattr(jax, 'device_get', slow_get)
    trainer.run(_batches(tokens), 3, log_every=1, log_fn=lambda m: None)
    monkeypatch.setattr(jax, 'device_get', real_get)
    gauge = [l for l in metrics_lib.render().splitlines()
             if l.startswith('skytpu_train_tokens_per_second ')]
    assert 0 < float(gauge[0].split()[1]) <= tokens.size / 0.2


# ---------------------------------------------------------------------------
# jobs top
# ---------------------------------------------------------------------------
def _seed_job_seven(tmp_path):
    led = goodput.GoodputLedger(str(tmp_path / 'ledger.db'))
    led.add('7', goodput.PRODUCTIVE, 360.0, t0=T0, t1=T0 + 360)
    led.add('7', goodput.CHECKPOINT_SAVE, 18.0)
    led.add('7', goodput.PREEMPTION_DOWNTIME, 9.8,
            t0=T0 + 360, t1=T0 + 369.8)
    led.add('7', goodput.RECOVERY_RELAUNCH, 13.1,
            t0=T0 + 369.8, t1=T0 + 382.9)
    return led


def test_jobs_top_snapshot_and_render(tmp_path):
    led = _seed_job_seven(tmp_path)
    store = obs_store.TelemetryStore(str(tmp_path / 'store.db'),
                                     resolution=1.0)
    store.ingest('job-7', _train_expo({'host0': (10, 0),
                                       'host1': (0, 5)}),
                 now=T0, leader_check=False)
    store.ingest('job-7', _train_expo({'host0': (30, 0),
                                       'host1': (0, 15)}),
                 now=T0 + 1, leader_check=False)
    snap = jobs_top.snapshot(
        '7', ledger=led, store=store,
        job_rec={'name': 'demo-ft', 'status': 'RUNNING',
                 'recovery_count': 1})
    wall = 360.0 + 18.0 + 9.8 + 13.1
    assert snap['wall_s'] == pytest.approx(wall)
    assert snap['goodput_pct'] == pytest.approx(100 * 360 / wall)
    assert [b['category'] for b in snap['badput']][0] == \
        goodput.CHECKPOINT_SAVE        # sorted by cost
    assert [h['host'] for h in snap['hosts']] == ['host0', 'host1']
    assert snap['skew']['slow_host'] == 'host1'
    assert [iv['category'] for iv in snap['recoveries']] == [
        goodput.PREEMPTION_DOWNTIME, goodput.RECOVERY_RELAUNCH]
    frame = jobs_top.render(snap)
    assert 'JOB 7 demo-ft (RUNNING)' in frame
    assert 'recoveries 1' in frame
    assert 'BADPUT' in frame and '█' in frame
    assert 'checkpoint_save' in frame
    assert '<- slow' in frame
    assert 'skew' in frame and 'slow host1' in frame
    assert 'RECOVERY TIMELINE:' in frame
    assert f't={T0 + 360:.0f} {goodput.PREEMPTION_DOWNTIME} 9.8s' \
        in frame
    assert 'ALERTS: none' in frame


def test_jobs_top_dead_job_postmortem_without_store(tmp_path):
    """No telemetry store (or a dead job whose scrapes are gone): the
    frame still renders the durable breakdown and recovery timeline."""
    led = _seed_job_seven(tmp_path)
    snap = jobs_top.snapshot('7', ledger=led)
    assert snap['hosts'] == [] and snap['skew'] is None
    frame = jobs_top.render(snap)
    assert 'goodput 89.8%' in frame
    assert 'RECOVERY TIMELINE:' in frame
    assert 'HOST' not in frame
    assert jobs_top.service_of('7') == 'job-7'


# ---------------------------------------------------------------------------
# The zero-hardware goodput sim
# ---------------------------------------------------------------------------
def test_goodput_sim_tiles_exactly_and_detects_the_planted_straggler(
        dsn):
    from skypilot_tpu.fleetsim.goodput_run import (GoodputScenario,
                                                   run_goodput_sim)
    sc = GoodputScenario(slow_host=2)
    res = run_goodput_sim(sc, ledger_dsn=dsn, store_dsn=dsn)
    # Sim clock => the ledger-vs-wall agreement is exact, far inside
    # the 1% acceptance bound.
    assert res['ledger_vs_wall_pct'] < 1e-6
    expected_wall = (2 * sc.init_compile_s + sc.restore_s
                     + sc.detect_s + sc.relaunch_s
                     + sc.steps * (sc.step_s * sc.slow_factor
                                   + sc.stall_s)
                     + (sc.steps // sc.checkpoint_every)
                     * sc.checkpoint_s)
    assert res['sim_wall_s'] == pytest.approx(expected_wall)
    assert res['goodput_pct'] == pytest.approx(
        100.0 * sc.steps * sc.step_s * sc.slow_factor
        / expected_wall)
    assert res['downtime_s'] == pytest.approx(sc.detect_s
                                              + sc.relaunch_s)
    # The injected preemption landed as interval rows bounded by the
    # recorded recovery stamps.
    p = res['preemption']
    assert res['preemption_intervals'] == [
        {'category': goodput.PREEMPTION_DOWNTIME,
         't0': pytest.approx(p['t_lost']),
         't1': pytest.approx(p['t_detect'])}]
    assert res['relaunch_intervals'][0]['t0'] == pytest.approx(
        p['t_detect'])
    assert res['relaunch_intervals'][0]['t1'] == pytest.approx(
        p['t_up'])
    # The planted slow host is named and both train rules fired.
    assert res['skew']['slow_host'] == 'host2'
    assert res['skew']['skew'] > 1.3
    assert {'straggler', 'goodput_low'} <= set(res['active_alerts'])


def test_goodput_sim_healthy_run_is_quiet(tmp_path):
    from skypilot_tpu.fleetsim.goodput_run import (GoodputScenario,
                                                   run_goodput_sim)
    # init small enough that even the first scrape's live goodput
    # gauge sits above the 80% target — no window ever trips.
    sc = GoodputScenario(slow_host=-1, preempt_at_step=-1, steps=100,
                         init_compile_s=1.0, stall_s=0.001)
    res = run_goodput_sim(sc, ledger_dsn=str(tmp_path / 'l.db'),
                          store_dsn=str(tmp_path / 's.db'))
    assert res['ledger_vs_wall_pct'] < 1e-6
    assert res['goodput_pct'] > 80.0   # above the goodput_low target
    assert res['downtime_s'] == 0.0
    assert res['preemption'] is None
    assert res['active_alerts'] == []
    # Balanced hosts: skew ~1, nobody named a straggler by noise.
    assert res['skew'] is None or res['skew']['skew'] < 1.1
