"""Tier-1 gate for the hot-path invariant analyzer (skypilot_tpu/analysis).

Two jobs:

1. THE GATE — zero unsuppressed findings over skypilot_tpu/ with the
   full rule set.  Every future PR that adds a stray sync / recompile /
   blocking call / rogue sqlite / unbounded IO / rogue metric fails
   tier-1 here, not in production.

2. THE ANALYZER'S OWN COVERAGE — known-bad fixtures per rule
   (tests/fixtures/analysis/), suppression semantics, call-graph
   reachability, JSON schema stability, and the proof that the
   engine's `# skytpu: allow-sync` annotations are load-bearing
   (deleting any one fails the gate).
"""
import json
import os
import re

import pytest

from skypilot_tpu import analysis
from skypilot_tpu.analysis import reporters
from skypilot_tpu.analysis.rules import all_rules

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, 'skypilot_tpu')
FIXTURES = os.path.join(REPO, 'tests', 'fixtures', 'analysis')


def _by_rule(findings):
    out = {}
    for f in findings:
        out.setdefault(f.rule, []).append(f)
    return out


# ---------------------------------------------------------------------------
# 1. the gate
# ---------------------------------------------------------------------------
def test_package_has_zero_findings():
    """THE invariant gate: the whole package is clean under every rule.

    If this fails after your change, either fix the violation or — if
    it is intentional — annotate the call site with
    `# skytpu: allow-<rule>(<reason>)` and defend the reason in review.
    """
    report = analysis.run_check([PKG])
    assert not report.parse_errors, report.parse_errors
    assert len(report.rules) >= 6
    msgs = '\n'.join(f.format() for f in report.unsuppressed)
    assert not report.unsuppressed, f'new invariant violations:\n{msgs}'


def test_gate_covers_the_real_loops():
    """The sync rule must actually anchor at the engine/trainer/RL
    loops — if the entry points vanish (rename without updating the
    markers/backstops), the gate would pass vacuously."""
    report = analysis.run_check([PKG], rules=['hot-loop-sync'])
    eps = set(report.entry_points)
    for needle in ('DecodeEngine.step_pipelined', 'DecodeEngine.step',
                   'Trainer.run', 'rl.rollout'):
        assert any(e.endswith(needle) for e in eps), (needle, eps)
    # The engine's intentional sync points are visible as SUPPRESSED
    # findings — the analyzer sees them and the annotation holds them.
    engine_suppressed = [f for f in report.suppressed
                         if f.path.endswith('inference/engine.py')]
    assert len(engine_suppressed) >= 2
    for f in engine_suppressed:
        assert f.reason       # the reason is mandatory and recorded


# ---------------------------------------------------------------------------
# 2. every rule fires on a known-bad fixture
# ---------------------------------------------------------------------------
@pytest.mark.parametrize('rule_name', [r.name for r in all_rules()])
def test_rule_fires_on_known_bad_fixture(rule_name):
    report = analysis.run_check([FIXTURES], rules=[rule_name])
    hits = [f for f in report.unsuppressed if f.rule == rule_name]
    assert hits, f'{rule_name} found nothing in its known-bad fixtures'


def test_fixture_findings_land_where_expected():
    report = analysis.run_check([FIXTURES])
    by_rule = _by_rule(report.unsuppressed)
    # hot-loop-sync: all five sync forms, including one two calls away.
    sync_paths = {(f.path, 'helper_two' in f.message)
                  for f in by_rule['hot-loop-sync']}
    assert ('hot_sync/bad_sync.py', True) in sync_paths
    msgs = ' '.join(f.message for f in by_rule['hot-loop-sync'])
    for form in ('.item()', 'jax.device_get', 'float(',
                 '.block_until_ready()', 'np.asarray'):
        assert form in msgs
    # Unreachable / jit-wrapped np.asarray sites are NOT flagged.
    flagged_lines = {f.line for f in by_rule['hot-loop-sync']
                     if f.path == 'hot_sync/bad_sync.py'}
    src = open(os.path.join(FIXTURES, 'hot_sync/bad_sync.py')).read()
    lines = src.splitlines()
    unreachable = next(i + 1 for i, l in enumerate(lines)
                       if 'unreachable' in l and 'def ' in l)
    assert all(ln < unreachable for ln in flagged_lines)
    # recompile-hazard: both the in-loop jits and the unpinned hot jit.
    rc = by_rule['recompile-hazard']
    assert sum('inside a loop' in f.message for f in rc) == 2
    assert any(f.path.endswith('train/trainer.py') and
               'without pinned' in f.message for f in rc)
    # blocking-in-async: sleep/requests/subprocess, not the offloaded
    # nested def and not asyncio.sleep.
    ba = by_rule['blocking-in-async']
    assert len(ba) == 3
    assert all(f.path == 'server/bad_blocking.py' for f in ba)
    # db-discipline: sqlite3 AND psycopg import + connect flagged; the
    # allowlisted funnel mirror (dbok/utils/db_utils.py) is clean.
    db = by_rule['db-discipline']
    assert {f.path for f in db} == {'bad_db.py', 'bad_psycopg.py'}
    assert sum('psycopg' in f.message for f in db) == 2
    # unbounded-io: two missing timeouts + the hot retry loop in the
    # provisioning fixture, plus the KV-transfer twin (handoff push
    # without timeout, hot handoff retry loop), plus the fleetsim twin
    # (deadline-less replica probe, hot readiness retry); the good
    # file is clean.
    ub = by_rule['unbounded-io']
    assert {f.path for f in ub} == {'provision/bad_unbounded.py',
                                    'inference/bad_kv_transfer.py',
                                    'fleetsim/bad_fleetsim.py'}
    assert sum('retry loop' in f.message for f in ub) == 3
    kv = [f for f in ub if f.path == 'inference/bad_kv_transfer.py']
    assert len(kv) == 2
    assert any('session.post' in f.message for f in kv)
    fleet = [f for f in ub if f.path == 'fleetsim/bad_fleetsim.py']
    assert len(fleet) == 2
    assert any('requests.get' in f.message for f in fleet)
    # metric-naming: _total / unit-suffix / legal-name / _HELP checks,
    # plus the span-registry half (legal dotted names, SPAN_HELP).
    mn = ' '.join(f.message for f in by_rule['metric-naming'])
    for needle in ('must end _total', 'must not end _total',
                   'unit suffix', 'not a legal', 'no _HELP',
                   'no SPAN_HELP', 'not a legal span name'):
        assert needle in mn
    span_hits = [f for f in by_rule['metric-naming']
                 if f.path == 'bad_spans.py']
    assert len(span_hits) == 3
    # Loop phases (tracing.phase, name first) are held to the same
    # registry: the rogue and the illegal name, not the registered one.
    phase_hits = [f for f in by_rule['metric-naming']
                  if f.path == 'bad_phases.py']
    assert len(phase_hits) == 2
    phase_msgs = ' '.join(f.message for f in phase_hits)
    assert 'engine.loop.rogue' in phase_msgs and "'Loop'" in phase_msgs
    # Paged-KV fixture: an unregistered page-cache gauge + counter and
    # an unregistered prefix span — each caught (registry discipline
    # covers the new families too).
    page_hits = [f for f in by_rule['metric-naming']
                 if f.path == 'bad_page_metrics.py']
    assert len(page_hits) == 3
    page_msgs = ' '.join(f.message for f in page_hits)
    assert 'skytpu_engine_kv_rogue_pages' in page_msgs
    assert 'skytpu_engine_prefix_cache_rogue_total' in page_msgs
    assert 'engine.prefix_rogue' in page_msgs
    # Perf fixture: device-cost attribution suffixes (_mfu /
    # _per_token / _intensity) are gauge-only — flagged even when the
    # family IS registered (skytpu_engine_mfu has a _HELP entry) —
    # and perf.* spans are held to the span registry like any other.
    perf_hits = [f for f in by_rule['metric-naming']
                 if f.path == 'bad_perf.py']
    assert len(perf_hits) == 6
    perf_msgs = ' '.join(f.message for f in perf_hits)
    assert sum('legal only as gauges' in f.message
               for f in perf_hits) == 2
    assert 'skytpu_engine_rogue_bytes_per_token' in perf_msgs
    assert 'perf.rogue_capture' in perf_msgs
    # State-backend fixture: db_op families are held to the same bar
    # (unit suffix on the histogram, _HELP entry on both).
    db_hits = [f for f in by_rule['metric-naming']
               if f.path == 'bad_db_metrics.py']
    assert len(db_hits) == 3
    db_msgs = ' '.join(f.message for f in db_hits)
    assert 'skytpu_db_op_millis' in db_msgs
    assert 'skytpu_db_op_rogue_total' in db_msgs
    # Fleetsim fixture: the new skytpu_fleetsim_* families are held to
    # the same registry discipline (unit suffix, _HELP entry).
    fleet_hits = [f for f in by_rule['metric-naming']
                  if f.path == 'fleetsim/bad_fleetsim.py']
    assert len(fleet_hits) == 3
    fleet_msgs = ' '.join(f.message for f in fleet_hits)
    assert 'skytpu_fleetsim_tick_millis' in fleet_msgs
    assert 'skytpu_fleetsim_rogue_total' in fleet_msgs
    # Obs fixture: AlertRule family references are held to the same
    # registry — unregistered literal, module-constant, ratio_family
    # denominator, and the PR 20 train-rule kinds (gauge_low goodput
    # floor, gauge_high skew ceiling) are each caught; the rules built
    # from registered metrics_lib constants (including the train
    # goodput/skew families) are clean.
    obs_hits = [f for f in by_rule['metric-naming']
                if f.path == 'obs/bad_alert_rule.py']
    assert len(obs_hits) == 5
    obs_msgs = ' '.join(f.message for f in obs_hits)
    assert 'skytpu_obs_rogue_seconds' in obs_msgs
    assert 'skytpu_engine_rogue_latency_seconds' in obs_msgs
    assert 'skytpu_lb_rogue_total' in obs_msgs
    assert 'skytpu_train_rogue_goodput_percent' in obs_msgs
    assert 'skytpu_train_rogue_skew' in obs_msgs
    assert all('can never fire' in f.message for f in obs_hits)
    # speculation: the jit-inside-propose/verify hazard AND the
    # unpinned verify program — both from the speculation fixture,
    # and ONLY from it (the engine's real verify wiring is clean).
    spec = by_rule['speculation']
    assert {f.path for f in spec} == {'inference/bad_speculation.py'}
    assert len(spec) == 2
    spec_msgs = ' '.join(f.message for f in spec)
    assert 'defeats the compile cache' in spec_msgs
    assert 'without pinned' in spec_msgs


def test_phase_calls_are_held_to_the_span_registry():
    """`tracing.phase(<name>)` is a span registration whose name comes
    FIRST (the recorders take the request id first): the rule reads the
    right argument, flags an unregistered or illegal phase, and passes
    the engine's and the trainer's own phases and the loop counters."""
    report = analysis.run_check(
        [os.path.join(FIXTURES, 'bad_phases.py')], rules=['metric-naming'])
    assert sorted(f.line for f in report.unsuppressed) == [7, 9]
    report = analysis.run_check(
        [os.path.join(PKG, 'inference', 'engine.py'),
         os.path.join(PKG, 'train', 'trainer.py')],
        rules=['metric-naming'])
    assert not report.unsuppressed, [f.format() for f in
                                     report.unsuppressed]
    # ...and it did look: drop a phase from SPAN_HELP's view by
    # renaming it in a copy of the trainer, and the rule objects.
    src = open(os.path.join(PKG, 'train', 'trainer.py')).read()
    assert "tracing.phase('train.feed')" in src
    mutated = src.replace("tracing.phase('train.feed')",
                          "tracing.phase('train.fed')")
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, 'trainer_copy.py')
        with open(path, 'w') as f:
            f.write(mutated)
        report = analysis.run_check([path], rules=['metric-naming'])
    assert ['train.fed' in f.message for f in report.unsuppressed] == [True]


# ---------------------------------------------------------------------------
# suppression semantics
# ---------------------------------------------------------------------------
def test_suppression_with_reason_suppresses():
    report = analysis.run_check(
        [os.path.join(FIXTURES, 'hot_sync', 'good_sync.py')],
        rules=['hot-loop-sync'])
    assert not report.unsuppressed
    assert len(report.suppressed) == 1
    assert 'fixture counterpart' in report.suppressed[0].reason


def test_suppression_requires_a_reason():
    report = analysis.run_check(
        [os.path.join(FIXTURES, 'hot_sync', 'empty_reason.py')],
        rules=['hot-loop-sync'])
    assert len(report.unsuppressed) == 1
    assert 'reason is required' in report.unsuppressed[0].message


def test_unknown_rule_rejected():
    with pytest.raises(ValueError, match='unknown rule'):
        analysis.run_check([FIXTURES], rules=['no-such-rule'])


# ---------------------------------------------------------------------------
# the engine annotations are load-bearing
# ---------------------------------------------------------------------------
def test_deleting_any_engine_allow_sync_fails_the_gate(tmp_path):
    """Acceptance criterion: strip any ONE `# skytpu: allow-sync`
    annotation from inference/engine.py and the gate must fail.  Runs
    the sync rule on a modified copy (pure AST — nothing imported)."""
    src = open(os.path.join(PKG, 'inference', 'engine.py')).read()
    pattern = re.compile(r'#\s*skytpu:\s*allow-sync\([^)]*\)')
    annotations = list(pattern.finditer(src))
    assert len(annotations) >= 2, 'engine.py lost its sync annotations'

    # Intact copy: clean.
    intact = tmp_path / 'engine_intact.py'
    intact.write_text(src)
    report = analysis.run_check([str(intact)], rules=['hot-loop-sync'])
    assert not report.unsuppressed
    assert len(report.suppressed) >= 2

    # Each annotation individually deleted: the gate fails.
    for i, m in enumerate(annotations):
        mutated = src[:m.start()] + src[m.end():]
        p = tmp_path / f'engine_drop{i}.py'
        p.write_text(mutated)
        report = analysis.run_check([str(p)], rules=['hot-loop-sync'])
        assert report.unsuppressed, (
            f'deleting annotation #{i} did not fail the gate')
        assert all(f.rule == 'hot-loop-sync'
                   for f in report.unsuppressed)


# ---------------------------------------------------------------------------
# reporters / CLI
# ---------------------------------------------------------------------------
def test_json_reporter_schema_is_stable():
    report = analysis.run_check([FIXTURES])
    doc = json.loads(analysis.render_json(report, root=FIXTURES))
    assert doc['version'] == reporters.JSON_SCHEMA_VERSION == 1
    assert set(doc) == {'version', 'root', 'rules', 'entry_points',
                        'findings', 'summary'}
    assert set(doc['summary']) == {'total', 'suppressed',
                                   'files_scanned', 'parse_errors'}
    assert doc['summary']['total'] == len(report.unsuppressed)
    for f in doc['findings']:
        assert set(f) == {'rule', 'path', 'line', 'col', 'message',
                          'suppressed', 'reason'}
    # Deterministic ordering (CI artifacts diff cleanly).
    assert doc['findings'] == sorted(
        doc['findings'],
        key=lambda f: (f['path'], f['line'], f['col'], f['rule']))


def test_cli_static_mode():
    from click.testing import CliRunner
    from skypilot_tpu.client.cli import cli
    runner = CliRunner()
    ok = runner.invoke(cli, ['check', PKG])
    assert ok.exit_code == 0, ok.output
    assert 'no findings' in ok.output
    bad = runner.invoke(cli, ['check', FIXTURES])
    assert bad.exit_code == 1
    as_json = runner.invoke(cli, ['check', FIXTURES, '--json'])
    doc = json.loads(as_json.output)
    assert doc['summary']['total'] > 0
    listed = runner.invoke(cli, ['check', '--list-rules'])
    assert listed.exit_code == 0
    for r in all_rules():
        assert r.name in listed.output
    only = runner.invoke(cli, ['check', FIXTURES, '--rule',
                               'db-discipline', '--json'])
    rules_seen = {f['rule']
                  for f in json.loads(only.output)['findings']}
    assert rules_seen == {'db-discipline'}


def test_text_reporter_mentions_suppressed_count():
    report = analysis.run_check([PKG])
    text = analysis.render_text(report)
    assert 'no findings' in text
    assert 'annotated exception' in text
