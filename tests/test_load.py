"""Load tier (ref shape: tests/load_tests/ — a concurrent-client load
generator against the API server).

Hammers one real server process with concurrent readers and writers and
asserts the service properties that matter under load: no 5xx, every
launch executes exactly once to completion, reads stay responsive
(bounded p95) while workers grind, and the server is still healthy
afterwards.
"""
import concurrent.futures
import time

import requests as requests_lib

# Fixture reuse: `chaos_server` depends on `chaos_backend_url`.
from test_chaos import chaos_backend_url, chaos_server  # noqa: F401


def _post_launch(port, i):
    t0 = time.perf_counter()
    r = requests_lib.post(
        f'http://127.0.0.1:{port}/launch',
        json={'task': {'name': f'load{i}',
                       'run': f'echo load-{i}',
                       'resources': {'infra': 'local'}},
              'cluster_name': f'loadc{i % 4}'},
        timeout=60)
    return r.status_code, time.perf_counter() - t0, r

def _get(port, path):
    t0 = time.perf_counter()
    r = requests_lib.get(f'http://127.0.0.1:{port}{path}', timeout=60)
    return r.status_code, time.perf_counter() - t0, r


def test_concurrent_load(chaos_server):  # noqa: F811
    port = chaos_server['port']
    n_launches = 12
    n_reads = 120

    with concurrent.futures.ThreadPoolExecutor(max_workers=16) as pool:
        launch_futs = [pool.submit(_post_launch, port, i)
                       for i in range(n_launches)]
        read_futs = [pool.submit(_get, port,
                                 '/status' if i % 2 else '/requests')
                     for i in range(n_reads)]
        launches = [f.result() for f in launch_futs]
        reads = [f.result() for f in read_futs]

    # No 5xx anywhere under concurrent write+read load.
    assert all(code < 500 for code, _, _ in launches), [
        (c, r.text[:80]) for c, _, r in launches if c >= 500]
    assert all(code == 200 for code, _, _ in reads), [
        (c, r.text[:80]) for c, _, r in reads if c != 200]

    # Reads stay responsive while 12 worker processes grind: generous
    # p95 bound — this is a smoke bar, not a perf benchmark.
    lat = sorted(d for _, d, _ in reads)
    p95 = lat[int(len(lat) * 0.95)]
    assert p95 < 10.0, f'p95 read latency {p95:.2f}s under load'

    # Every accepted launch runs to completion, exactly once.
    rids = [r.json()['request_id'] for code, _, r in launches
            if code == 200]
    assert len(rids) == n_launches
    deadline = time.time() + 300
    statuses = {}
    while time.time() < deadline:
        recs = {rec['request_id']: rec for rec in requests_lib.get(
            f'http://127.0.0.1:{port}/requests?limit=200',
            timeout=30).json()}
        statuses = {rid: recs.get(rid, {}).get('status') for rid in rids}
        if all(s in ('SUCCEEDED', 'FAILED', 'CANCELLED')
               for s in statuses.values()):
            break
        time.sleep(0.5)
    assert all(s == 'SUCCEEDED' for s in statuses.values()), statuses

    # Server is still healthy after the storm.
    assert requests_lib.get(f'http://127.0.0.1:{port}/api/health',
                            timeout=10).json()['status'] == 'healthy'


# ----- SLO autoscaling under a traffic ramp ----------------------------------
# ROADMAP item-4 "done when": under a traffic ramp, the SLO autoscaler
# holds p95 TPOT at/below target while the QPS autoscaler — with the
# SAME replica budget and the same ideal provisioning — violates it.
# Virtual replicas + simulated latency histograms (slo_sim), consumed by
# the autoscaler as real federated exposition text; virtual time, no
# sleeps.
import pytest

# Scenario constants + driver live in slo_sim.
from skypilot_tpu.serve.slo_sim import (DEFAULT_TARGET_TPOT_MS as
                                        TARGET_TPOT_MS)


def _run(qps_schedule, slo: bool):
    from skypilot_tpu.serve import slo_sim
    return slo_sim.run_policy(slo, qps_schedule)


def test_slo_autoscaler_holds_p95_where_qps_autoscaler_fails():
    from skypilot_tpu.serve import slo_sim
    ramp = slo_sim.default_ramp(plateau_ticks=12)
    slo_hist = _run(ramp, slo=True)
    qps_hist = _run(ramp, slo=False)
    p95_slo = slo_sim.requests_weighted_p95(slo_hist, last_n_ticks=4)
    p95_qps = slo_sim.requests_weighted_p95(qps_hist, last_n_ticks=4)
    # The SLO policy converges to a replica count that meets the target…
    assert p95_slo <= TARGET_TPOT_MS, (p95_slo, slo_hist)
    # …the QPS policy, with the identical budget, violates it badly.
    assert p95_qps > 2 * TARGET_TPOT_MS, (p95_qps, qps_hist)
    # Both stayed inside the same budget; the SLO one actually used it.
    assert max(r for _, r, _ in slo_hist) <= 8
    assert max(r for _, r, _ in qps_hist) <= 8
    assert slo_hist[-1][1] > qps_hist[-1][1]


@pytest.mark.slow
def test_slo_ramp_soak_repeated_cycles():
    """Soak variant: three full ramp/plateau/trough cycles.  The SLO
    policy must hold the target on EVERY plateau (no decay of the
    signal across cycles — windowed deltas, counter resets, and the
    downscale projection all keep working), and the QPS policy must
    fail every one of them."""
    from skypilot_tpu.serve import slo_sim
    cycle = slo_sim.default_ramp(plateau_ticks=20) + [2.0] * 10
    schedule = cycle * 3
    slo_hist = _run(schedule, slo=True)
    qps_hist = _run(schedule, slo=False)
    n = len(cycle)
    for c in range(3):
        # The plateau tail of cycle c (last 4 plateau ticks).
        lo, hi = c * n + 23, c * n + 27
        p95_slo = slo_sim.requests_weighted_p95(slo_hist[lo:hi])
        p95_qps = slo_sim.requests_weighted_p95(qps_hist[lo:hi])
        assert p95_slo <= TARGET_TPOT_MS, (c, p95_slo)
        assert p95_qps > TARGET_TPOT_MS, (c, p95_qps)
