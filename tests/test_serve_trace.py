"""Per-request distributed tracing + flight recorder (ISSUE 11).

The acceptance path: a chunked-prefill request through a REAL load
balancer + replica under concurrent load is traceable end to end by
`skytpu trace <id>` — LB admission/routing spans merged (federated)
with the engine's queue/chunk/dispatch spans — and the TTFT
decomposition (queue wait + N x chunk + dispatch) SUMS to the measured
TTFT within tolerance.  Plus: recorder ring semantics, the sync-count
invariant with tracing active, zero recompiles with traced chunked
traffic, the /debug federation dedupe, the LB scrape-age gauge, and
the jobs postmortem surface on the API server.
"""
import json
import re
import threading
import urllib.error
import urllib.request

import pytest

from skypilot_tpu.server import metrics
from skypilot_tpu.server import tracing
from test_observability import _free_port, _get, _run_app_on_thread


@pytest.fixture(autouse=True)
def _reset():
    metrics.reset_for_tests()
    tracing.reset_for_tests()
    yield
    metrics.reset_for_tests()
    tracing.reset_for_tests()


@pytest.fixture(scope='module')
def tiny_engine_model():
    import jax
    from skypilot_tpu.models.llama import LLAMA_CONFIGS, Llama, init_params
    model = Llama(LLAMA_CONFIGS['tiny'])
    params = init_params(model, jax.random.PRNGKey(0))['params']
    return model, params


def _post_json(url, payload, headers=None, timeout=120.0):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers=dict({'Content-Type': 'application/json'},
                     **(headers or {})), method='POST')
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, dict(resp.headers), json.load(resp)


# ----- recorder unit behavior -------------------------------------------------
def test_ring_buffer_bounded_and_evicts_oldest(monkeypatch):
    monkeypatch.setenv(tracing.RING_SIZE_ENV, '4')
    tracing.reset_for_tests()
    for i in range(10):
        tracing.record_instant(f'r{i}', 'engine.first_token', float(i))
    recent = {s['request_id'] for s in tracing.recent_requests()}
    assert recent == {'r6', 'r7', 'r8', 'r9'}       # oldest evicted
    assert tracing.events_for('r0') == []
    assert tracing.capacity() == 4


def test_ring_size_zero_disables_recording(monkeypatch):
    monkeypatch.setenv(tracing.RING_SIZE_ENV, '0')
    tracing.reset_for_tests()
    assert not tracing.enabled()
    tracing.record_instant('x', 'engine.first_token', 0.0)
    tracing.record_span('x', 'engine.queue_wait', 0.0, 1.0)
    assert tracing.events_for('x') == []
    assert tracing.recent_requests() == []


def test_decompose_tiles_and_chrome_export():
    t = 100.0
    tracing.record_span('d1', 'engine.queue_wait', t, t + 0.010)
    tracing.record_span('d1', 'engine.prefill_chunk', t + 0.010,
                        t + 0.050, offset=0, width=8, final=False)
    tracing.record_span('d1', 'engine.prefill_chunk', t + 0.050,
                        t + 0.080, offset=8, width=8, final=True)
    tracing.record_span('d1', 'engine.dispatch', t + 0.080, t + 0.100)
    tracing.record_instant('d1', 'engine.first_token', t + 0.100,
                           slot=0, batch=2, ttft_s=0.100)
    s = tracing.decompose(tracing.events_for('d1'))
    assert s['prefill_chunks'] == 2
    assert s['queue_wait_ms'] == pytest.approx(10.0, abs=0.01)
    assert s['decomposed_ttft_ms'] == pytest.approx(100.0, abs=0.01)
    assert abs(s['unattributed_ms']) < 0.01
    assert s['outcome'] == 'ok'
    # Chrome export: spans become 'X' with microsecond ts/dur, instants
    # 'i'; the document is the same shape utils/timeline.py writes.
    doc = tracing.to_chrome(tracing.events_for('d1'))
    assert set(doc) == {'traceEvents', 'displayTimeUnit'}
    phases = [e['ph'] for e in doc['traceEvents']]
    assert phases.count('X') == 4 and phases.count('i') == 1
    span = doc['traceEvents'][0]
    assert span['dur'] == pytest.approx(10_000, rel=0.01)   # 10 ms in us
    assert span['args']['request_id'] == 'd1'


def test_dedupe_merges_same_process_federation():
    tracing.record_span('dd', 'engine.queue_wait', 0.0, 1.0)
    events = tracing.events_for('dd')
    merged = tracing.dedupe(events + events)       # LB + replica, one process
    assert len(merged) == 1


# ----- engine invariants with the recorder active -----------------------------
class _CountingNumpy:
    def __init__(self, real):
        self._real = real
        self.asarray_calls = 0

    def __getattr__(self, name):
        return getattr(self._real, name)

    def asarray(self, *args, **kwargs):
        self.asarray_calls += 1
        return self._real.asarray(*args, **kwargs)


def test_tracing_adds_zero_device_syncs(tiny_engine_model, monkeypatch):
    """The engine's one-sync-per-step contract holds for a TRACED
    request: all span stamping is host-side perf_counter on the loop
    thread."""
    import numpy as real_np
    from skypilot_tpu.inference import engine as engine_mod
    counting = _CountingNumpy(real_np)
    monkeypatch.setattr(engine_mod, 'np', counting)
    model, params = tiny_engine_model
    engine = engine_mod.DecodeEngine(
        model, params,
        engine_mod.EngineConfig(n_slots=2, prefill_buckets=(8,)))
    req = engine.submit([1, 2, 3], 6, request_id='sync-check')
    active_steps = 0
    while req.finished_at is None:
        if engine.step() > 0:
            active_steps += 1
    assert req.tokens()
    # np.asarray fired once per active step — span recording added none
    # (the chunked path adds np.zeros buffers, not syncs; asarray is
    # the device->host fetch).
    assert counting.asarray_calls == active_steps
    names = [e['name'] for e in tracing.events_for('sync-check')]
    assert names == ['engine.queue_wait', 'engine.prefill',
                     'engine.dispatch', 'engine.prefill_wait',
                     'engine.first_token_ride', 'engine.first_token',
                     'engine.stream_end']


def test_zero_recompiles_with_traced_chunked_traffic(tiny_engine_model):
    """Recording spans must not perturb the compiled-shape story: after
    a warmup pass, traced mixed chunked/short traffic adds no compiled
    entries."""
    from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig
    model, params = tiny_engine_model
    engine = DecodeEngine(model, params,
                          EngineConfig(n_slots=2, prefill_buckets=(8,)))

    def run(tag):
        reqs = [engine.submit(list(range(1, 21)), 4,
                              request_id=f'{tag}-long'),
                engine.submit([1, 2, 3], 4, request_id=f'{tag}-short')]
        while any(r.finished_at is None for r in reqs):
            engine.step_pipelined()
        engine.drain()

    run('warm')
    fns = [engine._decode, engine._prefill_insert,
           engine._prefill_chunk, engine._chunk_insert,
           engine._scratch_fn]
    sizes = [f._cache_size() for f in fns]
    run('measured')
    assert [f._cache_size() for f in fns] == sizes
    # And the traced run recorded its chunk spans.
    chunk_spans = [e for e in tracing.events_for('measured-long')
                   if e['name'] == 'engine.prefill_chunk']
    assert len(chunk_spans) == 3                   # 20 tokens / bucket 8


# ----- e2e: LB + replica, chunked prefill under concurrent load ---------------
def test_trace_e2e_decomposition_sums_to_ttft(tiny_engine_model):
    """THE acceptance test: a chunked-prefill request through a real LB
    and replica under concurrent short-request load; `skytpu trace
    <id>` (against the LB's federated /debug) shows queue + per-chunk +
    dispatch spans whose sum equals the measured TTFT within
    tolerance."""
    from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig
    from skypilot_tpu.inference.server import build_app
    from skypilot_tpu.serve.load_balancer import LoadBalancer
    from skypilot_tpu.serve.load_balancing_policies import RoundRobinPolicy
    model, params = tiny_engine_model
    engine = DecodeEngine(model, params,
                          EngineConfig(n_slots=2, prefill_buckets=(8,)))
    engine.start()
    replica_port, stop_replica = _run_app_on_thread(build_app(engine))
    replica_url = f'http://127.0.0.1:{replica_port}'
    lb = LoadBalancer('trace-svc', _free_port(), RoundRobinPolicy(),
                      ready_urls_fn=lambda: [replica_url],
                      ready_replicas_fn=lambda: [(3, replica_url)])
    lb.start()
    try:
        # Concurrent load: short requests in flight while the long
        # prompt chunks through (client id honored end to end).
        short_errs = []

        def short_wave():
            try:
                _post_json(lb.endpoint + '/v1/completions',
                           {'prompt_ids': [1, 2, 3], 'max_tokens': 4})
            except Exception as e:  # pylint: disable=broad-except
                short_errs.append(e)

        threads = [threading.Thread(target=short_wave) for _ in range(4)]
        for t in threads:
            t.start()
        rid = 'e2e-chunked-1'
        status, headers, body = _post_json(
            lb.endpoint + '/v1/completions',
            {'prompt_ids': list(range(1, 21)), 'max_tokens': 5},
            headers={tracing.TRACE_HEADER: rid})
        for t in threads:
            t.join(timeout=60)
        assert not short_errs, short_errs
        assert status == 200
        assert headers[tracing.TRACE_HEADER] == rid   # id echoes back
        assert body['request_id'] == rid
        measured_ttft_ms = body['usage']['ttft_ms']
        assert measured_ttft_ms is not None

        # Federated /debug at the LB: LB spans + engine spans, one id.
        _, _, text = _get(lb.endpoint + f'/debug/requests/{rid}',
                          timeout=10)
        doc = json.loads(text)
        names = [e['name'] for e in doc['events']]
        assert 'lb.admission' in names
        assert 'lb.route' in names
        assert 'lb.proxy' in names
        assert names.count('engine.prefill_chunk') == 3  # 20 tok / 8
        assert 'engine.first_token' in names
        assert 'engine.stream_end' in names
        # Deduped: same-process LB+replica must not double-report.
        assert names.count('lb.admission') == 1
        assert names.count('engine.first_token') == 1

        # THE decomposition contract: queue + N x chunk + dispatch sums
        # to the measured TTFT (the spans tile by construction; allow
        # small float/rounding slack).
        s = doc['summary']
        assert s['outcome'] == 'ok'
        assert s['replica'] == '3'
        assert s['prefill_chunks'] == 3
        decomposed = (s['queue_wait_ms'] + s['prefill_ms'] +
                      s['dispatch_ms'])
        assert decomposed == pytest.approx(s['ttft_ms'], rel=0.02,
                                           abs=5.0)
        # The engine's own measurement and the HTTP-layer usage number
        # agree (same stamps).
        assert s['ttft_ms'] == pytest.approx(measured_ttft_ms, abs=1.0)

        # `skytpu trace <id>` against the LB renders the decomposition.
        from click.testing import CliRunner
        from skypilot_tpu.client.cli import cli
        res = CliRunner().invoke(
            cli, ['trace', rid, '--endpoint', lb.endpoint])
        assert res.exit_code == 0, res.output
        assert 'engine.prefill_chunk' in res.output
        assert re.search(r'TTFT [0-9.]+ ms = queue [0-9.]+ \+ '
                         r'3 x chunk [0-9.]+ \+ dispatch', res.output), \
            res.output
        # ...with dispatch's two parts shown inside it, not as terms.
        assert re.search(r'dispatch [0-9.]+ \[prefill wait [0-9.]+ \+ '
                         r'first-token ride [0-9.]+\] \(decomposed',
                         res.output), res.output
        assert s['prefill_wait_ms'] + s['first_token_ride_ms'] == \
            pytest.approx(s['dispatch_ms'], abs=1e-3)

        # Chrome/Perfetto export through the same endpoint.
        _, _, chrome_text = _get(
            lb.endpoint + f'/debug/requests/{rid}?format=chrome',
            timeout=10)
        chrome = json.loads(chrome_text)
        assert {e['name'] for e in chrome['traceEvents']} >= {
            'lb.proxy', 'engine.prefill_chunk', 'engine.dispatch'}

        # The federated index lists the request.
        _, _, idx_text = _get(lb.endpoint + '/debug/requests',
                              timeout=10)
        idx = json.loads(idx_text)
        assert any(s2['request_id'] == rid for s2 in idx['requests'])

        # Unknown ids 404 through the federation too.
        with pytest.raises(urllib.error.HTTPError) as err:
            _get(lb.endpoint + '/debug/requests/never-seen', timeout=10)
        assert err.value.code == 404
    finally:
        lb.stop()
        stop_replica()
        engine.stop()


def test_lb_mints_id_and_stamps_responses(tiny_engine_model):
    """Clients that send no id still get a traceable one: the LB mints
    at admission, the replica honors it, and the response carries it."""
    from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig
    from skypilot_tpu.inference.server import build_app
    from skypilot_tpu.serve.load_balancer import LoadBalancer
    from skypilot_tpu.serve.load_balancing_policies import RoundRobinPolicy
    model, params = tiny_engine_model
    engine = DecodeEngine(model, params,
                          EngineConfig(n_slots=2, prefill_buckets=(8,)))
    engine.start()
    replica_port, stop_replica = _run_app_on_thread(build_app(engine))
    url = f'http://127.0.0.1:{replica_port}'
    lb = LoadBalancer('mint-svc', _free_port(), RoundRobinPolicy(),
                      ready_urls_fn=lambda: [url],
                      ready_replicas_fn=lambda: [(1, url)])
    lb.start()
    try:
        status, headers, body = _post_json(
            lb.endpoint + '/v1/completions',
            {'prompt_ids': [1, 2, 3], 'max_tokens': 3})
        assert status == 200
        rid = headers[tracing.TRACE_HEADER]
        assert rid and body['request_id'] == rid
        _, _, text = _get(lb.endpoint + f'/debug/requests/{rid}',
                          timeout=10)
        names = [e['name'] for e in json.loads(text)['events']]
        assert 'lb.route' in names and 'engine.first_token' in names
    finally:
        lb.stop()
        stop_replica()
        engine.stop()


def test_shed_and_reject_outcomes_recorded():
    """Shed (429 at the LB) and reject (413 at the replica) leave a
    trace with the outcome, keyed by the response's request id."""
    from aiohttp import web
    from skypilot_tpu.serve.load_balancer import LoadBalancer
    from skypilot_tpu.serve.load_balancing_policies import RoundRobinPolicy

    backlog_header = metrics.BACKLOG_HEADER
    app = web.Application()

    async def work(_request):
        return web.Response(text='ok',
                            headers={backlog_header: '500'})

    app.router.add_get('/work', work)
    port, stop_replica = _run_app_on_thread(app)
    url = f'http://127.0.0.1:{port}'
    lb = LoadBalancer('shedtrace-svc', _free_port(), RoundRobinPolicy(),
                      ready_urls_fn=lambda: [url],
                      ready_replicas_fn=lambda: [(1, url)],
                      max_queue_tokens_per_replica=100)
    lb.start()
    try:
        _get(lb.endpoint + '/work')       # teaches the LB: over limit
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(
                urllib.request.Request(
                    lb.endpoint + '/work',
                    headers={tracing.TRACE_HEADER: 'shed-me'}),
                timeout=5)
        assert err.value.code == 429
        assert err.value.headers[tracing.TRACE_HEADER] == 'shed-me'
        s = tracing.decompose(tracing.events_for('shed-me'))
        assert s['outcome'] == 'shed'
    finally:
        lb.stop()
        stop_replica()


def test_replica_reject_413_recorded(tiny_engine_model):
    from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig
    from skypilot_tpu.inference.server import build_app
    model, params = tiny_engine_model
    engine = DecodeEngine(model, params,
                          EngineConfig(n_slots=2, prefill_buckets=(8,),
                                       max_prompt_len=10))
    port, stop_replica = _run_app_on_thread(build_app(engine))
    try:
        with pytest.raises(urllib.error.HTTPError) as err:
            _post_json(f'http://127.0.0.1:{port}/v1/completions',
                       {'prompt_ids': list(range(50)), 'max_tokens': 2},
                       headers={tracing.TRACE_HEADER: 'too-big'})
        assert err.value.code == 413
        s = tracing.decompose(tracing.events_for('too-big'))
        assert s['outcome'] == 'rejected'
        evt = tracing.events_for('too-big')[0]
        assert evt['attrs']['max_prompt_len'] == 10
    finally:
        stop_replica()


# ----- LB scrape-age gauge (satellite) ----------------------------------------
def test_lb_scrape_age_gauge_exported_and_pruned():
    """Every federated scrape exports skytpu_lb_scrape_age_seconds per
    replica (~0 right after a successful scrape; growing for a dark
    one), and a departed replica's series is removed."""
    from aiohttp import web
    from skypilot_tpu.serve.load_balancer import LoadBalancer
    from skypilot_tpu.serve.load_balancing_policies import RoundRobinPolicy

    app = web.Application()

    async def metrics_route(_request):
        return web.Response(text='# TYPE x gauge\nx 1\n',
                            content_type='text/plain')

    app.router.add_get('/metrics', metrics_route)
    port, stop_replica = _run_app_on_thread(app)
    url = f'http://127.0.0.1:{port}'
    ready = [(5, url)]
    lb = LoadBalancer('age-svc', _free_port(), RoundRobinPolicy(),
                      ready_urls_fn=lambda: [u for _, u in ready],
                      ready_replicas_fn=lambda: list(ready))
    lb.start()
    try:
        _get(lb.endpoint + '/metrics')
        out = metrics.render()
        m = re.search(
            r'skytpu_lb_scrape_age_seconds\{replica="5",'
            r'service="age-svc"\} ([0-9.]+)', out)
        assert m is not None, out
        assert float(m.group(1)) < 2.0          # scraped just now
        # A DARK replica (listed ready, not answering /metrics) shows a
        # growing age rather than silently vanishing.
        stop_replica()
        _get(lb.endpoint + '/metrics')
        assert re.search(r'skytpu_lb_scrape_age_seconds\{replica="5"',
                         metrics.render())
        # Replica leaves the ready set entirely: series pruned.
        ready.clear()
        _get(lb.endpoint + '/metrics')
        assert 'skytpu_lb_scrape_age_seconds' not in metrics.render()
    finally:
        lb.stop()


def test_lb_scrape_age_rebaselined_on_flap():
    """Regression: a replica that flaps ready -> notready -> ready must
    come back with a FRESH age baseline.  A scrape completion that was
    in flight when the replica left used to replant its _scrape_ok_at
    entry after the prune, so the readmitted replica inherited the dead
    incarnation's (possibly ancient) scrape success — surfacing a
    bogus multi-hour age the moment it rejoined."""
    import time as time_lib

    from aiohttp import web
    from skypilot_tpu.serve.load_balancer import LoadBalancer
    from skypilot_tpu.serve.load_balancing_policies import RoundRobinPolicy

    app = web.Application()

    async def metrics_route(_request):
        return web.Response(text='# TYPE x gauge\nx 1\n',
                            content_type='text/plain')

    app.router.add_get('/metrics', metrics_route)
    port, stop_replica = _run_app_on_thread(app)
    url = f'http://127.0.0.1:{port}'
    ready = [(7, url)]
    lb = LoadBalancer('flap-svc', _free_port(), RoundRobinPolicy(),
                      ready_urls_fn=lambda: [u for _, u in ready],
                      ready_replicas_fn=lambda: list(ready))
    lb.start()
    try:
        _get(lb.endpoint + '/metrics')          # scraped ok, age ~0
        ready.clear()
        _get(lb.endpoint + '/metrics')          # flap out: state pruned
        # Simulate the write-after-prune replant with an ancient
        # baseline (the in-handler guard now refuses this write for a
        # non-ready URL; even a survivor must not outlive readmission).
        lb._scrape_ok_at[url] = time_lib.monotonic() - 9999.0
        ready.append((7, url))
        _get(lb.endpoint + '/metrics')          # flap back in
        m = re.search(
            r'skytpu_lb_scrape_age_seconds\{replica="7",'
            r'service="flap-svc"\} ([0-9.]+)', metrics.render())
        assert m is not None, metrics.render()
        assert float(m.group(1)) < 5.0, (
            'readmitted replica inherited its previous incarnation\'s '
            f'scrape-age baseline: {m.group(1)}s')
    finally:
        lb.stop()
        stop_replica()


# ----- jobs postmortem surface (API server /debug dump) -----------------------
def test_jobs_events_dumpable_via_api_server_debug(tmp_home,
                                                   enable_all_clouds):
    """Preemption/recovery events record into the controller process's
    flight recorder; the API server's /debug dump surfaces them — the
    postmortem survives the job (and its cluster)."""
    import asyncio
    from aiohttp.test_utils import TestClient, TestServer
    from skypilot_tpu.server.app import make_app

    # The exact call sites jobs/controller.py uses.
    tracing.record_instant('job-42', 'jobs.preemption',
                           cluster='c1', cluster_status='STOPPED')
    tracing.record_instant('job-42', 'jobs.recovery',
                           reason='preemption', attempt=1, cluster='c1')

    async def drive():
        client = TestClient(TestServer(make_app()))
        await client.start_server()
        try:
            r = await client.get('/debug/requests')
            assert r.status == 200
            doc = await r.json()
            assert any(s['request_id'] == 'job-42'
                       for s in doc['requests'])
            r = await client.get('/debug/requests/job-42')
            assert r.status == 200
            names = [e['name'] for e in (await r.json())['events']]
            assert names == ['jobs.preemption', 'jobs.recovery']
        finally:
            await client.close()

    asyncio.new_event_loop().run_until_complete(drive())
