"""Disaggregated prefill/decode serving: KV-page handoff parity,
pool-aware LB routing, per-pool SLO autoscaling, spot-mixed pools.

The parity contract (the acceptance criterion): a request PREFILLED on
engine A and DECODED on engine B — its KV pages serialized, pushed and
adopted at page granularity, never recomputed per token — produces
greedy output token-identical to monolithic serving, single-device and
under the virtual tensor=2 mesh, including chunked prompts and
prefix-cache hits.  Float32 compute for the cross-engine comparisons,
per the test_serve_sharded.py precedent.

The perf contracts: zero recompiles and one device->host sync per step
hold on BOTH roles with handoff active (export is a read-only gather
synced on the caller's thread; adopt is one fixed-shape scatter).
"""
import dataclasses
import json
import threading
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.inference import kv_transfer
from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig
from skypilot_tpu.models.llama import LLAMA_CONFIGS, Llama, init_params
from skypilot_tpu.parallel.mesh import build_serve_mesh
from skypilot_tpu.server import metrics as metrics_lib
from skypilot_tpu.server import tracing

from test_observability import _free_port, _get, _run_app_on_thread
from test_serve_trace import _post_json

CFG = dataclasses.replace(LLAMA_CONFIGS['tiny'], dtype=jnp.float32)
PS = 8     # page size: divides the buckets (8, 16) and max_seq_len
_PROMPT_RNG = np.random.default_rng(23)


@pytest.fixture(scope='module')
def params():
    return init_params(Llama(CFG), jax.random.PRNGKey(0))['params']


@pytest.fixture(autouse=True)
def _reset_metrics():
    metrics_lib.reset_for_tests()
    yield
    metrics_lib.reset_for_tests()


def make_engine(params, tensor=1, **overrides):
    mesh = None
    if tensor > 1:
        mesh = build_serve_mesh(tensor, n_heads=CFG.n_heads,
                                n_kv_heads=CFG.n_kv_heads)
    kw = dict(n_slots=2, prefill_buckets=(8, 16), steps_per_call=3,
              kv_page_size=PS)
    kw.update(overrides)
    return DecodeEngine(Llama(CFG, mesh), params,
                        EngineConfig(mesh=mesh, **kw))


def run(engine, req, max_steps=2000):
    while req.finished_at is None:
        engine.step_pipelined()
        max_steps -= 1
        assert max_steps > 0, 'request never finished'
    engine.drain()
    return req.tokens()


def prompt_of(n):
    return _PROMPT_RNG.integers(1, CFG.vocab_size, n).tolist()


def handoff(a, b, prompt, max_new, request_id=None):
    """Prefill on `a`, serialize/deserialize the payload, adopt on
    `b`; returns (a's first token, b's full stream)."""
    ra = a.submit_prefill(prompt, max_new, request_id=request_id)
    first = run(a, ra)
    exported = a.export_result(ra)
    payload = kv_transfer.serialize(kv_transfer.KVHandoff(
        prompt_ids=prompt, first_token=exported['first_token'],
        max_new_tokens=max_new, page_size=PS,
        leaves=exported['leaves'], request_id=request_id))
    h = kv_transfer.deserialize(payload)
    rb = b.submit_adopt(h.prompt_ids, h.first_token, h.leaves,
                        h.max_new_tokens, request_id=request_id,
                        page_size=h.page_size)
    return first, run(b, rb)


# ----- greedy parity ----------------------------------------------------------
@pytest.mark.parametrize('plen', [7, 13, 16, 40])
def test_handoff_parity_single_device(params, plen):
    """Fused-bucket, partial-page, page-aligned and CHUNKED prompts:
    prefill-on-A + decode-on-B equals monolithic, token for token, and
    A's sampled first token heads the stream."""
    prompt = prompt_of(plen)
    mono = make_engine(params)
    ref = run(mono, mono.submit(prompt, 12))
    a, b = make_engine(params), make_engine(params)
    first, out = handoff(a, b, prompt, 12)
    assert first == [ref[0]]
    assert out == ref


def test_handoff_parity_prefix_hit(params):
    """A prompt that HITS A's radix cache (its prefix pages were
    published by an earlier request) hands off with identical output —
    the exported pages are the shared ones plus the fresh suffix."""
    shared = prompt_of(16)
    tails = [prompt_of(5), prompt_of(5)]
    mono = make_engine(params)
    refs = [run(mono, mono.submit(shared + t, 10)) for t in tails]
    a, b = make_engine(params), make_engine(params)
    run(a, a.submit(shared + tails[0], 10))   # publishes shared pages
    first, out = handoff(a, b, shared + tails[1], 10)
    assert out == refs[1]
    assert first == [refs[1][0]]
    # The handoff actually rode the hit path (pages referenced, their
    # prefill skipped), not a silent full prefill.
    assert 'skytpu_engine_prefix_cache_hits_total' in \
        metrics_lib.render()


def test_handoff_parity_tensor2(params):
    """Mesh-sharded engines (virtual tensor=2): export gathers the
    kv-head-sharded pool to a replicated payload, adopt scatters it
    back under the committed shardings — still token-identical,
    chunked prompt included."""
    for plen in (13, 40):
        prompt = prompt_of(plen)
        mono = make_engine(params, tensor=2)
        ref = run(mono, mono.submit(prompt, 10))
        single = make_engine(params)
        assert run(single, single.submit(prompt, 10)) == ref
        a = make_engine(params, tensor=2)
        b = make_engine(params, tensor=2)
        first, out = handoff(a, b, prompt, 10)
        assert first == [ref[0]]
        assert out == ref


def test_handoff_across_topologies(params):
    """Prefill single-device, decode tensor=2 (heterogeneous pools —
    ThunderServe's chip-type lever): the payload is topology-neutral
    numpy, so the output still matches."""
    prompt = prompt_of(13)
    mono = make_engine(params)
    ref = run(mono, mono.submit(prompt, 10))
    a = make_engine(params)
    b = make_engine(params, tensor=2)
    _, out = handoff(a, b, prompt, 10)
    assert out == ref


# ----- payload wire format ----------------------------------------------------
def test_payload_roundtrip_and_integrity(params):
    a = make_engine(params)
    prompt = prompt_of(13)
    ra = a.submit_prefill(prompt, 9)
    run(a, ra)
    exported = a.export_result(ra)
    payload = kv_transfer.serialize(kv_transfer.KVHandoff(
        prompt_ids=prompt, first_token=exported['first_token'],
        max_new_tokens=9, page_size=PS, leaves=exported['leaves'],
        request_id='rt-1'))
    h = kv_transfer.deserialize(payload)
    assert h.prompt_ids == prompt
    assert h.max_new_tokens == 9
    assert h.page_size == PS
    assert h.request_id == 'rt-1'
    assert h.n_kv_pages == -(-len(prompt) // PS)
    for got, want in zip(h.leaves, exported['leaves']):
        np.testing.assert_array_equal(got, want)
    # Corruption fails loudly — a bad transfer must never scatter
    # garbage into a live pool.
    flipped = bytearray(payload)
    flipped[-1] ^= 0xFF
    with pytest.raises(ValueError, match='checksum'):
        kv_transfer.deserialize(bytes(flipped))
    with pytest.raises(ValueError, match='truncated'):
        kv_transfer.deserialize(payload[:len(payload) - 8])
    with pytest.raises(ValueError, match='magic'):
        kv_transfer.deserialize(b'NOPE' + payload)


def test_adopt_geometry_validation(params):
    b = make_engine(params)
    leaves = [np.zeros((2, CFG.n_kv_heads, PS,
                        CFG.dim // CFG.n_heads), np.float32)]
    with pytest.raises(ValueError, match='page size'):
        b.submit_adopt(prompt_of(13), 1, leaves, 8, page_size=PS * 2)
    with pytest.raises(ValueError, match='does not cover'):
        b.submit_adopt(prompt_of(30), 1, leaves, 8, page_size=PS)
    # Model mismatch between pools must 422 at submit, not crash the
    # engine loop mid-scatter: wrong leaf COUNT (different layer
    # count) and wrong per-page SHAPE (different heads/head_dim) are
    # both rejected with the geometry named.
    with pytest.raises(ValueError, match='cache leaves'):
        b.submit_adopt(prompt_of(13), 1, leaves, 8, page_size=PS)
    pool_leaves = jax.tree_util.tree_leaves(b._cache)
    bad_shape = [np.zeros((2, leaf.shape[1] * 2, PS, leaf.shape[3]),
                          np.float32) for leaf in pool_leaves]
    with pytest.raises(ValueError, match='page shape'):
        b.submit_adopt(prompt_of(13), 1, bad_shape, 8, page_size=PS)
    bad_dtype = [np.zeros((2,) + tuple(leaf.shape[1:]), np.float16)
                 for leaf in pool_leaves]
    with pytest.raises(ValueError, match='dtype'):
        b.submit_adopt(prompt_of(13), 1, bad_dtype, 8, page_size=PS)
    unpaged = make_engine(b.params, kv_page_size=None)
    with pytest.raises(RuntimeError, match='paged'):
        unpaged.submit_adopt(prompt_of(13), 1, leaves, 8)
    with pytest.raises(RuntimeError, match='paged'):
        unpaged.submit_prefill(prompt_of(13), 8)


# ----- perf contracts ---------------------------------------------------------
def test_zero_recompiles_with_handoff_active(params):
    """Export and adopt are each ONE compiled shape: after a warmup
    handoff, arbitrary mixed traffic (handoffs of several lengths +
    local requests) adds no jit-cache entries on either role."""
    a, b = make_engine(params), make_engine(params)
    handoff(a, b, prompt_of(13), 6)           # warm every program,
    handoff(a, b, prompt_of(40), 4)           # chunked shape included
    run(a, a.submit(prompt_of(7), 4))
    run(b, b.submit(prompt_of(7), 4))
    fns = [a._prefill_insert, a._decode, a._chunk_insert,
           a._export_pages, b._decode, b._adopt_insert]
    sizes = [f._cache_size() for f in fns]
    handoff(a, b, prompt_of(7), 5)
    handoff(a, b, prompt_of(16), 6)
    handoff(a, b, prompt_of(40), 5)           # chunked prefill
    run(a, a.submit(prompt_of(12), 4))
    run(b, b.submit(prompt_of(12), 4))
    assert [f._cache_size() for f in fns] == sizes


def test_one_sync_per_step_with_handoff(params, monkeypatch):
    """Handoff adds ZERO loop-thread syncs: adopt ships host->device
    only, export is dispatch-only (the device->host copy happens in
    export_result on the CALLER's thread).  np.asarray — the engine's
    one sync — is called exactly once per active step on both
    roles."""
    from skypilot_tpu.inference import engine as engine_mod

    class CountingNp:
        def __init__(self, real):
            self._real = real
            self.asarray_calls = 0

        def __getattr__(self, name):
            return getattr(self._real, name)

        def asarray(self, *args, **kwargs):
            self.asarray_calls += 1
            return self._real.asarray(*args, **kwargs)

    a, b = make_engine(params), make_engine(params)
    handoff(a, b, prompt_of(13), 6)           # warm programs first
    counting = CountingNp(np)
    monkeypatch.setattr(engine_mod, 'np', counting)

    # Prefill role (synchronous step(): every active step fetches
    # exactly once): submit_prefill adds NO loop-thread sync — the
    # export gather is dispatch-only.
    ra = a.submit_prefill(prompt_of(13), 6)
    a_active = 0
    for _ in range(100):
        if a.step():
            a_active += 1
        if ra.finished_at is not None:
            break
    assert ra.finished_at is not None
    assert counting.asarray_calls == a_active
    # The device->host copy happens HERE, on the caller's thread.
    exported = a.export_result(ra)
    adopt_base = counting.asarray_calls
    assert adopt_base > a_active              # export synced off-loop
    # Decode role: adopt ships host->device only; decode keeps its one
    # fetch per active step.
    rb = b.submit_adopt(ra.prompt_ids, exported['first_token'],
                        exported['leaves'], 6)
    b_active = 0
    for _ in range(100):
        if b.step():
            b_active += 1
        if rb.finished_at is not None:
            break
    assert rb.finished_at is not None
    assert counting.asarray_calls - adopt_base == b_active
    monkeypatch.undo()


# ----- e2e through a real LB + two role servers -------------------------------
def test_e2e_disagg_through_lb(params):
    """THE acceptance path: a real LoadBalancer in front of a PREFILL
    server and a DECODE server (build_app role wiring).  A completion
    POSTed to the LB routes into the prefill pool, its KV pages push
    to the decode replica, and the relayed output is token-identical
    to monolithic serving; the flight recorder shows the
    kv_export/kv_adopt spans end to end."""
    from skypilot_tpu.inference.server import build_app
    from skypilot_tpu.serve.load_balancer import LoadBalancer
    from skypilot_tpu.serve.load_balancing_policies import (
        LeastLoadPolicy)
    tracing.reset_for_tests()
    prompt = prompt_of(13)
    mono = make_engine(params)
    ref = run(mono, mono.submit(prompt, 8))

    pre, dec = make_engine(params), make_engine(params)
    pre.start()
    dec.start()
    pre_port, stop_pre = _run_app_on_thread(build_app(pre,
                                                      role='prefill'))
    dec_port, stop_dec = _run_app_on_thread(build_app(dec,
                                                      role='decode'))
    pre_url = f'http://127.0.0.1:{pre_port}'
    dec_url = f'http://127.0.0.1:{dec_port}'
    lb = LoadBalancer(
        'disagg-svc', _free_port(), LeastLoadPolicy(),
        ready_urls_fn=lambda: [pre_url, dec_url],
        ready_replicas_fn=lambda: [(1, pre_url, 'prefill'),
                                   (2, dec_url, 'decode')])
    lb.start()
    try:
        rid = 'disagg-e2e-1'
        status, headers, body = _post_json(
            lb.endpoint + '/v1/completions',
            {'prompt_ids': prompt, 'max_tokens': 8},
            headers={tracing.TRACE_HEADER: rid})
        assert status == 200
        assert body['ids'] == ref
        assert body['disaggregated'] is True
        assert body['decode_url'] == dec_url
        assert headers[tracing.TRACE_HEADER] == rid
        # Both engines really played their role.
        out = metrics_lib.render()
        assert 'skytpu_engine_kv_exports_total 1.0' in out
        assert 'skytpu_engine_kv_adopts_total 1.0' in out
        assert ('skytpu_lb_kv_transfer_total{outcome="ok"} 1.0'
                in out)
        # One trace id tells the whole story across LB + both roles.
        _, _, text = _get(lb.endpoint + f'/debug/requests/{rid}',
                          timeout=10)
        names = [e['name'] for e in json.loads(text)['events']]
        for needle in ('lb.admission', 'lb.route', 'engine.kv_export',
                       'engine.kv_adopt', 'engine.first_token'):
            assert needle in names, (needle, names)
        # Health reports the role (the replica manager's probe view).
        assert json.loads(_get(pre_url + '/health')[2])['role'] == \
            'prefill'
        # A second, CHUNKED request through the same path.
        long_prompt = prompt_of(40)
        mono2 = make_engine(params)
        ref2 = run(mono2, mono2.submit(long_prompt, 6))
        status, _, body = _post_json(
            lb.endpoint + '/v1/completions',
            {'prompt_ids': long_prompt, 'max_tokens': 6})
        assert status == 200
        assert body['ids'] == ref2
    finally:
        lb.stop()
        stop_pre()
        stop_dec()
        pre.stop()
        dec.stop()


def test_push_failover_and_monolithic_fallback(params):
    """Re-route, then re-prefill: a dead PRIMARY decode candidate
    fails over to the fallback candidate with the SAME payload (one
    bounded push, no re-prefill); with EVERY candidate dead the
    prefill replica serves the request monolithically itself."""
    from skypilot_tpu.inference.server import build_app
    prompt = prompt_of(13)
    mono = make_engine(params)
    ref = run(mono, mono.submit(prompt, 8))
    pre, dec = make_engine(params), make_engine(params)
    pre.start()
    dec.start()
    pre_port, stop_pre = _run_app_on_thread(build_app(pre,
                                                      role='prefill'))
    dec_port, stop_dec = _run_app_on_thread(build_app(dec,
                                                      role='decode'))
    dead = f'http://127.0.0.1:{_free_port()}'
    dec_url = f'http://127.0.0.1:{dec_port}'
    try:
        # Dead primary, live fallback: served disaggregated anyway.
        status, _, body = _post_json(
            f'http://127.0.0.1:{pre_port}/v1/completions',
            {'prompt_ids': prompt, 'max_tokens': 8},
            headers={kv_transfer.DECODE_URL_HEADER:
                     f'{dead},{dec_url}'})
        assert status == 200
        assert body['ids'] == ref
        assert body['disaggregated'] is True
        assert body['decode_url'] == dec_url
        out = metrics_lib.render()
        assert 'skytpu_lb_kv_transfer_total{outcome="error"} 1.0' in out
        assert 'skytpu_lb_kv_transfer_total{outcome="ok"} 1.0' in out
        # Every candidate dead: monolithic fallback, same tokens (the
        # re-prefill hits the prefix cache the export donated to).
        status, _, body = _post_json(
            f'http://127.0.0.1:{pre_port}/v1/completions',
            {'prompt_ids': prompt, 'max_tokens': 8},
            headers={kv_transfer.DECODE_URL_HEADER: dead})
        assert status == 200
        assert body['ids'] == ref
        assert 'disaggregated' not in body
    finally:
        stop_pre()
        stop_dec()
        pre.stop()
        dec.stop()


# ----- LB pool routing & shedding --------------------------------------------
def _fake_role_replica(state, name):
    """Role-replica double: /v1/completions records the decode-url
    header it saw; /metrics exports the backlog gauge."""
    from aiohttp import web
    app = web.Application()

    async def completions(request):
        state.setdefault('hits', []).append(
            (name, request.headers.get(kv_transfer.DECODE_URL_HEADER)))
        return web.json_response(
            {'ids': [1], 'served_by': name},
            headers={metrics_lib.BACKLOG_HEADER:
                     str(state.get(f'{name}_backlog', 0.0))})

    async def metrics_route(_request):
        return web.Response(
            text=('# TYPE skytpu_engine_queued_prefill_tokens gauge\n'
                  f'skytpu_engine_queued_prefill_tokens '
                  f'{state.get(f"{name}_backlog", 0.0)}\n'),
            content_type='text/plain')

    app.router.add_post('/v1/completions', completions)
    app.router.add_get('/metrics', metrics_route)
    return app


def test_lb_routes_pools_and_sheds_on_prefill_backlog_only():
    """Pool-aware routing: completions land on the PREFILL replica
    with the decode candidate stamped; the shed check consults only
    the prefill pool — an idle decode pool cannot fail it open."""
    from skypilot_tpu.serve.load_balancer import LoadBalancer
    from skypilot_tpu.serve.load_balancing_policies import (
        RoundRobinPolicy)
    state = {}
    pre_port, stop_pre = _run_app_on_thread(
        _fake_role_replica(state, 'pre'))
    dec_port, stop_dec = _run_app_on_thread(
        _fake_role_replica(state, 'dec'))
    pre_url = f'http://127.0.0.1:{pre_port}'
    dec_url = f'http://127.0.0.1:{dec_port}'
    lb = LoadBalancer(
        'pool-svc', _free_port(), RoundRobinPolicy(),
        ready_urls_fn=lambda: [pre_url, dec_url],
        ready_replicas_fn=lambda: [(1, pre_url, 'prefill'),
                                   (2, dec_url, 'decode')],
        max_queue_tokens_per_replica=100)
    lb.start()
    try:
        for _ in range(3):
            status, _, body = _post_json(
                lb.endpoint + '/v1/completions', {'prompt': 'x'})
            assert status == 200
            assert body['served_by'] == 'pre'
        assert all(name == 'pre' and dec_url in (header or '')
                   for name, header in state['hits'])
        # Prefill backlog over the limit; decode idle at 0.  Shedding
        # consults ONLY the prefill pool -> 429 despite the fresh
        # under-limit decode observation.
        state['pre_backlog'] = 500.0
        _get(lb.endpoint + '/metrics')        # refresh both gauges
        with pytest.raises(urllib.error.HTTPError) as err:
            _post_json(lb.endpoint + '/v1/completions',
                       {'prompt': 'x'})
        assert err.value.code == 429
    finally:
        lb.stop()
        stop_pre()
        stop_dec()


def test_lb_degrades_without_a_decode_pool():
    """Decode pool empty (preemption churn, bring-up): traffic routes
    to whatever is ready WITHOUT a decode-candidate header — the
    prefill replica serves monolithically instead of 503ing."""
    from skypilot_tpu.serve.load_balancer import LoadBalancer
    from skypilot_tpu.serve.load_balancing_policies import (
        RoundRobinPolicy)
    state = {}
    pre_port, stop_pre = _run_app_on_thread(
        _fake_role_replica(state, 'pre'))
    pre_url = f'http://127.0.0.1:{pre_port}'
    lb = LoadBalancer(
        'halfpool-svc', _free_port(), RoundRobinPolicy(),
        ready_urls_fn=lambda: [pre_url],
        ready_replicas_fn=lambda: [(1, pre_url, 'prefill')])
    lb.start()
    try:
        status, _, _ = _post_json(lb.endpoint + '/v1/completions',
                                  {'prompt': 'x'})
        assert status == 200
        assert state['hits'] == [('pre', None)]
    finally:
        lb.stop()
        stop_pre()


# ----- spec plumbing ----------------------------------------------------------
def test_disagg_spec_roundtrip_and_validation():
    from skypilot_tpu import exceptions
    from skypilot_tpu.serve.service_spec import ServiceSpec
    cfg = {
        'readiness_probe': '/health',
        'kv_page_size': 64,
        'disaggregation': {
            'prefill_replicas': 2, 'decode_replicas': 4,
            'decode_max_replicas': 8, 'use_spot_decode': True,
            'spot_headroom': 2,
        },
    }
    spec = ServiceSpec.from_yaml_config(cfg)
    d = spec.disaggregation
    assert (d.prefill_replicas, d.decode_replicas) == (2, 4)
    assert d.max_for('decode') == 8
    assert d.max_for('prefill') == 2          # fixed pool: max == base
    assert d.use_spot('decode') and not d.use_spot('prefill')
    assert d.spot_headroom == 2
    spec2 = ServiceSpec.from_yaml_config(spec.to_yaml_config())
    assert spec2.disaggregation == d
    # Pages are the transfer unit: no paging, no disaggregation.
    with pytest.raises(exceptions.InvalidTaskError,
                       match='kv_page_size'):
        ServiceSpec.from_yaml_config({
            'readiness_probe': '/health',
            'disaggregation': {'prefill_replicas': 1,
                               'decode_replicas': 1}})
    with pytest.raises(exceptions.InvalidTaskError,
                       match='decode_max_replicas'):
        ServiceSpec.from_yaml_config({
            'readiness_probe': '/health', 'kv_page_size': 64,
            'disaggregation': {'prefill_replicas': 1,
                               'decode_replicas': 4,
                               'decode_max_replicas': 2}})


def test_replica_manager_stamps_role_env(tmp_home):
    """The replica task carries SKYTPU_SERVE_ROLE (the inference
    server's --role default) and per-pool spot placement follows the
    disaggregation spec, not the task's use_spot."""
    from skypilot_tpu import task as task_lib
    from skypilot_tpu.serve.replica_managers import (ENV_REPLICA_ROLE,
                                                     ReplicaManager)
    from skypilot_tpu.serve.service_spec import ServiceSpec
    spec = ServiceSpec.from_yaml_config({
        'readiness_probe': '/health', 'kv_page_size': 64,
        'disaggregation': {'prefill_replicas': 1,
                           'decode_replicas': 1,
                           'use_spot_decode': True}})
    task = task_lib.Task('svc', run='echo hi')
    mgr = ReplicaManager('role-svc', spec, task)
    rt = mgr._replica_task(1, 8080, None, False, role='prefill')
    assert rt.envs[ENV_REPLICA_ROLE] == 'prefill'
    assert mgr._next_is_spot('decode') is True
    assert mgr._next_is_spot('prefill') is False


# ----- per-pool autoscaling ---------------------------------------------------
def _exposition(ttft_s, tpot_s, n=200.0, backlog=0.0):
    """Synthetic federated scrape with every request at the given
    latencies (slo_sim's observe logic, inlined)."""
    import math
    lines = []
    for fam, val in ((metrics_lib.ENGINE_TPOT_FAMILY, tpot_s),
                     (metrics_lib.ENGINE_TTFT_FAMILY, ttft_s)):
        lines.append(f'# TYPE {fam} histogram')
        cum = 0.0
        for b in metrics_lib.buckets_for(fam):
            if val <= b:
                cum = n
            lines.append(f'{fam}_bucket{{le="{repr(float(b))}"}} {cum}')
        lines.append(f'{fam}_bucket{{le="+Inf"}} {n}')
    fam = metrics_lib.QUEUED_PREFILL_TOKENS_FAMILY
    lines.append(f'# TYPE {fam} gauge')
    lines.append(f'{fam} {backlog}')
    del math
    return '\n'.join(lines) + '\n'


def _make_pool_autoscaler(spot_headroom=0):
    from skypilot_tpu.serve.autoscalers import Autoscaler
    from skypilot_tpu.serve.service_spec import ServiceSpec
    spec = ServiceSpec.from_yaml_config({
        'readiness_probe': '/health', 'kv_page_size': 64,
        'max_queue_tokens_per_replica': 1000,
        'replica_policy': {
            'min_replicas': 1, 'max_replicas': 8,
            'target_qps_per_replica': 100.0,
            'target_ttft_ms': 200.0, 'target_tpot_ms': 20.0,
            'upscale_delay_seconds': 10.0,
            'downscale_delay_seconds': 10.0,
        },
        'disaggregation': {
            'prefill_replicas': 2, 'decode_replicas': 2,
            'prefill_max_replicas': 8, 'decode_max_replicas': 8,
            'use_spot_decode': bool(spot_headroom),
            'spot_headroom': spot_headroom,
        },
    })
    auto = Autoscaler.make(spec, decision_interval_seconds=10.0)
    assert auto.is_pool_autoscaler and auto.wants_lb_scrape
    return auto


def _feed(auto, ttft_s, tpot_s, live_p=2, live_d=2, backlog=0.0,
          now0=1000.0, requests0=100):
    """Two scrapes with growing cumulative counts: the windowed
    histograms measure DELTAS, so the first scrape is baseline only."""
    auto.evaluate_pools(
        _exposition(ttft_s, tpot_s, n=200.0, backlog=backlog),
        requests0, live_p, live_d, now=now0)
    return auto.evaluate_pools(
        _exposition(ttft_s, tpot_s, n=400.0, backlog=backlog),
        requests0 + 20, live_p, live_d, now=now0 + 10.0)


def test_ttft_violation_scales_prefill_only():
    auto = _make_pool_autoscaler()
    d = _feed(auto, ttft_s=0.5, tpot_s=0.005)
    assert d.prefill.delta == 1
    assert d.decode.delta == 0


def test_tpot_violation_scales_decode_only():
    auto = _make_pool_autoscaler()
    d = _feed(auto, ttft_s=0.05, tpot_s=0.08)
    assert d.prefill.delta == 0
    assert d.decode.delta == 1


def test_prefill_backlog_scales_prefill_pool():
    """Suppressed demand (the LB shedding on prefill backlog) argues
    for prefill capacity even while admitted-request latency looks
    healthy."""
    auto = _make_pool_autoscaler()
    d = _feed(auto, ttft_s=0.05, tpot_s=0.005, backlog=5000.0)
    # Backlog pressure argues every tick it persists: one replica per
    # evaluated scrape, decode untouched.
    assert d.prefill.delta >= 1
    assert d.decode.delta == 0


def test_spot_headroom_held_above_target_and_restored():
    """A spot decode pool holds `spot_headroom` extra replicas; after
    a preemption the next decision's positive delta IS the lightweight
    re-plan."""
    auto = _make_pool_autoscaler(spot_headroom=1)
    d = _feed(auto, ttft_s=0.05, tpot_s=0.005, live_d=3)
    assert d.decode.target_num_replicas == 3   # 2 target + 1 headroom
    assert d.decode.delta == 0
    d = auto.evaluate_pools(
        _exposition(ttft_s=0.05, tpot_s=0.005, n=600.0),
        140, 2, 2, now=1020.0)                 # one preempted
    assert d.decode.delta == 1                 # re-plan restores it


def test_scale_down_needs_projection_headroom():
    """Comfortable latency shrinks a pool only when the projected p95
    at the smaller size still clears the target with margin."""
    auto = _make_pool_autoscaler()
    # p95 tpot ~5 ms, target 20: the projection at the smaller size
    # clears the 0.8-margin target, so the pool may shrink toward its
    # floor of 2 — never below it.
    d = None
    for i, now in enumerate((1000.0, 1010.0, 1020.0)):
        d = auto.evaluate_pools(
            _exposition(ttft_s=0.05, tpot_s=0.005,
                        n=200.0 * (i + 1)),
            100 + 10 * i, 2, 4, now=now)
    assert d.decode.target_num_replicas >= 2
    assert d.decode.target_num_replicas < 4


# ----- the canonical scenario (slo_sim's DISAGG_* constants) -------------------
def test_disagg_sim_beats_monolithic_and_survives_preemption():
    """On the simulator's phase-cost model: at the peak the monolithic
    pool breaks the TPOT target where some split of the same chips
    meets both targets; under the per-pool autoscaler an injected
    decode-pool preemption mid-plateau does not breach the TPOT target
    (and the re-plan restores the pool), while a pool sized without
    headroom WOULD breach.  Both directions."""
    from skypilot_tpu.serve import slo_sim
    chips = slo_sim.DISAGG_TOTAL_CHIPS
    peak = slo_sim.DISAGG_PEAK_QPS
    target_ttft_ms = slo_sim.DISAGG_TARGET_TTFT_MS
    target_tpot_ms = slo_sim.DISAGG_TARGET_TPOT_MS
    svc = slo_sim.make_disagg_service()

    def ttft_ms(prefill, decode):
        return svc.latencies_pools(peak, prefill, decode)[0] * 1e3

    def tpot_ms(prefill, decode):
        return svc.latencies_pools(peak, prefill, decode)[1] * 1e3

    assert svc.latencies_monolithic(peak, chips)[1] * 1e3 > target_tpot_ms
    assert any(ttft_ms(p, chips - p) <= target_ttft_ms and
               tpot_ms(p, chips - p) <= target_tpot_ms
               for p in range(1, chips))

    ramp = slo_sim.disagg_ramp(6)
    preempt_tick = len(ramp) - 3
    hist = slo_sim.run_disagg_ramp(
        slo_sim.make_disagg_autoscaler(spot_headroom=1),
        slo_sim.make_disagg_service(), ramp, preempt_tick=preempt_tick)
    assert max(t for _, _, _, _, t in hist[preempt_tick:]) <= target_tpot_ms
    assert hist[preempt_tick + 1][2] >= hist[preempt_tick][2] + 1
    # Counterfactual, static by construction: a decode pool sized
    # EXACTLY to its target (the smallest that meets it at the peak, no
    # spot headroom) breaches the moment one replica is preempted.
    d_slo = next(d for d in range(1, chips + 1)
                 if tpot_ms(2, d) <= target_tpot_ms)
    assert tpot_ms(2, max(1, d_slo - 1)) > target_tpot_ms


def test_phase_latency_model_decouples_pools():
    """slo_sim phase costs: colocated phases degrade each other
    (processor sharing); dedicated pools reduce to the independent
    knee model."""
    from skypilot_tpu.serve import slo_sim
    svc = slo_sim.make_disagg_service()
    q = slo_sim.DISAGG_PEAK_QPS
    mono_ttft, mono_tpot = svc.latencies_monolithic(q, 8)
    dis_ttft, dis_tpot = svc.latencies_pools(q, 2, 6)
    assert dis_tpot < mono_tpot                # decode isolated
    assert dis_tpot == pytest.approx(
        slo_sim.DISAGG_COSTS.base_tpot_s)      # under the knee
    assert dis_ttft < mono_ttft
    # Handoff cost is charged on the disagg TTFT path only.
    base_only, _ = svc.latencies_pools(0.001, 2, 6)
    assert base_only == pytest.approx(
        slo_sim.DISAGG_COSTS.base_ttft_s +
        slo_sim.DISAGG_COSTS.handoff_s)
