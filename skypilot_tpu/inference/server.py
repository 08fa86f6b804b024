"""HTTP completions server over the decode engine.

The serve replica workload (analog of the reference's JetStream server
launched by examples/tpu/v6e/serve-llama2-7b.yaml).  Routes:

- GET  /health        -> 200 once the engine thread is up (readiness
                         probes from serve's replica manager hit this).
- GET  /metrics       -> Prometheus exposition: engine TTFT /
                         inter-token-latency histograms, token counters,
                         occupancy/queue gauges.  The serve load
                         balancer scrapes this per replica and federates
                         the series under a replica="<id>" label.
- POST /v1/completions  {"prompt": "...", "max_tokens": N} or
                        {"prompt_ids": [...], "max_tokens": N}
                        -> {"ids": [...], "text": "...", "usage": {...}}
                        Prompts longer than the largest prefill bucket
                        are admitted via chunked prefill (up to
                        max_prompt_len, default max_seq_len - 1); a
                        prompt beyond that limit gets 413 with the
                        limit in the body.
- POST /v1/kv_adopt     Disaggregated serving: a prefill replica's
                        KV-handoff payload (inference/kv_transfer.py
                        binary format).  The engine adopts the pages
                        into its own pool and decodes; the response is
                        the SAME completion JSON /v1/completions
                        returns, so the prefill replica can relay it
                        verbatim.

Roles (`--role`, env SKYTPU_SERVE_ROLE): `monolithic` (default) serves
each request end to end.  A `prefill` replica, when the serve LB
stamps X-Skytpu-Decode-Url with decode-pool candidates, runs only the
prefill phase and PUSHES the paged KV + sampled first token to the
first candidate that accepts (bounded timeout; a dead candidate fails
over to the next — the payload is re-routed, never re-prefilled).  If
every candidate fails it falls back to serving monolithically, and the
re-prefill hits its own prefix cache (the prompt pages were donated at
export).  A `decode` replica accepts /v1/kv_adopt.  Both roles run the
full engine, so a mis-routed request still completes.
- GET  /debug/requests        -> flight-recorder summaries (recent
                         request ids + their span names).
- GET  /debug/requests/<id>   -> one request's span events + TTFT
                         decomposition (`?format=chrome` exports the
                         Chrome-trace/Perfetto document).  This is what
                         `skytpu trace <id>` renders.

Every response carries `X-Skytpu-Queued-Prefill-Tokens` (the engine's
queued-prefill-token backlog — same value as the gauge): the serve LB
reads it for free on the proxy path and feeds queue-aware admission
control and least_load routing.  Every response also carries
`X-Skytpu-Request-Id` — honored from the request when the client (or
the serve LB, which mints one at admission) sent it, minted here
otherwise — and the id keys the request's span events in the always-on
flight recorder (server/tracing.py; ring size via
SKYTPU_TRACE_RING_SIZE).

Text prompts use a byte-level tokenizer (token id = byte value), which is
model-agnostic and dependency-free; real deployments pass `prompt_ids`
from their own tokenizer.
"""
from __future__ import annotations

import argparse
import asyncio
import os
from typing import List

import aiohttp
from aiohttp import web

from skypilot_tpu import sky_logging
from skypilot_tpu.inference import kv_transfer
from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig
from skypilot_tpu.perf import profiler as profiler_lib
from skypilot_tpu.server import metrics as metrics_lib
from skypilot_tpu.server import tracing

logger = sky_logging.init_logger(__name__)


def encode_bytes(text: str) -> List[int]:
    return list(text.encode('utf-8'))


def decode_bytes(ids: List[int]) -> str:
    return bytes(i for i in ids if 0 <= i < 256).decode('utf-8',
                                                        errors='replace')


# Engine backlog stamped on every response: queued prefill tokens.  The
# serve load balancer reads it for free on the proxy response path and
# feeds queue-aware admission control + least_load routing (shared
# constant: server/metrics.py owns the cross-process names).
BACKLOG_HEADER = metrics_lib.BACKLOG_HEADER


def build_app(engine: DecodeEngine,
              role: str = 'monolithic') -> web.Application:
    # One pooled client session for KV-handoff pushes, created lazily
    # on the app's own event loop and closed with the app.
    _state = {'session': None}

    def _session() -> aiohttp.ClientSession:
        if _state['session'] is None or _state['session'].closed:
            _state['session'] = aiohttp.ClientSession()
        return _state['session']

    async def _close_session(_app):
        if _state['session'] is not None and not _state['session'].closed:
            await _state['session'].close()

    @web.middleware
    async def stamp_backlog(request: web.Request, handler):
        # Honor the caller's request id (the serve LB mints one at
        # admission) or mint one here, so every request is traceable
        # even library-direct; stamped on the response so the client
        # always learns the id to `skytpu trace`.
        rid = request.headers.get(tracing.TRACE_HEADER) or \
            tracing.mint_request_id()
        request['skytpu_request_id'] = rid
        resp = await handler(request)
        resp.headers[BACKLOG_HEADER] = str(engine.queued_prefill_tokens)
        resp.headers[tracing.TRACE_HEADER] = rid
        return resp

    # aiohttp's default client_max_size is 1 MiB — a KV-handoff
    # payload (layer-major pages of a real model) is tens to hundreds
    # of MB, so the default would 413 every /v1/kv_adopt and silently
    # degrade disaggregation to permanent monolithic fallback.
    max_payload = int(os.environ.get('SKYTPU_SERVE_MAX_PAYLOAD_BYTES',
                                     str(2 * 1024 ** 3)))
    app = web.Application(middlewares=[stamp_backlog],
                          client_max_size=max_payload)

    async def health(_request):
        if not engine.healthy:
            return web.json_response(
                {'status': 'error', 'error': repr(engine.error),
                 'role': role}, status=503)
        return web.json_response({'status': 'ok', 'role': role})

    def _completion_json(rid, ids, out, req):
        return {
            'ids': out,
            'text': decode_bytes(out),
            'request_id': rid,
            'usage': {
                'prompt_tokens': len(ids),
                'completion_tokens': len(out),
                'ttft_ms': round(
                    (req.first_token_at - req.submitted_at) * 1e3, 2)
                if req.first_token_at else None,
            },
        }

    async def _serve_monolithic(ids, max_tokens, rid):
        try:
            req = engine.submit(ids, max_tokens, request_id=rid)
        except ValueError as e:
            # Admission rejection: the prompt exceeds max_prompt_len
            # (engine message carries the limit).  413, not 400 — the
            # request was well-formed, just too large; clients can read
            # the limit and re-chunk.
            tracing.record_instant(rid, 'server.reject', status=413,
                                   prompt_tokens=len(ids),
                                   max_prompt_len=engine.max_prompt_len)
            return web.json_response(
                {'error': str(e),
                 'max_prompt_len': engine.max_prompt_len}, status=413)
        out = await asyncio.get_event_loop().run_in_executor(
            None, req.tokens)
        return web.json_response(_completion_json(rid, ids, out, req))

    def _export_payload(req, ids, max_tokens, rid):
        """Executor-thread half of a handoff: the device->host copy of
        the gathered pages plus serialization — never on the event
        loop, never on the engine loop."""
        exported = engine.export_result(req)
        return kv_transfer.serialize(kv_transfer.KVHandoff(
            prompt_ids=ids,
            first_token=exported['first_token'],
            max_new_tokens=max_tokens,
            page_size=engine.cfg.kv_page_size,
            leaves=exported['leaves'],
            request_id=rid))

    async def _serve_prefill_handoff(ids, max_tokens, rid, targets):
        """Prefill role: run the prefill phase locally, push the KV
        pages + first token to a decode candidate, relay its
        completion.  Every failure falls back one level: next decode
        candidate, then monolithic serving on this replica (whose
        re-prefill hits the prefix cache — export donated the prompt
        pages)."""
        loop = asyncio.get_event_loop()
        try:
            req = engine.submit_prefill(ids, max_tokens, request_id=rid)
        except ValueError as e:
            tracing.record_instant(rid, 'server.reject', status=413,
                                   prompt_tokens=len(ids),
                                   max_prompt_len=engine.max_prompt_len)
            return web.json_response(
                {'error': str(e),
                 'max_prompt_len': engine.max_prompt_len}, status=413)
        await loop.run_in_executor(None, req.tokens)
        if req.kv_export is None:
            # Engine died mid-prefill; serve the error like any other.
            return web.json_response(
                {'error': f'prefill failed: {engine.error!r}'},
                status=503)
        payload = await loop.run_in_executor(
            None, _export_payload, req, ids, max_tokens, rid)
        body, url = await kv_transfer.push(_session(), targets, payload,
                                           request_id=rid)
        if body is not None:
            body['request_id'] = rid
            body['disaggregated'] = True
            body['decode_url'] = url
            return web.json_response(body)
        logger.warning(f'every decode candidate failed for {rid}; '
                       f'serving monolithically')
        return await _serve_monolithic(ids, max_tokens, rid)

    async def completions(request):
        try:
            body = await request.json()
        except Exception:  # pylint: disable=broad-except
            return web.json_response({'error': 'invalid JSON'}, status=400)
        ids = body.get('prompt_ids')
        if ids is None:
            prompt = body.get('prompt')
            if not isinstance(prompt, str):
                return web.json_response(
                    {'error': 'need "prompt" or "prompt_ids"'}, status=400)
            ids = encode_bytes(prompt)
        max_tokens = int(body.get('max_tokens', 64))
        rid = request['skytpu_request_id']
        targets = kv_transfer.parse_decode_targets(
            request.headers.get(kv_transfer.DECODE_URL_HEADER))
        if role == 'prefill' and targets and engine.cfg.kv_page_size:
            return await _serve_prefill_handoff(ids, max_tokens, rid,
                                                targets)
        return await _serve_monolithic(ids, max_tokens, rid)

    async def kv_adopt(request):
        """Decode role: adopt a prefill replica's KV handoff and
        decode it to completion.  The response is the completions JSON
        so the pushing replica relays it verbatim."""
        raw = await request.read()
        rid = request['skytpu_request_id']
        try:
            handoff = kv_transfer.deserialize(raw)
        except ValueError as e:
            return web.json_response({'error': str(e)}, status=400)
        try:
            req = engine.submit_adopt(
                handoff.prompt_ids, handoff.first_token, handoff.leaves,
                handoff.max_new_tokens, request_id=rid,
                page_size=handoff.page_size)
        except ValueError as e:
            # Geometry mismatch (page size/count): this replica cannot
            # serve the payload — 422 tells the pusher to try another.
            return web.json_response({'error': str(e)}, status=422)
        except RuntimeError as e:
            return web.json_response({'error': str(e)}, status=503)
        out = await asyncio.get_event_loop().run_in_executor(
            None, req.tokens)
        return web.json_response(
            _completion_json(rid, handoff.prompt_ids, out, req))

    async def metrics_route(_request):
        return web.Response(text=metrics_lib.render(),
                            content_type='text/plain')

    # On-demand profiler capture (perf/profiler.py): artifacts live
    # under a retention-bounded store, wholly removed at shutdown so
    # long-lived replicas never grow disk without bound.
    profile_store = profiler_lib.ProfileStore()

    async def debug_profile(request):
        try:
            duration_ms = float(request.query.get('duration_ms', '500'))
        except ValueError:
            return web.json_response(
                {'error': 'duration_ms must be a number'}, status=400)
        if duration_ms <= 0:
            return web.json_response(
                {'error': 'duration_ms must be positive'}, status=400)
        rid = request['skytpu_request_id']
        loop = asyncio.get_event_loop()
        try:
            # Executor thread: capture() sleeps for the whole window.
            summary = await loop.run_in_executor(
                None, profile_store.capture, duration_ms, rid)
        except profiler_lib.CaptureBusy as e:
            return web.json_response({'error': str(e)}, status=409)
        summary['role'] = role
        return web.json_response(summary)

    async def debug_profile_artifact(request):
        try:
            path = profile_store.artifact_path(
                request.match_info['tail'])
        except (ValueError, FileNotFoundError) as e:
            return web.json_response({'error': str(e)}, status=404)
        return web.FileResponse(path)

    async def _cleanup_profiles(_app):
        profile_store.cleanup()

    debug_requests, debug_request = tracing.make_debug_handlers()

    app.router.add_get('/health', health)
    app.router.add_get('/metrics', metrics_route)
    app.router.add_get('/debug/requests', debug_requests)
    app.router.add_get('/debug/requests/{request_id}', debug_request)
    app.router.add_get('/debug/profile', debug_profile)
    app.router.add_get('/debug/profile/artifact/{tail:.+}',
                       debug_profile_artifact)
    app.router.add_post('/v1/completions', completions)
    app.router.add_post(kv_transfer.ADOPT_ROUTE, kv_adopt)
    app.on_cleanup.append(_close_session)
    app.on_cleanup.append(_cleanup_profiles)
    # Tests and embedders reach the store for retention assertions.
    app['skytpu_profile_store'] = profile_store
    return app


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument('--model', default='bench-600m')
    parser.add_argument('--port', type=int,
                        default=int(os.environ.get(
                            'SKYTPU_SERVE_REPLICA_PORT', '8200')))
    parser.add_argument('--n-slots', type=int, default=8)
    parser.add_argument('--max-seq-len', type=int, default=1024)
    parser.add_argument(
        '--max-prompt-len', type=int,
        default=int(os.environ.get('SKYTPU_SERVE_MAX_PROMPT_LEN', '0')),
        help='longest admissible prompt in tokens (0 = model limit, '
        'max_seq_len - 1).  Prompts beyond the largest prefill bucket '
        'are chunked and interleaved with decode, so this is a policy '
        'cap, not a capability one.  Serve specs set it via '
        'service.max_prompt_len, which arrives here as '
        'SKYTPU_SERVE_MAX_PROMPT_LEN.')
    parser.add_argument(
        '--tensor', type=int,
        default=int(os.environ.get('SKYTPU_SERVE_TENSOR', '1')),
        help='tensor-parallel degree: shard weights/KV cache over this '
        'many chips (must divide the model\'s head counts; 1 = '
        'single-chip engine).  Serve specs set it via '
        'service.tensor_parallel, which arrives here as '
        'SKYTPU_SERVE_TENSOR.')
    parser.add_argument(
        '--kv-page-size', type=int,
        default=int(os.environ.get('SKYTPU_SERVE_KV_PAGE_SIZE', '0')),
        help='paged KV cache: page size in tokens (must divide every '
        'prefill bucket and max_seq_len).  Admission then charges '
        'pages instead of reserving n_slots x max_seq_len of HBM, and '
        'shared prompt prefixes are prefilled once (--prefix-cache).  '
        '0 = the contiguous layout.  Serve specs set it via '
        'service.kv_page_size, which arrives here as '
        'SKYTPU_SERVE_KV_PAGE_SIZE.')
    parser.add_argument(
        '--kv-pages', type=int,
        default=int(os.environ.get('SKYTPU_SERVE_KV_PAGES', '0')),
        help='page-pool size (with --kv-page-size).  0 = full backing '
        '(n_slots x max_seq_len / page_size, no admission risk); '
        'smaller values cap KV HBM at pool size and let admission '
        'control — which charges actual request length — pack more '
        'slots than full reservation would.')
    parser.add_argument(
        '--prefix-cache', type=int, choices=(0, 1),
        default=int(os.environ.get('SKYTPU_SERVE_PREFIX_CACHE', '1')),
        help='radix prefix cache over the paged KV pool (needs '
        '--kv-page-size): requests sharing a page-aligned token '
        'prefix (system prompts, few-shot templates, multi-turn '
        'replays) reference the cached pages instead of prefilling '
        'them.  Serve specs set it via service.prefix_cache '
        '(SKYTPU_SERVE_PREFIX_CACHE).')
    parser.add_argument(
        '--kv-dtype', choices=('bf16', 'int8'),
        default=os.environ.get('SKYTPU_SERVE_KV_DTYPE', 'bf16'),
        help='KV-page storage dtype (needs --kv-page-size).  int8 '
        'quantizes pages at scatter time (per-page absmax scale '
        'stored alongside), halving the per-token KV read that '
        'bounds decode throughput.  Serve specs set it via '
        'service.kv_dtype (SKYTPU_SERVE_KV_DTYPE).')
    parser.add_argument(
        '--spec-ngram', type=int,
        default=int(os.environ.get('SKYTPU_SERVE_SPEC_NGRAM', '0')),
        help='self-speculative n-gram decoding: draft length k per '
        'verify step (needs --kv-page-size; 0 = off).  The engine '
        'drafts k tokens from each request\'s own history and '
        'verifies all k+1 positions in one fixed-shape dispatch.  '
        'Serve specs set it via service.speculation '
        '(SKYTPU_SERVE_SPEC_NGRAM).')
    parser.add_argument(
        '--role', choices=('monolithic', 'prefill', 'decode'),
        default=os.environ.get('SKYTPU_SERVE_ROLE', 'monolithic'),
        help='disaggregated serving role (requires --kv-page-size: '
        'pages are the KV-transfer unit).  `prefill` replicas run '
        'only the prefill phase when the serve LB names decode '
        'candidates (X-Skytpu-Decode-Url) and push the paged KV + '
        'first token to one of them; `decode` replicas accept '
        '/v1/kv_adopt.  Both run the full engine, so a mis-routed '
        'request still completes.  Serve specs set the pools via '
        'service.disaggregation, which arrives here as '
        'SKYTPU_SERVE_ROLE.')
    parser.add_argument(
        '--checkpoint', default=None,
        help='orbax checkpoint dir (local path or gs://bucket/prefix); '
        'restores trained params instead of random init')
    parser.add_argument(
        '--param-dtype', choices=['float32', 'bfloat16'], default=None,
        help='cast restored params (bfloat16 halves HBM — required to '
        'fit 7B-class models on one v5e chip)')
    args = parser.parse_args()
    if args.max_prompt_len < 0:
        # A negative cap would 413 every request while /health stays
        # green — refuse at startup instead of serving a dead replica.
        parser.error(f'--max-prompt-len must be >= 0, '
                     f'got {args.max_prompt_len}')

    import dataclasses
    import jax
    from skypilot_tpu.models.llama import LLAMA_CONFIGS, Llama, init_params
    from skypilot_tpu.utils import compile_cache

    # Before the first compile: a restarted replica reloads its programs.
    compile_cache.enable()
    cfg = dataclasses.replace(LLAMA_CONFIGS[args.model],
                              max_seq_len=args.max_seq_len)
    if args.param_dtype:
        cfg = dataclasses.replace(
            cfg, param_dtype=getattr(jax.numpy, args.param_dtype))
    mesh = None
    if args.tensor > 1:
        from skypilot_tpu.parallel.mesh import build_serve_mesh
        mesh = build_serve_mesh(args.tensor, n_heads=cfg.n_heads,
                                n_kv_heads=cfg.n_kv_heads)
    model = Llama(cfg, mesh)
    if args.checkpoint:
        from skypilot_tpu.inference.weights import (load_serving_params,
                                                    serving_shardings)
        shardings = (serving_shardings(model, mesh)
                     if mesh is not None else None)
        # Under a mesh each leaf lands directly in its sharded placement
        # — the full tree never exists on one chip.
        params = load_serving_params(args.checkpoint,
                                     dtype=cfg.param_dtype,
                                     shardings=shardings)
    else:
        logger.warning('no --checkpoint given: serving RANDOM-INIT params '
                       '(demo mode)')
        params = init_params(model, jax.random.PRNGKey(0))['params']
    engine = DecodeEngine(
        model, params,
        EngineConfig(n_slots=args.n_slots, mesh=mesh,
                     max_prompt_len=args.max_prompt_len or None,
                     kv_page_size=args.kv_page_size or None,
                     kv_pages=args.kv_pages or None,
                     prefix_cache=bool(args.prefix_cache),
                     kv_dtype=(args.kv_dtype
                               if args.kv_page_size else 'bf16'),
                     speculation=(args.spec_ngram
                                  if args.kv_page_size else 0)))
    # Compile every prefill shape before taking traffic — a mid-burst
    # XLA compile would stall the whole decode batch for seconds.
    engine.prewarm()
    engine.start()
    if args.role != 'monolithic' and not args.kv_page_size:
        # A roled replica without paging cannot hand KV off; serve
        # monolithically rather than advertise a capability it lacks.
        logger.warning(f'--role {args.role} requires --kv-page-size; '
                       f'serving monolithically')
        args.role = 'monolithic'
    logger.info(f'serving {args.model} on :{args.port} '
                f'({args.n_slots} slots, tensor={args.tensor}, '
                f'role={args.role}, '
                f'kv_page_size={args.kv_page_size or "off"}, '
                f'prefix_cache='
                f'{bool(args.prefix_cache and args.kv_page_size)}, '
                f'kv_dtype='
                f'{args.kv_dtype if args.kv_page_size else "bf16"}, '
                f'speculation='
                f'{args.spec_ngram if args.kv_page_size else 0}, '
                f'checkpoint={args.checkpoint or "random-init"})')
    web.run_app(build_app(engine, role=args.role), port=args.port,
                print=None)


if __name__ == '__main__':
    main()
