"""Smoke run on the chip: does the system still start and answer there?

    python chip_smoke.py              one chip: serve, serve_paged, train
    python chip_smoke.py --chips 4    four chips: serve4, train4, no other
    python chip_smoke.py --rehearse   the same control flow at `tiny` on
                                      the CPU; can never pass for a chip run

The parent never imports JAX.  It runs each phase as a child
(`--phase <name>`), one after the other, so the chip has one owner at a
time and a phase that dies or runs out of HBM cannot poison the next.
Children get JAX_PLATFORMS=tpu: a chip that cannot be initialised is an
error, not a quiet move to the CPU.  Every child prints one JSON line
(seconds, peak HBM, what it checked); any child that exits non-zero or
fails a check makes the run fail.  The last line of a passing run is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as the children reported it.  Data and weights come from
--seed; no network, no git; nothing large is written anywhere (no
checkpoint, no trace — only the JSON lines and the compile cache).
Times here are set-up facts of a smoke run, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
PHASES = {1: ('serve', 'serve_paged', 'train'), 4: ('serve4', 'train4')}
# The driver allows 1200 s; children share this budget.
DEADLINE_S = 1150
NO_CHIP_RC = 3

# Real sizes, then what --rehearse swaps in (same shapes of traffic; the
# model names become `tiny`, training and the four-chip runs get short
# sequences so the CPU finishes in seconds).
SIZES = dict(serve_model='llama2-7b', paged_model='llama3-1b',
             train_model='bench-1b', train_seq=4096, train_batch=4,
             tp_model='llama3-1b', tp_kv_heads=None, rehearse=False)
REHEARSAL_SIZES = dict(serve_model='tiny', paged_model='tiny',
                       train_model='tiny', train_seq=128, train_batch=4,
                       tp_model='tiny', tp_kv_heads=4, rehearse=True)

# First-token logits of the tensor-parallel engine against the one-device
# engine: bf16 weights and activations, sums split four ways in another
# order.  Allowed: this share of the largest |logit| of the reference.
LOGIT_RTOL = 2.0**-6
# Losses of 3 fsdp=4 steps against 3 one-device steps (f32 params, bf16
# compute, gradient sums in another order).
LOSS_RTOL = 1e-2


# ----- parent ----------------------------------------------------------------
def run_parent(args) -> int:
    t0 = time.monotonic()
    env = dict(os.environ)
    env['JAX_PLATFORMS'] = 'cpu' if args.rehearse else 'tpu'
    if args.rehearse:
        env['XLA_FLAGS'] = (
            f'--xla_force_host_platform_device_count={args.chips}')
    failed, device = [], None
    for phase in PHASES[args.chips]:
        cmd = [sys.executable, os.path.abspath(__file__), '--phase', phase,
               '--chips', str(args.chips), '--seed', str(args.seed)]
        if args.rehearse:
            cmd.append('--rehearse')
        left = DEADLINE_S - (time.monotonic() - t0)
        report, rc = None, None
        try:
            # stderr is inherited; stdout is read for the JSON line.
            proc = subprocess.run(cmd, env=env, cwd=REPO, timeout=max(left, 1),
                                  stdout=subprocess.PIPE, text=True,
                                  check=False)
            rc = proc.returncode
            sys.stdout.write(proc.stdout)
            sys.stdout.flush()
            lines = proc.stdout.strip().splitlines()
            if lines:
                try:
                    report = json.loads(lines[-1])
                except ValueError:
                    pass
        except subprocess.TimeoutExpired:     # run() has killed the child
            print(json.dumps({'phase': phase, 'ok': False,
                              'error': f'over the {DEADLINE_S} s budget'}),
                  flush=True)
        if rc != 0 or not isinstance(report, dict) or not report.get('ok'):
            failed.append(phase)
            if rc == NO_CHIP_RC:
                break                 # no accelerator: the rest cannot run
            continue
        device = report['device']
    last = {'ok': not failed}
    if failed:
        last['failed'] = failed
    if device is not None:
        last['device'] = device
    if args.rehearse:
        last['rehearsal'] = True
    print(json.dumps(last), flush=True)
    return 0 if not failed else 1


# ----- child: helpers ----------------------------------------------------------
def _claim_device(chips: int, rehearse: bool) -> dict:
    """A child's first act: own the device and say what it is."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f'chip_smoke: no accelerator: {e}', file=sys.stderr)
        sys.exit(NO_CHIP_RC)
    platform = devs[0].platform
    if platform != ('cpu' if rehearse else 'tpu'):
        print(f'chip_smoke: platform is {platform!r}', file=sys.stderr)
        sys.exit(NO_CHIP_RC)
    if jax.device_count() != chips:
        sys.exit(f'chip_smoke: need {chips} device(s), '
                 f'have {jax.device_count()}')
    return {'platform': platform, 'kind': devs[0].device_kind,
            'count': len(devs)}


def _mem(key: str = 'peak_bytes_in_use'):
    """Per-device memory reading (None where the backend has none: CPU)."""
    import jax
    stats = [d.memory_stats() for d in jax.local_devices()]
    return [s[key] if s else None for s in stats]


def _metric(text: str, name: str) -> float:
    """Sum of one family's samples in a Prometheus exposition."""
    from skypilot_tpu.serve.metrics_math import parse_samples
    return sum(v for n, _, v in parse_samples(text) if n == name)


def _prompts(rng, vocab: int, n: int, lo: int, hi: int):
    return [rng.integers(0, vocab, int(rng.integers(lo, hi + 1))).tolist()
            for _ in range(n)]


async def _drive_http(engine, waves, max_tokens: int) -> dict:
    """Serve `engine` with the server's own app on a loopback port and
    send the waves in order, the prompts of a wave concurrently."""
    import asyncio
    import socket

    import aiohttp
    from aiohttp import web

    from skypilot_tpu.inference.server import build_app

    runner = web.AppRunner(build_app(engine))
    await runner.setup()
    sock = socket.socket()
    sock.bind(('127.0.0.1', 0))
    base = f'http://127.0.0.1:{sock.getsockname()[1]}'
    await web.SockSite(runner, sock).start()
    out = {'answers': []}
    timeout = aiohttp.ClientTimeout(total=DEADLINE_S)
    try:
        async with aiohttp.ClientSession(timeout=timeout) as http:
            async def get(path):
                async with http.get(base + path) as r:
                    return r.status, await r.text()

            async def complete(ids):
                body = {'prompt_ids': ids, 'max_tokens': max_tokens}
                async with http.post(base + '/v1/completions',
                                     json=body) as r:
                    return r.status, await r.json()

            out['health_before'] = (await get('/health'))[0]
            for wave in waves:
                t0 = time.perf_counter()
                out['answers'] += await asyncio.gather(
                    *(complete(ids) for ids in wave))
                out.setdefault('wave_s', []).append(
                    round(time.perf_counter() - t0, 3))
            out['health_after'] = (await get('/health'))[0]
            out['metrics'] = (await get('/metrics'))[1]
    finally:
        await runner.cleanup()
    return out


def _serve_checks(engine, http: dict, n: int, max_tokens: int) -> dict:
    answers = http['answers']
    return {
        'all_200': [s for s, _ in answers] == [200] * n,
        'completion_tokens': all(
            b.get('usage', {}).get('completion_tokens') == max_tokens
            and len(b.get('ids', ())) == max_tokens for _, b in answers),
        'health_200': (http['health_before'], http['health_after'])
        == (200, 200),
        'decode_tokens_total': _metric(
            http['metrics'], 'skytpu_engine_decode_tokens_total')
        >= n * (max_tokens - 1),
        'engine_error_none': engine.error is None,
    }


def _compile_count() -> float:
    """XLA compiles so far, as perf/compile_telemetry counts them."""
    from skypilot_tpu.server import metrics as metrics_lib
    return _metric(metrics_lib.render(), 'skytpu_engine_xla_compile_total')


def _compiles_since(before: float) -> dict:
    """Compiles since `before`, with the shapes the armed sentinel
    recorded for them."""
    from skypilot_tpu.perf import compile_telemetry
    from skypilot_tpu.server import tracing
    events = tracing.events_for(compile_telemetry.SENTINEL_REQUEST_ID)
    return {'count': int(_compile_count() - before),
            'programs': [{'s': e['attrs'].get('compile_seconds'),
                          'shapes': str(e['attrs'].get('shapes'))[:160]}
                         for e in events]}


def _prefill_logits(model, params, rows, at):
    """Logits [N, K, V] at positions `at` [N, K] of each row, by one
    prefill-style pass over the whole row (decode=True, fresh cache): the
    plain reference for what the engines compute step by step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from skypilot_tpu.utils import compile_cache

    def logits_at(p, tokens, idx):
        logits, _ = model.apply({'params': p}, tokens, decode=True,
                                mutable=['cache'])
        return jnp.take_along_axis(logits, idx[:, :, None], axis=1)

    with compile_cache.bypassed():      # params may carry pinned layouts
        return np.asarray(jax.jit(logits_at)(params, jnp.asarray(rows),
                                             jnp.asarray(at)))


def _decided(logits, tol: float):
    """Per position [K, V] -> [K]: the two top logits differ by `tol`."""
    import numpy as np
    top2 = np.sort(logits, axis=-1)[:, -2:]
    return top2[:, 1] - top2[:, 0] >= tol


def _greedy_check(model, params, prompt, tokens) -> dict:
    """A served answer against the reference: every token is the argmax
    of the model's own logits after what precedes it, wherever the
    reference is decided beyond the bf16 tolerance."""
    import numpy as np
    rows = np.array([prompt + tokens], np.int32)
    at = (len(prompt) - 1 + np.arange(len(tokens)))[None]
    logits = _prefill_logits(model, params, rows, at)[0]
    decided = _decided(logits, LOGIT_RTOL * float(np.abs(logits).max()))
    wrong = decided & (logits.argmax(-1) != np.asarray(tokens))
    finite = bool(np.isfinite(logits).all())
    return {'positions': len(tokens), 'decided': int(decided.sum()),
            'wrong_where_decided': int(wrong.sum()), 'finite': finite,
            'ok': finite and bool(decided.any()) and not wrong.any()}


# ----- child: one chip ---------------------------------------------------------
def phase_serve(seed: int, sz: dict) -> dict:
    """llama2-7b bf16 at published width and depth, contiguous cache,
    behind the HTTP server: what inference/server.py main() builds."""
    import asyncio
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig
    from skypilot_tpu.models.llama import LLAMA_CONFIGS, Llama, init_params

    n_req, max_tokens = 6, 32
    cfg = dataclasses.replace(LLAMA_CONFIGS[sz['serve_model']],
                              max_seq_len=448, param_dtype=jnp.bfloat16)
    model = Llama(cfg)
    t0 = time.perf_counter()
    params = init_params(model, jax.random.PRNGKey(seed))['params']
    t1 = time.perf_counter()
    # One bucket and max_prompt_len == bucket: prewarm compiles 4 prefill
    # programs + 1 decode, and no chunk programs.
    engine = DecodeEngine(
        model, params,
        EngineConfig(n_slots=8, prefill_buckets=(256,), max_prompt_len=256))
    del params
    engine.prewarm()
    t2 = time.perf_counter()
    compiles_warm = _compile_count()
    engine.start()
    prompts = _prompts(np.random.default_rng(seed), cfg.vocab_size, n_req,
                       100, 250)
    waves = [[p] for p in prompts[:3]] + [prompts[3:]]   # 3 at once last
    http = asyncio.run(_drive_http(engine, waves, max_tokens))
    t3 = time.perf_counter()
    engine.stop()
    compiles = _compiles_since(compiles_warm)
    checks = _serve_checks(engine, http, n_req, max_tokens)
    greedy = _greedy_check(model, engine.params, prompts[0],
                           http['answers'][0][1]['ids'])
    checks['served_tokens_are_greedy'] = greedy['ok']
    if not sz['rehearse']:
        # The AOT layout pass ran (it exists on the TPU only): without it
        # 7B does not fit 16 GB.
        checks['layout_pass_ran'] = engine._fmt_params is not None  # pylint: disable=protected-access
    return {'model': sz['serve_model'], 'params': cfg.num_params(),
            'init_s': round(t1 - t0, 2), 'compile_s': round(t2 - t1, 2),
            'run_s': round(t3 - t2, 2), 'wave_s': http['wave_s'],
            'compiles_after_prewarm': compiles, 'greedy': greedy,
            'checks': checks}


def phase_serve_paged(seed: int, sz: dict) -> dict:
    """llama3-1b bf16 on the paged pool with the radix prefix cache (what
    examples/serve_llama.yaml turns on), behind the HTTP server."""
    import asyncio
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig
    from skypilot_tpu.models.llama import LLAMA_CONFIGS, Llama, init_params

    n_req, max_tokens = 6, 32
    cfg = dataclasses.replace(LLAMA_CONFIGS[sz['paged_model']],
                              max_seq_len=1024, param_dtype=jnp.bfloat16)
    model = Llama(cfg)
    t0 = time.perf_counter()
    params = init_params(model, jax.random.PRNGKey(seed))['params']
    t1 = time.perf_counter()
    engine = DecodeEngine(
        model, params,
        EngineConfig(n_slots=8, prefill_buckets=(128, 256), kv_page_size=64,
                     prefix_cache=True))
    del params
    # On one device without the layout pass prewarm() compiles nothing
    # (engine.py prewarm): one request first is the warm-up, timed apart.
    engine.prewarm()
    compiles_0 = _compile_count()
    engine.start()
    rng = np.random.default_rng(seed)
    warm = _prompts(rng, cfg.vocab_size, 1, 100, 120)
    t2 = time.perf_counter()
    warm_http = asyncio.run(_drive_http(engine, [warm], max_tokens))
    t3 = time.perf_counter()
    compiles_warm = _compile_count()
    # One request carries a 128-token prefix (two pages); after it has
    # finished, three more share it; two others do not.
    prefix = rng.integers(0, cfg.vocab_size, 128).tolist()
    tails = _prompts(rng, cfg.vocab_size, 4, 40, 100)
    others = _prompts(rng, cfg.vocab_size, 2, 100, 120)
    waves = [[prefix + tails[0]], [prefix + t for t in tails[1:]],
             [others[0]], [others[1]]]
    http = asyncio.run(_drive_http(engine, waves, max_tokens))
    t4 = time.perf_counter()
    engine.stop()
    compiles = _compiles_since(compiles_warm)
    checks = _serve_checks(engine, http, n_req, max_tokens)
    # The second answer rode the prefix cache: gathered pages + chunk path.
    greedy = _greedy_check(model, engine.params, prefix + tails[1],
                           http['answers'][1][1]['ids'])
    checks['served_tokens_are_greedy'] = greedy['ok']
    checks['warm_200'] = warm_http['answers'][0][0] == 200
    hits = _metric(http['metrics'], 'skytpu_engine_prefix_cache_hits_total')
    checks['prefix_cache_hits'] = hits > 0
    engine._pool_alloc.check_conserved()  # pylint: disable=protected-access
    checks['pages_conserved'] = True
    return {'model': sz['paged_model'], 'params': cfg.num_params(),
            'init_s': round(t1 - t0, 2), 'build_s': round(t2 - t1, 2),
            'warm_request_s': round(t3 - t2, 2),
            'warm_request_compiles': int(compiles_warm - compiles_0),
            'run_s': round(t4 - t3, 2), 'wave_s': http['wave_s'],
            'compiles_after_warm': compiles, 'greedy': greedy,
            'prefix_hits': hits, 'checks': checks}


def _train_batches(seed: int, cfg, batch: int, seq: int):
    import jax
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), 2)
    return [jax.random.randint(k, (batch, seq), 0, cfg.vocab_size)
            for k in keys]


def _run_trainer(trainer, batches, num_steps: int) -> dict:
    """`num_steps` through Trainer.run on an iterator cycling `batches`;
    the time to the first logged step is compile + one step."""
    import itertools
    losses, stamps = [], []

    def log_fn(m):
        losses.append(float(m['loss']))
        stamps.append(time.perf_counter())

    t0 = time.perf_counter()
    trainer.run(itertools.cycle(batches), num_steps=num_steps, log_every=1,
                log_fn=log_fn)
    return {'losses': losses, 'first_step_s': round(stamps[0] - t0, 2),
            'rest_s': round(stamps[-1] - stamps[0], 2)}


def phase_train(seed: int, sz: dict) -> dict:
    """bench-1b, sequence 4096, batch 4, six steps through Trainer.run.
    No checkpoint on purpose: the state is 11.4 GB."""
    import math

    import jax

    from skypilot_tpu.models.llama import LLAMA_CONFIGS, Llama
    from skypilot_tpu.parallel.mesh import build_mesh, plan_mesh
    from skypilot_tpu.perf.cost_model import chip_kind
    from skypilot_tpu.server import metrics as metrics_lib
    from skypilot_tpu.train.trainer import TrainConfig, Trainer

    cfg = LLAMA_CONFIGS[sz['train_model']]
    mesh = build_mesh(plan_mesh(1), jax.devices()[:1])
    batches = _train_batches(seed, cfg, sz['train_batch'], sz['train_seq'])
    t0 = time.perf_counter()
    trainer = Trainer(Llama(cfg, mesh), mesh, jax.random.PRNGKey(seed),
                      batches[0],
                      TrainConfig(warmup_steps=2, total_steps=1000))
    t1 = time.perf_counter()
    # The program that runs holds the Pallas kernel, not the reference.
    text = trainer.train_step.lower(trainer.state, batches[0]).as_text()
    run = _run_trainer(trainer, batches, 6)
    losses = run['losses']
    mfu = _metric(metrics_lib.render(), 'skytpu_train_mfu_percent')
    checks = {
        'six_finite_losses': len(losses) == 6 and all(
            math.isfinite(x) for x in losses),
        'loss_fell': sum(losses[4:6]) < sum(losses[0:2]),
        'mfu_in_0_100': 0.0 < mfu < 100.0,
    }
    if not sz['rehearse']:
        checks['pallas_kernel_in_program'] = 'tpu_custom_call' in text
        checks['chip_kind_v5litepod'] = chip_kind() == 'v5litepod'
    report = {'model': sz['train_model'], 'params': cfg.num_params(),
              'seq': sz['train_seq'], 'batch': sz['train_batch'],
              'init_s': round(t1 - t0, 2), 'compile_s': run['first_step_s'],
              'run_s': run['rest_s'], 'losses': losses,
              'chip_kind': chip_kind(), 'checks': checks}
    if not sz['rehearse']:  # a CPU's "MFU" is no device metric: not shown
        report['exported_mfu_percent'] = mfu
    return report


# ----- child: four chips -------------------------------------------------------
def _drop_device_state() -> None:
    """Between the sharded run and its one-device twin (the caller has
    `del`ed its own references): device 0 cannot hold both."""
    import gc

    import jax
    jax.clear_caches()
    gc.collect()


def _placement(leaves, shardings) -> dict:
    """Placement is checked, not assumed: every leaf whose sharding means
    to split it sits on all four devices in pieces, every device holds
    something, and device 0 holds less than twice the mean."""
    meant = [(leaf, s.shard_shape(leaf.shape))
             for leaf, s in zip(leaves, shardings)
             if s.shard_shape(leaf.shape) != leaf.shape]
    split = all(len(leaf.sharding.device_set) == 4 and
                leaf.sharding.shard_shape(leaf.shape) == piece
                for leaf, piece in meant)
    used = _mem('bytes_in_use')
    out = {'leaves': len(leaves), 'leaves_meant_sharded': len(meant),
           'bytes_in_use': used,
           'checks': {'sharded_leaves_on_4_devices': bool(meant) and split}}
    if all(u is not None for u in used):      # the CPU reports none
        mean = sum(used) / len(used)
        out['checks']['every_device_holds_bytes'] = min(used) > 0
        out['checks']['device0_below_twice_mean'] = used[0] < 2 * mean
    return out


def _generate(engine, prompts, n_new: int):
    """All prompts in before the loop starts: one admission group."""
    reqs = [engine.submit(p, n_new) for p in prompts]
    engine.start()
    toks = [r.tokens() for r in reqs]
    engine.stop()
    return toks


def phase_serve4(seed: int, sz: dict) -> dict:
    """llama3-1b bf16, tensor-parallel over four chips, against the same
    weights on a one-device engine in this process: 4 prompts, greedy."""
    import dataclasses

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig
    from skypilot_tpu.inference.weights import serving_shardings
    from skypilot_tpu.models.llama import LLAMA_CONFIGS, Llama, init_params
    from skypilot_tpu.parallel import sharding as sharding_lib
    from skypilot_tpu.parallel.mesh import build_serve_mesh

    n_new, bucket = 16, 128
    cfg = dataclasses.replace(LLAMA_CONFIGS[sz['tp_model']], max_seq_len=256,
                              param_dtype=jnp.bfloat16)
    if sz['tp_kv_heads']:           # `tiny` has 2 kv heads; 4 chips need 4
        cfg = dataclasses.replace(cfg, n_kv_heads=sz['tp_kv_heads'])
    ecfg = EngineConfig(n_slots=4, prefill_buckets=(bucket,),
                        max_prompt_len=bucket)
    key = jax.random.PRNGKey(seed)
    prompts = _prompts(np.random.default_rng(seed), cfg.vocab_size, 4, 60,
                       bucket)
    last = np.array([[len(p) - 1] for p in prompts])
    rows = np.zeros((4, bucket), np.int32)
    for i, p in enumerate(prompts):
        rows[i, :len(p)] = p

    # --- sharded: build, read, delete.
    t0 = time.perf_counter()
    mesh = build_serve_mesh(4, n_heads=cfg.n_heads,
                            n_kv_heads=cfg.n_kv_heads)
    model4 = Llama(cfg, mesh)
    engine = DecodeEngine(model4, init_params(model4, key)['params'],
                          dataclasses.replace(ecfg, mesh=mesh))
    guarded = jax.tree.leaves(serving_shardings(model4, mesh))
    abstract = jax.eval_shape(lambda: model4.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    meant = jax.tree.leaves(nn.meta.unbox(nn.logical_to_mesh_sharding(
        nn.get_partition_spec(abstract)['params'], mesh,
        list(sharding_lib.DEFAULT_RULES))))
    n_guard = sum(m.spec != g.spec for m, g in zip(meant, guarded))
    placement = _placement(jax.tree.leaves(engine.params), guarded)
    placement['leaves_replicated_by_divisibility_guard'] = n_guard
    logits4 = _prefill_logits(engine.model, engine.params, rows, last)
    toks4 = _generate(engine, prompts, n_new)
    err4 = engine.error
    t1 = time.perf_counter()
    peak4 = _mem()
    del engine, model4
    _drop_device_state()

    # --- one device, same weights (same key, same initialisers).
    model1 = Llama(cfg)
    engine = DecodeEngine(model1, init_params(model1, key)['params'], ecfg)
    logits1 = _prefill_logits(model1, engine.params, rows, last)
    toks1 = _generate(engine, prompts, n_new)
    # Where the reference itself is undecided (its two top logits closer
    # than the tolerance), the runs may part: compare up to there.  The
    # reference's logits at its own tokens come from one more prefill.
    tol = LOGIT_RTOL * float(np.abs(logits1).max())
    full = np.zeros((4, bucket + n_new), np.int32)
    for i, (p, t) in enumerate(zip(prompts, toks1)):
        full[i, :len(p) + n_new] = p + t
    ref = _prefill_logits(model1, engine.params, full,
                          last + np.arange(n_new))
    agree_to, decided_to = [], []
    for i in range(4):
        close = np.nonzero(~_decided(ref[i], tol))[0]
        decided_to.append(int(close[0]) if len(close) else n_new)
        diff = np.nonzero(np.asarray(toks4[i]) != np.asarray(toks1[i]))[0]
        agree_to.append(int(diff[0]) if len(diff) else n_new)
    t2 = time.perf_counter()
    logit_err = float(np.abs(logits4 - logits1).max())
    checks = dict(placement.pop('checks'))
    checks.update({
        'engine_errors_none': err4 is None and engine.error is None,
        'sixteen_tokens_each': all(
            len(t) == n_new for t in toks4 + toks1),
        'first_token_logits_within_tol': logit_err <= tol,
        'tokens_agree_while_reference_decided': all(
            a >= d for a, d in zip(agree_to, decided_to)),
    })
    return {'model': sz['tp_model'], 'sharded_s': round(t1 - t0, 2),
            'one_device_s': round(t2 - t1, 2), 'placement': placement,
            'peak_bytes_sharded_run': peak4,
            'logit_tol': tol, 'logit_max_abs_err': logit_err,
            'tokens_agree_to': agree_to, 'reference_decided_to': decided_to,
            'checks': checks}


def phase_train4(seed: int, sz: dict) -> dict:
    """bench-1b, 3 steps on fsdp=4 against 3 steps on one device, same
    batches, both through Trainer.run."""
    import jax

    from skypilot_tpu.models.llama import LLAMA_CONFIGS, Llama
    from skypilot_tpu.parallel.mesh import build_mesh, plan_mesh
    from skypilot_tpu.train.trainer import TrainConfig, Trainer

    cfg = LLAMA_CONFIGS[sz['train_model']]
    batches = _train_batches(seed, cfg, sz['train_batch'], sz['train_seq'])
    tcfg = TrainConfig(warmup_steps=2, total_steps=1000)
    key = jax.random.PRNGKey(seed)

    mesh4 = build_mesh(plan_mesh(4, fsdp=4))
    trainer = Trainer(Llama(cfg, mesh4), mesh4, key, batches[0], tcfg)
    placement = _placement(jax.tree.leaves(trainer.state.params),
                           jax.tree.leaves(trainer.shardings.params))
    run4 = _run_trainer(trainer, batches, 3)
    peak4 = _mem()
    del trainer
    _drop_device_state()

    mesh1 = build_mesh(plan_mesh(1), jax.devices()[:1])
    trainer = Trainer(Llama(cfg, mesh1), mesh1, key, batches[0], tcfg)
    run1 = _run_trainer(trainer, batches, 3)
    rel = [abs(a - b) / abs(b)
           for a, b in zip(run4['losses'], run1['losses'])]
    checks = dict(placement.pop('checks'))
    checks['three_losses_each'] = (len(run4['losses']),
                                   len(run1['losses'])) == (3, 3)
    checks['losses_within_rtol'] = len(rel) == 3 and max(rel) <= LOSS_RTOL
    return {'model': sz['train_model'], 'seq': sz['train_seq'],
            'batch': sz['train_batch'], 'placement': placement,
            'peak_bytes_sharded_run': peak4, 'fsdp4': run4,
            'one_device': run1, 'loss_rtol': LOSS_RTOL,
            'loss_rel_err': rel, 'checks': checks}


# ----- child entry ---------------------------------------------------------------
def run_child(args) -> int:
    device = _claim_device(args.chips, args.rehearse)
    cache_dir = None
    if not args.rehearse:       # CPU entries are of no use to a chip run
        from skypilot_tpu.utils import compile_cache
        cache_dir = compile_cache.enable()
    phase_fn = globals()[f'phase_{args.phase}']
    t0 = time.perf_counter()
    report = phase_fn(args.seed, REHEARSAL_SIZES if args.rehearse else SIZES)
    report.update({'phase': args.phase, 'device': device,
                   'total_s': round(time.perf_counter() - t0, 2),
                   'peak_bytes_in_use': _mem(), 'compile_cache': cache_dir,
                   'seed': args.seed})
    report['ok'] = all(report['checks'].values())
    print(json.dumps(report), flush=True)
    return 0 if report['ok'] else 1


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    parser.add_argument('--seed', type=int, default=0)
    parser.add_argument('--chips', type=int, choices=(1, 4), default=1)
    parser.add_argument('--rehearse', action='store_true',
                        help='tiny models on the CPU; never a chip result')
    parser.add_argument('--phase', choices=sum(PHASES.values(), ()),
                        help='run one phase in this process (the parent '
                        'starts its children with this)')
    args = parser.parse_args(argv)
    if args.phase:
        if args.phase not in PHASES[args.chips]:
            parser.error(f'--phase {args.phase} needs --chips '
                         f'{4 if args.chips == 1 else 1}')
        return run_child(args)
    return run_parent(args)


if __name__ == '__main__':
    sys.exit(main())
