"""Headline benchmark: Llama training MFU + serving throughput on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} with the
north-star metrics in "detail":
  - train: MFU, tokens/sec/chip, $/1M-tokens (catalog price x throughput)
  - serve: req/s, output tok/s, TTFT, TPOT from the continuous-batching
    decode engine (skypilot_tpu/inference)

Training baseline: the reference's published Llama-3-8B run on TPU v6e-8
(PyTorch/XLA FSDP, examples/tpu/v6e/README.md:34-48): total_flos
109935420 GF over train_runtime 672.77 s on 8 chips = 163.4 TFLOP/s
= 20.4 TFLOP/s/chip = 2.22% MFU (v6e peak 918 bf16 TFLOP/s/chip).
MFU is the hardware-neutral comparison: this bench trains a ~1B Llama at
seq 4096 (single chip, 16 GB HBM) but measures the same quantity — model
FLOPs utilization of the chip it runs on — so vs_baseline = our MFU / 2.22%.

Serving baseline: JetStream Llama-2-7B on v6e: 11.42 req/s, 2147.98
output tok/s, median TPOT 18.88 ms (examples/tpu/v6e/README.md:119-127).
Reported for context; model sizes differ, so serve numbers are not folded
into vs_baseline.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from skypilot_tpu.train.flops import (PEAK_BF16_TFLOPS, chip_kind,
                                      train_flops_per_token)

REFERENCE_MFU = 2.225  # % — derived above from the reference's own numbers


_CATALOG_GENERATION = {'v5e': 'v5litepod'}  # device-kind name != SKU name


def _chip_price_per_hr(kind: str) -> tuple:
    """(on-demand, spot) $/chip/hr from the bundled catalog."""
    try:
        from skypilot_tpu.catalog import gcp_catalog
        df = gcp_catalog._tpu_df.read()  # pylint: disable=protected-access
        rows = df[df['generation'] == _CATALOG_GENERATION.get(kind, kind)]
        if len(rows):
            return (float(rows['price_chip_hr'].min()),
                    float(rows['spot_price_chip_hr'].min()))
    except Exception:  # pylint: disable=broad-except
        pass
    return (0.0, 0.0)


def bench_train(on_tpu: bool, seq: int = None, batch: int = None,
                steps: int = None, remat_policy: str = None) -> dict:
    from skypilot_tpu.models.llama import LLAMA_CONFIGS, Llama
    from skypilot_tpu.parallel.mesh import build_mesh, plan_mesh
    from skypilot_tpu.train.trainer import TrainConfig, Trainer

    cfg = LLAMA_CONFIGS['bench-1b' if on_tpu else 'tiny']
    seq = seq or (4096 if on_tpu else 64)
    batch = batch or 4
    steps = steps or (15 if on_tpu else 3)
    if seq > cfg.max_seq_len or remat_policy:
        cfg = dataclasses.replace(
            cfg, max_seq_len=max(seq, cfg.max_seq_len),
            remat_policy=remat_policy or cfg.remat_policy)

    mesh = build_mesh(plan_mesh(1), jax.devices()[:1])
    model = Llama(cfg, mesh)
    rng = jax.random.PRNGKey(0)
    tokens = jax.random.randint(rng, (batch, seq), 0, cfg.vocab_size)
    trainer = Trainer(model, mesh, rng, tokens,
                      TrainConfig(warmup_steps=5, total_steps=1000))

    state = trainer.state
    for _ in range(2):
        state, metrics = trainer.train_step(state, tokens)
    np.array(metrics['loss'])  # sync: the warm-up steps have finished

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = trainer.train_step(state, tokens)
    np.array(metrics['loss'])
    dt = (time.perf_counter() - t0) / steps

    tokens_per_s = batch * seq / dt
    n_params = cfg.num_params()
    model_tflops = tokens_per_s * train_flops_per_token(
        n_params, cfg.n_layers, cfg.dim, seq) / 1e12
    kind = chip_kind()
    peak = PEAK_BF16_TFLOPS[kind]
    mfu = 100.0 * model_tflops / peak
    price, spot_price = _chip_price_per_hr(kind)
    tok_per_hr = tokens_per_s * 3600.0
    usd_per_1m = price / (tok_per_hr / 1e6) if tok_per_hr else 0.0
    usd_per_1m_spot = spot_price / (tok_per_hr / 1e6) if tok_per_hr else 0.0
    return {
        'mfu_pct': round(mfu, 2),
        'tokens_per_s_per_chip': round(tokens_per_s, 1),
        'usd_per_1m_tokens': round(usd_per_1m, 4),
        'usd_per_1m_tokens_spot': round(usd_per_1m_spot, 4),
        'model_params_m': round(n_params / 1e6, 1),
        'model_tflops_per_s': round(model_tflops, 2),
        'chip': kind,
        'chip_peak_tflops': peak,
        'chip_price_hr': price,
        'step_time_ms': round(dt * 1e3, 2),
        'seq_len': seq,
        'batch': batch,
    }


# The reference's serving benchmark is JetStream Llama-2-7B on a
# v6e-8 SLICE (8 chips, serve-llama2-7b.yaml:2): 11.42 req/s, 2147.98
# out tok/s, median TPOT 18.88 ms over 100 requests of ~219 in / ~188
# out tokens (examples/tpu/v6e/README.md:119-127).  This bench serves
# the SAME model (llama2-7b, bf16) on the ONE chip available, at the
# same request shape, and compares per-chip and per-HBM-bandwidth
# (decode is bandwidth-bound; v6e-8 aggregates 16x this v5e chip's
# 819 GB/s).
_SERVE_BASELINE = {
    'out_tok_per_s': 2147.98,
    'req_per_s': 11.42,
    'tpot_median_ms': 18.88,
    'n_chips': 8,
    'chip_hbm_gbps': 1640.0,           # v6e (Trillium) per chip
}
# Single source of truth for per-chip HBM bandwidth: the decode cost
# model uses the same table for its roofline, and the perf gate
# (skytpu perf) cross-checks bench output against it.
from skypilot_tpu.perf.cost_model import HBM_GBPS as _HBM_GBPS  # noqa: E402


def bench_serve(on_tpu: bool) -> dict:
    from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig
    from skypilot_tpu.models.llama import LLAMA_CONFIGS, Llama, init_params

    if on_tpu:
        # Llama-2-7B bf16 = 13.3 GB of 15.75 usable; 8 slots x 448 of
        # MHA KV = 1.8 GB.  Fits one v5e chip only because the engine
        # pre-lays-out weights for the decode loop (engine.py
        # _optimize_layouts).
        cfg = dataclasses.replace(LLAMA_CONFIGS['llama2-7b'],
                                  max_seq_len=448,
                                  param_dtype=jnp.bfloat16)
        n_slots, steps_per_call, buckets = 8, 32, (256,)
        prompt_len, new_tokens, n_requests = 219, 150, 48
    else:
        cfg = dataclasses.replace(LLAMA_CONFIGS['tiny'], max_seq_len=128)
        n_slots, steps_per_call, buckets = 2, 4, (8,)
        prompt_len, new_tokens, n_requests = 8, 4, 4
    model = Llama(cfg)
    params = init_params(model, jax.random.PRNGKey(0))['params']

    engine = DecodeEngine(
        model, params,
        EngineConfig(n_slots=n_slots, steps_per_call=steps_per_call,
                     prefill_buckets=buckets))
    engine.prewarm()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len).tolist()
               for _ in range(n_requests)]
    # Warm the decode shape (prewarm covers prefill shapes on TPU).
    w = engine.submit(prompts[0], 2)
    while w.finished_at is None:
        engine.step()

    # --- saturated regime: every request offered at t=0.  TTFT here is
    # queueing-dominated by construction (48 requests into 8 slots);
    # the honest interactive-latency numbers come from the sub-
    # saturating Poisson regime below.
    reqs = [engine.submit(p, new_tokens) for p in prompts]
    t0 = time.perf_counter()
    while any(r.finished_at is None for r in reqs):
        engine.step_pipelined()
    wall = time.perf_counter() - t0

    out_tokens = sum(r.emitted for r in reqs)
    ttfts = sorted((r.first_token_at - t0) * 1e3 for r in reqs)
    tpots = []
    for r in reqs:
        if r.emitted > 1:
            tpots.append((r.finished_at - r.first_token_at) * 1e3 /
                         (r.emitted - 1))
    tpots.sort()
    out_tok_per_s = out_tokens / wall

    # --- sub-saturating regime: Poisson arrivals at 0.7x measured
    # capacity; the engine runs its own pipelined loop thread.
    poisson_n = max(8, n_requests // 2)
    rate = 0.7 * out_tok_per_s / new_tokens          # req/s offered
    engine.start()
    try:
        arr_rng = np.random.default_rng(1)
        gaps = arr_rng.exponential(1.0 / rate, poisson_n)
        p_reqs = []
        p_t0 = time.perf_counter()
        for i in range(poisson_n):
            target = p_t0 + float(np.sum(gaps[:i + 1]))
            dt = target - time.perf_counter()
            if dt > 0:
                time.sleep(dt)
            p_reqs.append(engine.submit(
                prompts[i % len(prompts)], new_tokens))
        deadline = time.perf_counter() + 300
        while any(r.finished_at is None for r in p_reqs) and \
                time.perf_counter() < deadline:
            time.sleep(0.05)
    finally:
        engine.stop()
    p_ttfts = sorted((r.first_token_at - r.submitted_at) * 1e3
                     for r in p_reqs if r.first_token_at is not None)
    p_tpots = sorted(
        (r.finished_at - r.first_token_at) * 1e3 / (r.emitted - 1)
        for r in p_reqs if r.finished_at is not None and r.emitted > 1)
    kind = chip_kind()
    base = _SERVE_BASELINE
    per_chip_base = base['out_tok_per_s'] / base['n_chips']
    bw_base = base['out_tok_per_s'] / (base['chip_hbm_gbps'] *
                                       base['n_chips'])
    bw_ours = out_tok_per_s / _HBM_GBPS[kind]
    # Device-cost attribution: the SAME cost model that drives the
    # engine's live skytpu_engine_mfu / _hbm_bytes_per_token gauges,
    # evaluated at this run's measured saturated throughput.  `skytpu
    # perf` asserts the live gauges agree with these within 5%.
    cm = engine.perf_cost_model
    mean_ctx = prompt_len + new_tokens / 2.0
    n_active = min(n_slots, n_requests)
    perf = {
        'mfu_pct': round(cm.mfu(out_tok_per_s, mean_ctx), 6),
        'hbm_bytes_per_token': round(
            cm.decode_hbm_bytes_per_token(mean_ctx, n_active), 1),
        'arith_intensity': round(
            cm.arith_intensity(mean_ctx, n_active), 4),
        'roofline_out_tok_per_s': round(
            cm.roofline_decode_tokens_per_s(mean_ctx, n_active), 1),
        'mean_context_len': mean_ctx,
        'mean_occupancy': n_active,
        # The dtype the cost model priced KV traffic at — reads the
        # engine config (NOT assumed bf16) so an int8 serve bench and
        # the perf gate's roofline agree on bytes/token.
        'kv_dtype': engine.cfg.kv_dtype,
    }
    return {
        'model': 'llama2-7b' if on_tpu else 'tiny',
        'req_per_s': round(n_requests / wall, 2),
        'out_tok_per_s': round(out_tok_per_s, 1),
        'ttft_median_ms': round(ttfts[len(ttfts) // 2], 2),
        'tpot_median_ms': round(tpots[len(tpots) // 2], 2),
        # Sub-saturating (0.7x capacity, Poisson arrivals): the latency
        # a real user sees when the service is provisioned sanely.
        'poisson_load_frac': 0.7,
        'poisson_ttft_median_ms': round(
            p_ttfts[len(p_ttfts) // 2], 2) if p_ttfts else None,
        'poisson_tpot_median_ms': round(
            p_tpots[len(p_tpots) // 2], 2) if p_tpots else None,
        'n_slots': n_slots,
        'prompt_len': prompt_len,
        'new_tokens': new_tokens,
        'n_chips': 1,
        'perf': perf,
        # Honest-scale comparisons vs the 8-chip v6e baseline:
        'vs_baseline_out_tok_per_chip': round(out_tok_per_s /
                                              per_chip_base, 2),
        'vs_baseline_req_per_s_per_chip': round(
            (n_requests / wall) / (base['req_per_s'] / base['n_chips']), 2),
        'vs_baseline_per_hbm_bandwidth': round(bw_ours / bw_base, 2),
        'vs_baseline_tpot': round(base['tpot_median_ms'] /
                                  tpots[len(tpots) // 2], 2),
        'baseline': 'JetStream Llama-2-7B on v6e-8 (8 chips): 11.42 '
                    'req/s, 2147.98 out tok/s, median TPOT 18.88 ms '
                    '(examples/tpu/v6e/README.md:119-127)',
    }


def bench_saturated_ttft(on_tpu: bool) -> dict:
    """Long prompts injected into a busy engine: what happens to
    everyone ELSE's TTFT.

    Two engines serve the identical workload — a burst of long prompts
    followed by a wave of short interactive prompts:
      - `chunked`: the long prompts exceed the largest bucket, so they
        prefill chunk-by-chunk interleaved with decode; the shorts
        admit into free slots immediately and their first tokens ride
        decode calls that the long prefills delay by at most one chunk.
      - `fused` (the old single-dispatch path): a bucket big enough to
        swallow a long prompt whole — the longs admit first (FIFO) and
        the shorts' prefills + first decode stall behind monolithic
        long-prefill dispatches.
    Reported: median TTFT of the short wave under each engine (the
    saturated-TTFT headline, tracked round-over-round) and the long
    prompts' own median TTFT.  `ttft_saturated_ms` is the chunked
    number; strictly below `ttft_saturated_fused_ms` is the win.
    """
    from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig
    from skypilot_tpu.models.llama import LLAMA_CONFIGS, Llama, init_params

    if on_tpu:
        # Scheduling scenario, not a throughput one: the 600M bench
        # model keeps params + the 2k-deep KV cache far under HBM while
        # a 1500-token fused prefill is still real device work.
        cfg = dataclasses.replace(LLAMA_CONFIGS['bench-600m'],
                                  param_dtype=jnp.bfloat16)
        n_slots, steps_per_call = 8, 16
        buckets, fused_buckets = (64, 256), (64, 256, 1536)
        long_len, short_len, new_tokens = 1500, 60, 48
        n_longs, n_shorts = 4, 8
    else:
        cfg = dataclasses.replace(LLAMA_CONFIGS['tiny'], max_seq_len=128)
        n_slots, steps_per_call = 4, 2
        buckets, fused_buckets = (8,), (8, 128)
        long_len, short_len, new_tokens = 120, 4, 8
        n_longs, n_shorts = 3, 4
    model = Llama(cfg)
    params = init_params(model, jax.random.PRNGKey(0))['params']

    def run(prefill_buckets) -> dict:
        engine = DecodeEngine(
            model, params,
            EngineConfig(n_slots=n_slots, steps_per_call=steps_per_call,
                         prefill_buckets=prefill_buckets))
        engine.prewarm()
        rng = np.random.default_rng(0)
        # Warm every shape this workload will hit, including the
        # power-of-two padded admission-burst shapes (prewarm covers
        # them on TPU; elsewhere run the same burst pattern through) —
        # a mid-measurement XLA compile would swamp the scheduling
        # effect being measured.
        warm = [engine.submit(
            rng.integers(0, cfg.vocab_size, long_len).tolist(), 2)
            for _ in range(n_longs)]
        warm += [engine.submit(
            rng.integers(0, cfg.vocab_size, short_len).tolist(), 2)
            for _ in range(n_shorts)]
        while any(r.finished_at is None for r in warm):
            engine.step_pipelined()
        engine.drain()
        longs = [engine.submit(
            rng.integers(0, cfg.vocab_size, long_len).tolist(),
            new_tokens) for _ in range(n_longs)]
        shorts = [engine.submit(
            rng.integers(0, cfg.vocab_size, short_len).tolist(),
            new_tokens) for _ in range(n_shorts)]
        watched = longs + shorts
        while any(r.finished_at is None for r in watched):
            engine.step_pipelined()
        engine.drain()

        def med(reqs):
            ttfts = sorted((r.first_token_at - r.submitted_at) * 1e3
                           for r in reqs)
            return round(ttfts[len(ttfts) // 2], 2)

        return {'short': med(shorts), 'long': med(longs)}

    chunked = run(buckets)
    fused = run(fused_buckets)
    return {
        'ttft_saturated_ms': chunked['short'],
        'ttft_saturated_fused_ms': fused['short'],
        'long_prompt_ttft_chunked_ms': chunked['long'],
        'long_prompt_ttft_fused_ms': fused['long'],
        'long_len': long_len,
        'n_longs': n_longs,
        'short_len': short_len,
        'n_shorts': n_shorts,
        'speedup_vs_fused': round(
            fused['short'] / max(chunked['short'], 1e-9), 2),
    }


def bench_prefix_cache(on_tpu: bool) -> dict:
    """Shared-prefix workload sweep over the paged-KV engine: TTFT and
    out-tok/s at 0/50/90% prefix-hit-rate targets, plus the
    HBM-per-slot comparison against the contiguous layout.

    The workload models production traffic at millions-of-users scale:
    every request carries the same long system-prompt/few-shot prefix
    plus a short unique tail.  With the radix prefix cache the prefix
    is prefilled ONCE per replica and every later request gathers the
    cached pages instead — so TTFT and throughput should improve
    MONOTONICALLY with hit rate (the pinned acceptance criterion),
    while the page pool (sized to actual request length, not
    n_slots x max_seq_len) cuts KV HBM per slot.
    """
    from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig
    from skypilot_tpu.models.llama import LLAMA_CONFIGS, Llama, init_params

    if on_tpu:
        cfg = dataclasses.replace(LLAMA_CONFIGS['bench-600m'],
                                  param_dtype=jnp.bfloat16)
        n_slots, steps_per_call = 8, 16
        page, buckets = 64, (64, 256)
        shared_len, tail_len, new_tokens, n_requests = 1024, 27, 96, 32
    else:
        cfg = dataclasses.replace(LLAMA_CONFIGS['tiny'], max_seq_len=512)
        n_slots, steps_per_call = 4, 4
        page, buckets = 16, (16, 64)
        shared_len, tail_len, new_tokens, n_requests = 192, 8, 8, 12
    model = Llama(cfg)
    params = init_params(model, jax.random.PRNGKey(0))['params']
    pages_per_req = -(-(shared_len + tail_len + new_tokens) // page)
    # Pool sized to the ACTUAL workload (+ headroom for cached prefix
    # pages), not to n_slots x max_seq_len — the reservation delta IS
    # the HBM win reported below.
    kv_pages = n_slots * pages_per_req + shared_len // page + 4

    rng = np.random.default_rng(0)
    shared = rng.integers(1, cfg.vocab_size, shared_len).tolist()

    def run(hit_frac: float) -> dict:
        engine = DecodeEngine(
            model, params,
            EngineConfig(n_slots=n_slots, steps_per_call=steps_per_call,
                         prefill_buckets=buckets, kv_page_size=page,
                         kv_pages=kv_pages, prefix_cache=True))
        engine.prewarm()
        wrng = np.random.default_rng(1)
        # Warm every compiled shape with prompts DISJOINT from the
        # measured traffic (their cached pages are evicted by the
        # measured run at worst, never hit).
        warm = [engine.submit(
            wrng.integers(1, cfg.vocab_size,
                          shared_len + tail_len).tolist(), 2)
            for _ in range(2)]
        while any(r.finished_at is None for r in warm):
            engine.step_pipelined()
        engine.drain()

        n_shared = round(hit_frac * n_requests)
        prompts = []
        for i in range(n_requests):
            tail = wrng.integers(1, cfg.vocab_size, tail_len).tolist()
            if i < n_shared:
                prompts.append(shared + tail)
            else:
                prompts.append(
                    wrng.integers(1, cfg.vocab_size,
                                  shared_len).tolist() + tail)
        from skypilot_tpu.server import metrics as metrics_lib
        before = _counter_value(
            metrics_lib, 'skytpu_engine_prefix_cache_hits_total')
        reqs = [engine.submit(p, new_tokens) for p in prompts]
        t0 = time.perf_counter()
        while any(r.finished_at is None for r in reqs):
            engine.step_pipelined()
        engine.drain()
        wall = time.perf_counter() - t0
        hits = _counter_value(
            metrics_lib, 'skytpu_engine_prefix_cache_hits_total') - before
        ttfts = sorted((r.first_token_at - t0) * 1e3 for r in reqs)
        pool_bytes = sum(
            int(np.prod(leaf.shape)) * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(engine._cache))  # pylint: disable=protected-access
        dense_abs = jax.eval_shape(engine._make_cache, params)  # pylint: disable=protected-access
        dense_bytes = sum(
            int(np.prod(leaf.shape)) * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(dense_abs))
        return {
            'hit_rate_target': hit_frac,
            'hit_rate_measured': round(hits / n_requests, 3),
            'ttft_median_ms': round(ttfts[len(ttfts) // 2], 2),
            'out_tok_per_s': round(
                sum(r.emitted for r in reqs) / wall, 1),
            'hbm_bytes_per_slot': pool_bytes // n_slots,
            'hbm_bytes_per_slot_contiguous': dense_bytes // n_slots,
        }

    sweep = [run(f) for f in (0.0, 0.5, 0.9)]
    top = sweep[-1]
    return {
        'page_size': page,
        'kv_pages': kv_pages,
        'n_requests': n_requests,
        'shared_prefix_len': shared_len,
        'sweep': sweep,
        # Headline keys (README/ROADMAP claims pin on these):
        'ttft_prefix_hit_ms': top['ttft_median_ms'],
        'out_tok_per_s_prefix': top['out_tok_per_s'],
        'hbm_bytes_per_slot': top['hbm_bytes_per_slot'],
        'hbm_bytes_per_slot_contiguous':
            top['hbm_bytes_per_slot_contiguous'],
        'hbm_savings_ratio': round(
            top['hbm_bytes_per_slot_contiguous'] /
            max(top['hbm_bytes_per_slot'], 1), 2),
    }


def bench_speculative(on_tpu: bool) -> dict:
    """Speculative decoding + int8 KV pages: acceptance sweep and the
    {spec off/on} x {bf16, int8} throughput grid.

    Acceptance is workload-dependent, so two param sets bracket it with
    the SAME prompts: the stock random-init params produce chaotic
    greedy trajectories (incompressible-traffic proxy — drafts self-
    reject and the engine degrades to plain decode), while params
    scaled toward zero make greedy generation context-insensitive and
    settle into short cycles (repetitive-traffic proxy: templated
    text, code, multi-turn replays).  Both run the full forward pass —
    nothing about the verify dispatch is mocked.

    Honest-proxy caveat: on CPU the verify FLOPs (S = k+1 positions)
    cost linearly, so spec-on can trail spec-off in raw tok/s even at
    high acceptance — the win this bench demonstrates is tokens per
    DISPATCH (one sync per m accepted tokens) plus the int8 halving of
    roofline KV bytes/token; on memory-bound TPU decode those are the
    binding terms.
    """
    from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig
    from skypilot_tpu.models.llama import LLAMA_CONFIGS, Llama, init_params
    from skypilot_tpu.server import metrics as metrics_lib

    if on_tpu:
        cfg = dataclasses.replace(LLAMA_CONFIGS['bench-600m'],
                                  param_dtype=jnp.bfloat16)
        n_slots, page, buckets = 8, 64, (64,)
        prompt_len, new_tokens, n_requests = 57, 960, 16
        spec_k = 8
    else:
        cfg = dataclasses.replace(LLAMA_CONFIGS['tiny'], max_seq_len=256)
        n_slots, page, buckets = 8, 16, (16,)
        prompt_len, new_tokens, n_requests = 12, 224, 16
        spec_k = 8
    model = Llama(cfg)
    params = init_params(model, jax.random.PRNGKey(0))['params']
    # Repetitive-traffic proxy: scaling params toward zero flattens the
    # context dependence of the logits, so greedy generation locks into
    # short cycles — the regime n-gram drafts always hit.
    rep_params = jax.tree.map(lambda x: (x * 0.1).astype(x.dtype),
                              params)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
               for _ in range(n_requests)]

    def run(run_params, k: int, kv_dtype: str) -> dict:
        engine = DecodeEngine(
            model, run_params,
            EngineConfig(n_slots=n_slots, steps_per_call=4,
                         prefill_buckets=buckets, kv_page_size=page,
                         kv_dtype=kv_dtype, speculation=k))
        warm = engine.submit(prompts[0], 2)
        while warm.finished_at is None:
            engine.step()
        before_p = _counter_value(
            metrics_lib, 'skytpu_engine_spec_proposed_tokens_total')
        before_a = _counter_value(
            metrics_lib, 'skytpu_engine_spec_accepted_tokens_total')
        reqs = [engine.submit(p, new_tokens) for p in prompts]
        t0 = time.perf_counter()
        while any(r.finished_at is None for r in reqs):
            engine.step()
        wall = time.perf_counter() - t0
        proposed = _counter_value(
            metrics_lib,
            'skytpu_engine_spec_proposed_tokens_total') - before_p
        accepted = _counter_value(
            metrics_lib,
            'skytpu_engine_spec_accepted_tokens_total') - before_a
        tpots = sorted(
            (r.finished_at - r.first_token_at) * 1e3 / (r.emitted - 1)
            for r in reqs if r.emitted > 1)
        cm = engine.perf_cost_model
        mean_ctx = prompt_len + new_tokens / 2.0
        return {
            'k': k,
            'kv_dtype': kv_dtype,
            'out_tok_per_s': round(
                sum(r.emitted for r in reqs) / wall, 1),
            'tpot_median_ms': round(tpots[len(tpots) // 2], 2),
            'acceptance': round(accepted / max(proposed, 1), 3),
            # Roofline attribution from the engine's own cost model —
            # where the int8 halving is visible even on the CPU proxy.
            'hbm_bytes_per_token': round(
                cm.decode_hbm_bytes_per_token(mean_ctx, n_slots), 1),
        }

    # Acceptance sweep over draft length, repetitive vs incompressible.
    accept_sweep = {
        'repetitive': [run(rep_params, k, 'bf16') for k in (2, 4)],
        'random': [run(params, 4, 'bf16')],
    }
    # Throughput grid at the headline draft length.
    grid = {
        'spec_off_bf16': run(rep_params, 0, 'bf16'),
        'spec_on_bf16': run(rep_params, spec_k, 'bf16'),
        'spec_off_int8': run(rep_params, 0, 'int8'),
        'spec_on_int8': run(rep_params, spec_k, 'int8'),
    }
    accept_sweep['repetitive'].append(grid['spec_on_bf16'])
    top = grid['spec_on_bf16']
    return {
        'spec_k': spec_k,
        'page_size': page,
        'n_requests': n_requests,
        'new_tokens': new_tokens,
        'accept_sweep': accept_sweep,
        'grid': grid,
        # Headline keys (README/ROADMAP claims pin on these):
        'out_tok_per_s_spec': top['out_tok_per_s'],
        'tpot_spec_ms': top['tpot_median_ms'],
        'acceptance_repetitive': top['acceptance'],
        'acceptance_random': accept_sweep['random'][0]['acceptance'],
        'hbm_bytes_per_token_bf16': grid['spec_off_bf16'][
            'hbm_bytes_per_token'],
        'hbm_bytes_per_token_int8': grid['spec_off_int8'][
            'hbm_bytes_per_token'],
    }


def _counter_value(metrics_lib, family: str) -> float:
    """Sum of one counter family's samples in the live registry
    (serve/metrics_math.py owns the exposition parsing)."""
    from skypilot_tpu.serve import metrics_math
    return metrics_math.counter_total(
        metrics_math.parse_samples(metrics_lib.render()), family)


def bench_trace_overhead(on_tpu: bool) -> dict:
    """Cost of the always-on flight recorder (server/tracing.py).

    Backs the "<1% throughput overhead" contract (test_readme_bench
    pins it once this lands in an artifact):
      - ns_per_event: microbenched record_span cost (lock + deque
        append on the engine loop thread) — robustly measurable;
      - out-tok/s with the recorder ON vs OFF
        (SKYTPU_TRACE_RING_SIZE=0) over the identical saturated
        workload, interleaved + median;
      - overhead_pct: the headline, computed as
        events-per-token x ns_per_event over the measured per-token
        wall time.  Recording is strictly additive work on the loop
        thread, so this product IS the overhead; the differential
        throughput comparison is reported too but on a noisy shared
        host it is jitter-dominated (run-to-run swings dwarf a
        sub-percent effect), so the derived number is the honest one.
    """
    import os
    from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig
    from skypilot_tpu.models.llama import LLAMA_CONFIGS, Llama, init_params
    from skypilot_tpu.server import tracing

    # ns/event: pure recorder cost, no engine in the loop.  Min over
    # several batches: scheduler jitter only ever inflates a batch, so
    # the minimum is the honest per-event cost.
    tracing.reset_for_tests()
    batch, per_batch = 20_000, []
    for _ in range(5):
        t0 = time.perf_counter()
        for i in range(batch):
            tracing.record_span('bench-ev', 'engine.prefill_chunk',
                                0.0, 1.0, offset=i, width=256,
                                final=False)
        per_batch.append((time.perf_counter() - t0) / batch * 1e9)
    ns_per_event = min(per_batch)

    if on_tpu:
        cfg = dataclasses.replace(LLAMA_CONFIGS['bench-600m'],
                                  param_dtype=jnp.bfloat16)
        n_slots, steps_per_call, buckets = 8, 16, (64, 256)
        prompt_len, new_tokens, n_requests = 219, 96, 32
    else:
        cfg = dataclasses.replace(LLAMA_CONFIGS['tiny'], max_seq_len=128)
        n_slots, steps_per_call, buckets = 4, 4, (8,)
        prompt_len, new_tokens, n_requests = 8, 48, 12
    model = Llama(cfg)
    params = init_params(model, jax.random.PRNGKey(0))['params']
    engine = DecodeEngine(
        model, params,
        EngineConfig(n_slots=n_slots, steps_per_call=steps_per_call,
                     prefill_buckets=buckets))
    engine.prewarm()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len).tolist()
               for _ in range(n_requests)]
    w = engine.submit(prompts[0], 2)
    while w.finished_at is None:
        engine.step()

    def run(recorder_on: bool):
        saved = os.environ.get(tracing.RING_SIZE_ENV)
        os.environ[tracing.RING_SIZE_ENV] = \
            str(tracing.DEFAULT_RING_SIZE if recorder_on else 0)
        tracing.reset_for_tests()
        try:
            reqs = [engine.submit(p, new_tokens,
                                  request_id=f'bench-{i}')
                    for i, p in enumerate(prompts)]
            t0 = time.perf_counter()
            while any(r.finished_at is None for r in reqs):
                engine.step_pipelined()
            engine.drain()
            wall = time.perf_counter() - t0
            n_events = len([e for r in reqs
                            for e in tracing.events_for(r.request_id)])
            return sum(r.emitted for r in reqs) / wall, n_events
        finally:
            if saved is None:
                os.environ.pop(tracing.RING_SIZE_ENV, None)
            else:
                os.environ[tracing.RING_SIZE_ENV] = saved
            tracing.reset_for_tests()

    # One discarded warmup of the measured workload (first run in a
    # process pays cache/allocator warmup whichever mode it is), then
    # alternate modes so drift lands on both equally; medians compare.
    run(True)
    ons, offs, event_counts = [], [], []
    for _ in range(3):
        offs.append(run(False)[0])
        tput, n_events = run(True)
        ons.append(tput)
        event_counts.append(n_events)
    on = sorted(ons)[len(ons) // 2]
    off = sorted(offs)[len(offs) // 2]
    total_tokens = n_requests * new_tokens
    events_per_token = max(event_counts) / total_tokens
    # The headline: additive per-event cost over the measured per-token
    # budget.  (1/on) seconds per token; overhead = recorded work in it.
    overhead_pct = (events_per_token * ns_per_event * 1e-9) * on * 100.0
    diff_pct = (off - on) / off * 100.0 if off else 0.0
    return {
        'ns_per_event': round(ns_per_event, 1),
        'events_per_token': round(events_per_token, 4),
        'out_tok_per_s_recorder_on': round(on, 1),
        'out_tok_per_s_recorder_off': round(off, 1),
        'overhead_pct': round(overhead_pct, 3),
        'overhead_pct_differential': round(diff_pct, 2),
    }


def bench_obs_overhead(on_tpu: bool) -> dict:
    """Cost of the fleet telemetry plane (skypilot_tpu/obs).

    Backs the "<1% serving-throughput overhead" contract
    (test_readme_bench pins it once this lands in an artifact).  The
    plane touches serving in exactly two places, measured separately:

      - us_per_ingest: a full scrape -> counter-reset-aware downsample
        -> store transaction on a realistic mixed-pool exposition.
        The CONTROLLER pays this once per tick, off the serving path;
        ingest_duty_pct is that cost over the default resolution — the
        fraction of one controller core the store consumes.
      - ns_per_digest: the crc32 path-digest + XOR the ENGINE pays per
        radix-cache insert/evict for the prefix-fingerprint gauge —
        the only on-serving-path addition.
      - overhead_pct: the headline — engine-side additive work per
        generated token over the measured per-token budget, same
        derivation as the tracing bench (strictly additive work on the
        loop thread, so the product IS the overhead; a differential
        run would be jitter-dominated at this magnitude).
    """
    import os
    import tempfile
    import zlib
    from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig
    from skypilot_tpu.models.llama import LLAMA_CONFIGS, Llama, init_params
    from skypilot_tpu.obs import store as obs_store
    from skypilot_tpu.server import metrics as metrics_lib

    # A realistic federated exposition: 8 replicas across two pools
    # with latency histograms, traffic counters, and engine gauges.
    metrics_lib.reset_for_tests()
    rng = np.random.default_rng(0)
    for i in range(8):
        rid = str(i)
        for _ in range(40):
            metrics_lib.observe_hist(metrics_lib.ENGINE_TTFT_FAMILY,
                                     float(rng.uniform(0.05, 0.4)),
                                     replica=rid)
            metrics_lib.observe_hist(metrics_lib.ENGINE_TPOT_FAMILY,
                                     float(rng.uniform(0.01, 0.04)),
                                     replica=rid)
        metrics_lib.inc_counter('skytpu_lb_requests_total', 40.0)
        metrics_lib.set_gauge('skytpu_engine_kv_free_pages', 512.0,
                              replica=rid)
        metrics_lib.set_gauge('skytpu_engine_prefix_fingerprint',
                              float(i * 2654435761 % 2**32),
                              replica=rid)
    text = metrics_lib.render()
    metrics_lib.reset_for_tests()

    db = os.path.join(tempfile.mkdtemp(prefix='skytpu-bench-obs-'),
                      'obs.db')
    store = obs_store.TelemetryStore(db, resolution=1.0)
    roles = {str(i): ('prefill' if i < 2 else 'decode')
             for i in range(8)}
    now0 = 1_000_000.0
    store.ingest('bench', text, now=now0, leader_check=False)  # warmup
    per_call = []
    for batch in range(5):
        t0 = time.perf_counter()
        for i in range(20):
            store.ingest('bench', text, now=now0 + batch * 20 + i + 1,
                         roles=roles, leader_check=False)
        per_call.append((time.perf_counter() - t0) / 20 * 1e6)
    us_per_ingest = min(per_call)
    ingest_duty_pct = (us_per_ingest * 1e-6 /
                       obs_store.DEFAULT_RESOLUTION_S * 100.0)

    # ns/digest: the per-insert fingerprint cost, microbenched exactly
    # as paging.py computes it (crc32 of the parent-digest/key pair).
    batch, per_batch, acc = 50_000, [], 0
    for _ in range(5):
        t0 = time.perf_counter()
        for i in range(batch):
            acc ^= zlib.crc32(repr((acc, (i, i + 1, i + 2)))
                              .encode('ascii'))
        per_batch.append((time.perf_counter() - t0) / batch * 1e9)
    ns_per_digest = min(per_batch)

    # Per-token budget from a short saturated run of the real engine
    # (the fingerprint accounting is always on — it ships in insert/
    # evict — so this throughput already carries the cost it prices).
    if on_tpu:
        cfg = dataclasses.replace(LLAMA_CONFIGS['bench-600m'],
                                  param_dtype=jnp.bfloat16)
        n_slots, steps_per_call, buckets = 8, 16, (64, 256)
        prompt_len, new_tokens, n_requests = 219, 96, 32
    else:
        cfg = dataclasses.replace(LLAMA_CONFIGS['tiny'], max_seq_len=128)
        n_slots, steps_per_call, buckets = 4, 4, (8,)
        prompt_len, new_tokens, n_requests = 8, 48, 12
    model = Llama(cfg)
    params = init_params(model, jax.random.PRNGKey(0))['params']
    engine = DecodeEngine(
        model, params,
        EngineConfig(n_slots=n_slots, steps_per_call=steps_per_call,
                     prefill_buckets=buckets))
    engine.prewarm()
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len).tolist()
               for _ in range(n_requests)]
    w = engine.submit(prompts[0], 2)
    while w.finished_at is None:
        engine.step()
    reqs = [engine.submit(p, new_tokens) for p in prompts]
    t0 = time.perf_counter()
    while any(r.finished_at is None for r in reqs):
        engine.step_pipelined()
    engine.drain()
    wall = time.perf_counter() - t0
    tok_s = sum(r.emitted for r in reqs) / wall
    # One radix insert (+ at most one evict) per request; the digest is
    # computed once per inserted node on the prompt path.
    digests_per_token = 2.0 * n_requests / (n_requests * new_tokens)
    overhead_pct = (digests_per_token * ns_per_digest * 1e-9) \
        * tok_s * 100.0
    return {
        'us_per_ingest': round(us_per_ingest, 1),
        'ingest_duty_pct': round(ingest_duty_pct, 4),
        'ns_per_digest': round(ns_per_digest, 1),
        'out_tok_per_s': round(tok_s, 1),
        'overhead_pct': round(overhead_pct, 4),
    }


def bench_goodput(on_tpu: bool) -> dict:
    """Training goodput plane (goodput ledger + straggler detection).

    Three measured contracts, each pinned by test_readme_bench once
    this lands in an artifact:

      - **ledger-vs-wall agreement <1%**: a real (tiny, one-chip)
        Trainer run with real orbax checkpoints and an injected
        preemption — incarnation 1 dies after its checkpoint,
        controller-style downtime rows are written, incarnation 2
        restores and finishes — and the durable ledger's categories
        must re-tile the externally measured wall-clock;
      - **instrumentation overhead <1%**: the per-step hot-loop
        additions (two perf_counter stamps, the input-stall carve, the
        host-labeled step histogram) microbenched the same
        strictly-additive way as the tracing/obs benches, priced
        against the run's own measured step time;
      - the **sim validation**: the fleetsim goodput scenario (planted
        slow host, injected preemption on a sim clock) driven through
        the production store/skew/alert path — exact tiling, skew
        attribution to the planted host, goodput_low + straggler
        firing.
    """
    del on_tpu  # tiny model everywhere: the plane under test is
    # clock/ledger arithmetic, not matmuls
    import os
    import tempfile
    from skypilot_tpu.fleetsim.goodput_run import (GoodputScenario,
                                                   run_goodput_sim)
    from skypilot_tpu.models.llama import LLAMA_CONFIGS, Llama
    from skypilot_tpu.obs import goodput as goodput_lib
    from skypilot_tpu.parallel.mesh import build_mesh, plan_mesh
    from skypilot_tpu.server import metrics as metrics_lib
    from skypilot_tpu.server import tracing
    from skypilot_tpu.train.trainer import TrainConfig, Trainer

    tmp = tempfile.mkdtemp(prefix='skytpu-bench-goodput-')
    ledger = goodput_lib.GoodputLedger(os.path.join(tmp, 'jobs.db'))
    job = 'bench'
    rid = f'job-{job}'

    cfg = LLAMA_CONFIGS['tiny']
    seq, batch, steps1, steps2 = 64, 4, 12, 12
    mesh = build_mesh(plan_mesh(1), jax.devices()[:1])
    model = Llama(cfg, mesh)
    rng = jax.random.PRNGKey(0)
    tokens = jax.random.randint(rng, (batch, seq), 0, cfg.vocab_size)
    ckpt_dir = os.path.join(tmp, 'ckpt')

    def data_iter():
        while True:
            yield tokens

    # ---- incarnation 1: train, checkpoint, "lose the slice" ----------
    wall0 = time.perf_counter()
    rec1 = goodput_lib.PhaseRecorder(job=job, ledger=ledger, rid=rid)
    trainer = Trainer(model, mesh, rng, tokens,
                      TrainConfig(warmup_steps=2, total_steps=100),
                      checkpoint_dir=ckpt_dir, phases=rec1)
    trainer.run(data_iter(), steps1, checkpoint_every=6, log_every=6)
    rec1.close()    # the worker dies with its slice
    # ---- controller: detect, teardown, relaunch (jobs/controller
    # _record_downtime semantics, compressed sleeps) -------------------
    lost_p = time.perf_counter()
    time.sleep(0.05)
    rec_p = time.perf_counter()
    time.sleep(0.05)
    up_p = time.perf_counter()
    for cat, p0, p1 in (
            (goodput_lib.PREEMPTION_DOWNTIME, lost_p, rec_p),
            (goodput_lib.RECOVERY_RELAUNCH, rec_p, up_p)):
        tracing.record_span(rid, goodput_lib.DOWNTIME_SPAN, p0, p1,
                            category=cat)
        ledger.add(job, cat, p1 - p0, t0=tracing.wall_of(p0),
                   t1=tracing.wall_of(p1))
    # ---- incarnation 2: restore and finish ---------------------------
    rec2 = goodput_lib.PhaseRecorder(job=job, ledger=ledger, rid=rid)
    trainer2 = Trainer(model, mesh, rng, tokens,
                       TrainConfig(warmup_steps=2, total_steps=100),
                       checkpoint_dir=ckpt_dir, phases=rec2)
    resumed_step = trainer2.restore_if_available()
    out = trainer2.run(data_iter(), steps2, checkpoint_every=6,
                       log_every=6)
    rec2.close()
    wall_s = time.perf_counter() - wall0

    totals = ledger.totals(job)
    ledger_wall = sum(totals.values())
    # Ledger intervals vs flight-recorder span timestamps for the
    # injected preemption (the ±1 s acceptance check).
    ev_starts = {e['attrs']['category']: e['ts']
                 for e in tracing.events_for(rid)
                 if e['name'] == goodput_lib.DOWNTIME_SPAN}
    deltas = [abs(iv['t0'] - ev_starts[cat])
              for cat in (goodput_lib.PREEMPTION_DOWNTIME,
                          goodput_lib.RECOVERY_RELAUNCH)
              if cat in ev_starts
              for iv in ledger.intervals(job, cat)]
    event_delta_s = max(deltas) if deltas else None

    # ---- per-step instrumentation cost (strictly additive) -----------
    rec = goodput_lib.PhaseRecorder()
    rec.begin(goodput_lib.PRODUCTIVE)
    n, per_batch = 20_000, []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            f0 = time.perf_counter()
            stall = time.perf_counter() - f0
            rec.carve(goodput_lib.INPUT_STALL, stall + 1e-12)
            metrics_lib.observe_hist('skytpu_train_step_seconds',
                                     0.01, host='host0')
        per_batch.append((time.perf_counter() - t0) / n)
    instr_s_per_step = min(per_batch)
    # Price it against this run's own productive step time.
    step_time_s = tokens.size / out['tokens_per_s']
    overhead_pct = 100.0 * instr_s_per_step / step_time_s

    # ---- sim validation (planted slow host, sim clock) ---------------
    sim = run_goodput_sim(
        GoodputScenario(slow_host=2),
        ledger_dsn=os.path.join(tmp, 'sim_ledger.db'),
        store_dsn=os.path.join(tmp, 'sim_store.db'))

    return {
        'goodput_pct': round(ledger.goodput_pct(job), 2),
        'badput_s': {c: round(s, 4) for c, s in sorted(totals.items())
                     if c != goodput_lib.PRODUCTIVE},
        'productive_s': round(totals.get(goodput_lib.PRODUCTIVE, 0.0),
                              4),
        'wall_s': round(wall_s, 4),
        'ledger_wall_s': round(ledger_wall, 4),
        'ledger_vs_wall_pct': round(
            100.0 * abs(ledger_wall - wall_s) / wall_s, 4),
        'preemption_event_delta_s': (round(event_delta_s, 4)
                                     if event_delta_s is not None
                                     else None),
        'resumed_from_step': resumed_step,
        'instr_us_per_step': round(instr_s_per_step * 1e6, 3),
        'overhead_pct': round(overhead_pct, 4),
        'sim': {
            'goodput_pct': round(sim['goodput_pct'], 2),
            'ledger_vs_wall_pct': round(sim['ledger_vs_wall_pct'], 6),
            'skew': round(sim['skew']['skew'], 2) if sim['skew']
                    else None,
            'slow_host': (sim['skew'] or {}).get('slow_host'),
            'active_alerts': sim['active_alerts'],
            'downtime_s': round(sim['downtime_s'], 2),
        },
    }


def bench_slo_ramp(plateau_ticks: int = 12) -> dict:
    """SLO-aware vs QPS-only autoscaling under a synthetic traffic ramp
    (virtual replicas, virtual time — hermetic and chip-free).

    The setup is the one that breaks QPS autoscaling in production: the
    operator's `target_qps_per_replica` (8) over-states the replicas'
    true batching knee (2 qps — e.g. calibrated on short prompts, then
    traffic shifted long), so the QPS policy under-provisions at the
    ramp top while the SLO policy reads the p95 TPOT users actually see
    from the federated histograms and scales until the target holds.
    Both policies get the same replica budget (max 8) and ideal, instant
    provisioning — the comparison isolates DECISION quality.  Reported:
    requests-weighted p95 TPOT over the plateau tail for each policy,
    against the 15 ms target.
    """
    from skypilot_tpu.serve import slo_sim

    # Scenario constants + driver live in slo_sim so this bench and its
    # load-tier test twin (tests/test_load.py) provably run the SAME
    # experiment.
    target_tpot_ms = slo_sim.DEFAULT_TARGET_TPOT_MS
    ramp = slo_sim.default_ramp(plateau_ticks)
    out: dict = {'target_tpot_ms': target_tpot_ms,
                 'peak_qps': ramp[-1], 'ticks': len(ramp)}
    for key, slo in (('slo', True), ('qps', False)):
        history = slo_sim.run_policy(slo, ramp)
        out[f'p95_tpot_ms_{key}'] = round(
            slo_sim.requests_weighted_p95(history, last_n_ticks=4), 2)
        out[f'final_replicas_{key}'] = history[-1][1]
    out['slo_meets_target'] = out['p95_tpot_ms_slo'] <= target_tpot_ms
    out['qps_meets_target'] = out['p95_tpot_ms_qps'] <= target_tpot_ms
    return out


def bench_disagg(plateau_ticks: int = 8) -> dict:
    """Disaggregated prefill/decode vs monolithic serving at EQUAL
    chip budget (slo_sim phase-cost model — hermetic, chip-free).

    Saturated mixed long/short traffic (canonical scenario constants
    in serve/slo_sim.py, shared with the test twin): on a monolithic
    pool the compute-bound prefill phase steals decode device time, so
    TPOT breaches its SLO long before the chips run out of aggregate
    throughput; splitting the same chips into a prefill pool and a
    decode pool (KV pages handed off between them) isolates the
    phases.  Reported per pool shape: TTFT/TPOT at the plateau, the
    SLO-met request fraction over the whole ramp, and $-per-1k-SLO-met
    (decode pool on spot — ThunderServe's cost lever).

    Second half: the per-pool SLO autoscaler (DisaggSLOAutoscaler)
    drives the pools through the ramp and one decode replica is
    PREEMPTED mid-plateau.  With the spot pool's preemption headroom
    the TPOT SLO holds through the preemption and the next tick's
    re-plan restores the margin; the no-headroom counterfactual run
    breaches on the preemption tick — both directions are pinned by
    tests/test_readme_bench.py once this lands in an artifact.
    """
    from skypilot_tpu.serve import slo_sim

    costs = slo_sim.DISAGG_COSTS
    target_ttft = slo_sim.DISAGG_TARGET_TTFT_MS
    target_tpot = slo_sim.DISAGG_TARGET_TPOT_MS
    chips = slo_sim.DISAGG_TOTAL_CHIPS
    tick = slo_sim.DISAGG_TICK_S
    ramp = slo_sim.disagg_ramp(plateau_ticks)
    price, spot_price = _chip_price_per_hr('v5e')
    if not price:
        price, spot_price = 1.2, 0.6       # nominal v5e list prices

    svc = slo_sim.make_disagg_service()

    def met(ttft_s, tpot_s):
        return (ttft_s * 1e3 <= target_ttft and
                tpot_s * 1e3 <= target_tpot)

    def run_static(latency_fn, cost_per_hr):
        met_req = total_req = 0
        peak_lat = None
        for qps in ramp:
            ttft, tpot = latency_fn(qps)
            n = qps * tick
            total_req += n
            if met(ttft, tpot):
                met_req += n
            peak_lat = (ttft, tpot)
        hours = len(ramp) * tick / 3600.0
        usd_per_1k = (cost_per_hr * hours / (met_req / 1e3)
                      if met_req else None)
        return {
            'ttft_peak_ms': round(peak_lat[0] * 1e3, 2),
            'tpot_peak_ms': round(peak_lat[1] * 1e3, 2),
            'slo_met_frac': round(met_req / total_req, 3),
            'cost_per_hr': round(cost_per_hr, 2),
            'usd_per_1k_slo_met': (round(usd_per_1k, 4)
                                   if usd_per_1k is not None else None),
        }

    mono = run_static(
        lambda q: svc.latencies_monolithic(q, chips), chips * price)
    # Equal-chip split sweep: every (prefill, decode) partition,
    # decode pool on spot.  Best = most SLO-met requests, cheapest on
    # ties (no silent cap: the full sweep lands in the JSON).
    sweep = []
    for n_prefill in range(1, chips):
        n_decode = chips - n_prefill
        cost = n_prefill * price + n_decode * spot_price
        entry = run_static(
            lambda q, p=n_prefill, d=n_decode:
                svc.latencies_pools(q, p, d), cost)
        entry.update(prefill_replicas=n_prefill,
                     decode_replicas=n_decode)
        sweep.append(entry)
    best = max(sweep, key=lambda e: (e['slo_met_frac'],
                                     -e['cost_per_hr']))

    # --- preemption mid-plateau under the per-pool autoscaler --------
    preempt_tick = len(ramp) - 3
    hist = slo_sim.run_disagg_ramp(
        slo_sim.make_disagg_autoscaler(spot_headroom=1),
        slo_sim.make_disagg_service(), ramp, preempt_tick=preempt_tick)
    after = hist[preempt_tick:]
    preempt_max_tpot = max(t for _, _, _, _, t in after)
    recovered = hist[preempt_tick + 1][2] >= hist[preempt_tick][2] + 1
    # Counterfactual, static by construction: a decode pool sized
    # EXACTLY to its SLO (the minimal size meeting the TPOT target at
    # peak, no spot headroom) breaches the moment one replica
    # preempts — the margin the headroom knob buys is load-bearing.
    d_slo = next(d for d in range(1, chips + 1)
                 if svc.latencies_pools(
                     slo_sim.DISAGG_PEAK_QPS, 2, d)[1] * 1e3
                 <= target_tpot)
    no_headroom_max_tpot = svc.latencies_pools(
        slo_sim.DISAGG_PEAK_QPS, 2, max(1, d_slo - 1))[1] * 1e3
    return {
        'total_chips': chips,
        'peak_qps': slo_sim.DISAGG_PEAK_QPS,
        'target_ttft_ms': target_ttft,
        'target_tpot_ms': target_tpot,
        'prompt_tokens': slo_sim.DISAGG_PROMPT_TOKENS,
        'new_tokens': slo_sim.DISAGG_NEW_TOKENS,
        'monolithic': mono,
        'disagg': best,
        'split_sweep': sweep,
        # Headline keys (README claims pin on these):
        'usd_per_1k_slo_met_monolithic': mono['usd_per_1k_slo_met'],
        'usd_per_1k_slo_met_disagg': best['usd_per_1k_slo_met'],
        'slo_met_frac_monolithic': mono['slo_met_frac'],
        'slo_met_frac_disagg': best['slo_met_frac'],
        'preemption_tick': preempt_tick,
        'preemption_max_tpot_ms': round(preempt_max_tpot, 2),
        'preemption_tpot_ok': preempt_max_tpot <= target_tpot,
        'preemption_replan_restored_pool': recovered,
        'no_headroom_preemption_tpot_ms': round(no_headroom_max_tpot,
                                                2),
        'no_headroom_preemption_breaches':
            no_headroom_max_tpot > target_tpot,
    }


def bench_launch() -> dict:
    """Control-plane overhead: launch -> agent READY -> rank-0 start.

    Hermetic: provisions a one-node cluster on the `local` cloud (the
    same agent bootstrap path every cloud uses) under a throwaway $HOME,
    so the bench never touches real state or credentials.  Three
    stamps:
      - agent_ready_s: execution.launch() return — optimizer +
        provision + agent bootstrap; launch() returns only after the
        agent answered its readiness probe and rank 0 was submitted.
      - rank0_start_s: job-queue `started_at` minus launch() return —
        scheduler latency from submission to the rank-0 process
        starting.
      - launch_overhead_s: the whole path, launch() call to rank-0
        start.  This is the per-replica scale-up cost the serve
        autoscaler pays before a new replica takes traffic.
    """
    import os
    import shutil
    import tempfile

    keys = ('HOME', 'SKYTPU_GLOBAL_CONFIG', 'SKYTPU_PROJECT_CONFIG',
            'SKYTPU_ENABLED_CLOUDS')
    saved = {k: os.environ.get(k) for k in keys}
    home = tempfile.mkdtemp(prefix='skytpu-bench-home-')
    os.environ['HOME'] = home
    os.environ['SKYTPU_GLOBAL_CONFIG'] = os.path.join(
        home, '.skytpu', 'config.yaml')
    os.environ['SKYTPU_PROJECT_CONFIG'] = os.path.join(home, '.skytpu.yaml')
    os.environ['SKYTPU_ENABLED_CLOUDS'] = 'local'
    cluster = 'bench-launch'
    launched = False
    try:
        from skypilot_tpu import core, execution
        from skypilot_tpu.resources import Resources
        from skypilot_tpu.task import Task

        task = Task('bench-launch', run='true')
        task.set_resources(Resources.from_yaml_config({'infra': 'local'}))
        wall0 = time.time()
        t0 = time.perf_counter()
        job_id, _ = execution.launch(task, cluster, detach_run=True,
                                     quiet_optimizer=True)
        launched = True
        agent_ready_s = time.perf_counter() - t0
        started_at = None
        deadline = time.time() + 60
        while time.time() < deadline:
            rec = next((j for j in core.queue(cluster)
                        if j['job_id'] == job_id), None)
            if rec is not None and rec.get('started_at'):
                started_at = float(rec['started_at'])
                break
            time.sleep(0.1)
        if started_at is None:
            return {'error': 'rank-0 never started within 60s',
                    'agent_ready_s': round(agent_ready_s, 3)}
        return {
            'launch_overhead_s': round(started_at - wall0, 3),
            'agent_ready_s': round(agent_ready_s, 3),
            'rank0_start_s': round(started_at - (wall0 + agent_ready_s),
                                   3),
        }
    except Exception as e:  # pylint: disable=broad-except
        return {'error': f'{type(e).__name__}: {e}'}
    finally:
        # Teardown BEFORE the env restore / rmtree: the agent spawned by
        # launch() must be stopped under the same $HOME it was started
        # with, and must never outlive its deleted state directory.
        if launched:
            try:
                core.down(cluster)
            except Exception:  # pylint: disable=broad-except
                pass
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        shutil.rmtree(home, ignore_errors=True)


def bench_fleet(seed: int = None) -> dict:
    """Fleet-scale simulation: the zero-hardware millions-of-users run.

    Pure CPU, no device state: the canonical FLEET scenario
    (skypilot_tpu/fleetsim) drives the REAL load balancer, autoscaler,
    replica manager, and state backend against thousands of virtual
    replicas through a diurnal peak, a traffic burst, a 50% decode
    preemption storm, a leaseholder kill, and an LB sever.  Emits the
    headline scale claim plus the per-run control-plane profile (the
    ranked hot paths) for the sqlite backend — and for Postgres too
    when SKYTPU_TEST_PG_URL points at a live server (the CI
    postgres-state job does; psycopg is not in the local image).
    """
    import os

    from skypilot_tpu.fleetsim import fleet_config, run_fleet
    from skypilot_tpu.fleetsim import profile as fleet_profile

    result = run_fleet(fleet_config(seed=seed))
    out = {
        'scale': {
            'sustained_qps_at_slo': result.sustained_qps_at_slo,
            'replicas': result.peak_replicas,
            'pools': result.pools,
            'storm_fraction_pct': result.storm_fraction_pct,
            'recovery_s': result.recovery_s,
            'headline': result.headline(),
            'admitted': result.admitted,
            'shed': result.shed,
            'no_ready': result.no_ready,
            'retried': result.retried,
            'prefix_hit_rate': result.prefix_hit_rate,
            'lease_frozen_s': result.lease_frozen_s,
            'seed': result.seed,
            'horizon_s': result.horizon_s,
            'wall_s': result.wall_s,
        },
        'alerts': result.alerts,
        'profile': {'sqlite': fleet_profile.top(result.profile),
                    'postgres': None},
    }
    pg_url = os.environ.get('SKYTPU_TEST_PG_URL')
    if pg_url:
        pg = run_fleet(fleet_config(seed=seed, db=pg_url))
        out['profile']['postgres'] = fleet_profile.top(pg.profile)
    else:
        out['profile']['note'] = (
            'postgres profile needs SKYTPU_TEST_PG_URL (live server + '
            'psycopg); the CI postgres-state job measures it')
    return out


def main(argv=None) -> None:
    import argparse
    parser = argparse.ArgumentParser()
    parser.add_argument('--seed', type=int, default=None,
                        help='RNG seed for the simulation-backed '
                             'sections (fleet); default: the canonical '
                             'published seed')
    args = parser.parse_args(argv)
    dev = jax.devices()[0]
    print(f'bench: platform={dev.platform} device_kind={dev.device_kind} '
          f'device_count={jax.device_count()}', file=sys.stderr, flush=True)
    if dev.platform != 'tpu':
        sys.exit('bench.py measures the chip; it does not benchmark the '
                 '`tiny` model on another platform in its place')
    on_tpu = True
    from skypilot_tpu.utils import compile_cache
    compile_cache.enable()
    # Control-plane first: hermetic, no device state, and the number is
    # honest-cold (no JAX executables or page cache warmed by training).
    launch = bench_launch()
    train = bench_train(on_tpu)
    # Long-context differentiator: same model/token budget at 2x the
    # sequence length (flash fwd+bwd + per-block remat keep attention
    # memory linear in S; the reference publishes nothing at this axis).
    train_8k = bench_train(on_tpu, seq=8192 if on_tpu else 128,
                           batch=2, steps=8 if on_tpu else 2)
    # Drop the train executables before serving: compiled TPU programs
    # (two big train graphs) hold HBM, and the 7B serve section needs
    # 13.3 GB of params + cache on a 16 GB chip.
    import gc
    jax.clear_caches()
    gc.collect()
    serve = bench_serve(on_tpu)
    # Saturated-TTFT scenario (chunked vs fused long-prompt prefill) —
    # its engines are small; drop the 7B serve state first.
    jax.clear_caches()
    gc.collect()
    serve['saturated'] = bench_saturated_ttft(on_tpu)
    # Cross-request KV reuse: paged KV + radix prefix cache under a
    # shared-prefix sweep (hit rate 0/50/90%) — TTFT/out-tok/s must
    # improve with hit rate and HBM/slot must drop vs contiguous.
    jax.clear_caches()
    gc.collect()
    serve['prefix_cache'] = bench_prefix_cache(on_tpu)
    # Per-chip decode plateau breakers: self-speculative n-gram verify
    # (tokens per dispatch) + int8 KV pages (bytes per token).
    jax.clear_caches()
    gc.collect()
    serve['speculative'] = bench_speculative(on_tpu)
    # SLO-vs-QPS autoscaling comparison: pure-CPU virtual-replica
    # simulation (no device state to manage).
    serve['slo_ramp'] = bench_slo_ramp()
    # Disaggregated prefill/decode vs monolithic at equal chip budget
    # + spot decode-pool preemption resilience (slo_sim-backed).
    serve['disagg'] = bench_disagg()
    # Fleet-scale simulation: real control plane, virtual replicas —
    # pure CPU (runs after the device sections so its thousands of
    # launch threads never race compiled-program HBM).
    fleet = bench_fleet(seed=args.seed)
    # Flight-recorder overhead: ns/event + recorder-on vs -off
    # throughput on the identical workload (tracing is always-on in
    # production, so its cost is a headline, not a footnote).
    jax.clear_caches()
    gc.collect()
    serve['tracing'] = bench_trace_overhead(on_tpu)
    # Telemetry-plane overhead: store ingest duty cycle + the one
    # on-serving-path cost (the radix prefix-fingerprint digest).
    jax.clear_caches()
    gc.collect()
    serve['obs'] = bench_obs_overhead(on_tpu)
    # Training goodput plane: ledger-vs-wall agreement on a real
    # checkpointed run with an injected preemption + the sim-clock
    # straggler/alert validation (tiny model — runs last so its
    # registry resets never race the scrape-based sections).
    jax.clear_caches()
    gc.collect()
    train['goodput'] = bench_goodput(on_tpu)
    print(json.dumps({
        'metric': 'llama_train_mfu_single_chip',
        'value': train['mfu_pct'],
        'unit': '%MFU',
        'vs_baseline': round(train['mfu_pct'] / REFERENCE_MFU, 2),
        'detail': {
            'train': train,
            'train_long_context_8k': train_8k,
            'serve': serve,
            'fleet': fleet,
            'launch': launch,
            'baseline': 'reference Llama-3-8B PyTorch/XLA FSDP v6e-8 '
                        '= 2.225% MFU (examples/tpu/v6e/README.md:34-48)',
        },
    }))


if __name__ == '__main__':
    main()
