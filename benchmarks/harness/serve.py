"""A serving cell: the engine built as `inference/server.py` builds it, the
client's load, the window's readings, and `correct`."""
from __future__ import annotations

import dataclasses
import gc
import shutil
import tempfile
import threading
import time

import jax.numpy as jnp
import numpy as np

from benchmarks import families
from benchmarks.harness import check, loadgen, traffic, weights

COMPILE_COUNTER = 'skytpu_engine_xla_compile_total'
DTYPE = jnp.bfloat16        # weights, activations and cache as served


def counters() -> dict:
    """The program's /metrics registry, summed by family."""
    from skypilot_tpu.server import metrics as metrics_lib
    out = {}
    for line in metrics_lib.render().splitlines():
        if not line or line.startswith('#'):
            continue
        name, _, value = line.rpartition(' ')
        name = name.split('{', 1)[0]
        try:
            out[name] = out.get(name, 0.0) + float(value)
        except ValueError:
            pass
    return out


def memory_peak_bytes(devices):
    stats = [d.memory_stats() for d in devices]
    peaks = [s['peak_bytes_in_use'] for s in stats if s]
    return max(peaks) if peaks else None


def note(what: str, since: float) -> float:
    """A line of the run's own log: what took how long."""
    now = time.perf_counter()
    print(f'[bench] {what}: {now - since:.2f} s', flush=True)
    return now


def build_engine(family, config: dict, dims, seed: int, device):
    import jax
    from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig

    model = families.need(
        family, 'serve_model', 'a serving mix needs the module that '
        '`DecodeEngine` is handed')(dims, config, DTYPE)
    # Every key of `serve` that is a field of `EngineConfig`, by its name.
    fields = {f.name for f in dataclasses.fields(EngineConfig)}
    options = {k: tuple(v) if isinstance(v, list) else v
               for k, v in config['serve'].items() if k in fields}
    t = time.perf_counter()
    with jax.default_device(device):
        params = jax.jit(
            lambda k: family.make_params(k, dims, DTYPE))(
                weights.seed_key(seed))
        jax.block_until_ready(params)
        t = note('weights made on the device', t)
        engine = DecodeEngine(model, params, EngineConfig(**options))
        del params
        t = note('engine built (decode program, layout pass)', t)
        engine.prewarm()
        note('prewarm (prefill programs)', t)
    return engine


class Tracer:
    """The profiler, started and stopped off the client's thread."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix='bench-trace-')
        self._threads = []

    def _run(self, fn):
        t = threading.Thread(target=fn, daemon=True)
        t.start()
        self._threads.append(t)

    def start(self):
        import jax
        self._run(lambda: jax.profiler.start_trace(self.dir))
        return time.perf_counter()

    def stop(self):
        import jax
        for t in self._threads:
            t.join()
        self._run(jax.profiler.stop_trace)
        return time.perf_counter()

    def collect(self):
        from benchmarks.harness import trace as trace_lib
        for t in self._threads:
            t.join()
        try:
            return trace_lib.extract(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def measured(mix: dict, outcome: loadgen.Outcome):
    """The window's requests: those due in it (open loop), or those that
    ended in it (backlog)."""
    recs = outcome.records
    if mix['kind'] == 'open_loop':
        return [r for r in recs if 0.0 <= r.plan.due < outcome.seconds]
    return [r for r in recs if r.error or (
        r.done and r.last is not None and 0.0 <= r.last < outcome.seconds)]


def samples_of(mix: dict, outcome: loadgen.Outcome, recs):
    """(the client's readings by series, the requests answered whole)."""
    whole = [r for r in recs if r.done and not r.error
             and len(r.tokens) == r.plan.max_new]
    out = {
        'tpot_ms': [r.tpot_ms for r in whole if len(r.tokens) > 1],
        'out_tokens_per_s': ([outcome.token_rate] if outcome.token_rate
                             else []),
    }
    if mix['kind'] == 'open_loop':
        out['ttft_ms'] = [r.ttft_ms for r in whole]
        out['gen_late_ms'] = [r.late_ms for r in recs if r.sent is not None]
    return out, whole


def pick_for_check(whole, seed: int, n: int):
    """A sample drawn from the seed, with the longest request in it."""
    if not whole:
        return []
    longest = max(whole, key=lambda r: len(r.plan.prompt) + len(r.tokens))
    rest = [r for r in whole if r is not longest]
    rng = np.random.default_rng(int(seed) + 1)
    idx = rng.permutation(len(rest))[:max(n - 1, 0)]
    return [longest] + [rest[i] for i in idx]


def run_cell(*, family, config, mix, dims, seed, seconds, traced, devices,
             control=False, submit_wrapper=None):
    """Returns (ctx for the readers, result fields).  `submit_wrapper` is
    for the test that breaks the timed path underneath."""
    import jax
    from skypilot_tpu.server import tracing

    engine = build_engine(family, config, dims, seed, devices[0])
    plan = traffic.plan_requests(mix, seed, seconds, dims.vocab)
    submit = engine.submit
    if submit_wrapper is not None:
        submit = submit_wrapper(submit)
    tracer = Tracer() if traced else None
    trace_at = float(mix.get('trace_at_share', 0.4)) * seconds
    trace_len = min(float(mix.get('trace_s', 3.0)), seconds - trace_at)
    hooks = {0.0: counters, float(seconds): counters}
    if tracer:
        hooks[trace_at] = tracer.start
        hooks[trace_at + trace_len] = tracer.stop
    engine.start()
    try:
        outcome = loadgen.drive(
            submit, plan, seconds=seconds, traced=traced,
            drain=mix['kind'] == 'open_loop',
            drain_limit_s=float(mix.get('drain_limit_s', 30.0)), at=hooks)
    finally:
        engine.stop()
    peak = memory_peak_bytes(devices)
    t = note('lead-in, window and drain', outcome.t_open - float(mix['lead_in_s']))
    recs = measured(mix, outcome)
    samples, whole = samples_of(mix, outcome, recs)
    spans = {}
    if traced:
        for r in recs:
            for ev in tracing.events_for(r.rid) if r.rid else ():
                if ev['dur_ms'] is not None:
                    spans.setdefault(ev['name'], []).append(ev['dur_ms'])
    before, after = outcome.hooks[0.0], outcome.hooks[float(seconds)]
    trace = tracer.collect() if tracer else None
    failed = [r for r in recs if r not in whole]
    if engine.error is not None:
        raise SystemExit(f'the engine died: {engine.error!r}')
    picked = pick_for_check(whole, seed, int(mix.get('check_sample', 6)))
    del engine
    gc.collect()
    jax.clear_caches()
    verdict = {'widest_gap': None}
    if picked:
        longest_out = int(mix['output_tokens'].get(
            'max', mix['output_tokens'].get('value', 0)))
        verdict = check.served_gap(
            family, dims, seed, DTYPE,
            [(r.plan.prompt, r.tokens) for r in picked],
            (int(mix['prompt_tokens']['max']) + longest_out, longest_out),
            config['check']['control'] if control else None)
    note('trace read and reference', t)
    limits = config['check']
    print(f'correct: widest gap of a served token below the reference\'s '
          f'best {verdict["widest_gap"]} (limit '
          f'{limits["served_gap_limit"]}), mean gap '
          f'{verdict.get("mean_gap")} (limit {limits["mean_gap_limit"]}), '
          f'over {verdict.get("positions", 0)} positions of {len(picked)} '
          f'requests; requests whole {len(whole)} of {len(recs)} '
          f'(limit: all)')
    verdict.update(requests_whole=len(whole), limits={
        'widest_gap': limits['served_gap_limit'],
        'mean_gap': limits['mean_gap_limit'], 'requests_whole': len(recs)})
    correct = bool(picked and verdict['finite'] and not failed and
                   verdict['widest_gap'] <= limits['served_gap_limit'] and
                   verdict['mean_gap'] <= limits['mean_gap_limit'])
    ctx = {
        'samples': samples, 'spans': spans,
        'counters': {k: after.get(k, 0.0) - before.get(k, 0.0)
                     for k in after},
        'trace': trace, 'records': recs,
        'trace_span': ((trace_at, trace_at + trace_len) if traced else None),
        'memory_peak_bytes': peak,
    }
    info = {'correct': correct, 'attempted': len(recs), 'failed': len(failed),
            't_open': outcome.t_open, 'queue_at_open': outcome.queue_at_open,
            'queue_at_close': outcome.queue_at_close, 'check': verdict}
    return ctx, info
