"""The rate sweep of an open-loop cell, in one process: the engine is built
once and each rate gets a short window of its own.  The knee is the highest
rate at which no more requests wait for a first token at the window's end
than at its opening; the cell's mix then states four fifths of it.

    python3 benchmarks/tools/sweep.py --workload <cell> --rates 6,8,10 --seconds 15
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import families, run as run_lib  # noqa: E402
from benchmarks.harness import loadgen, manifest, reducers, serve  # noqa: E402
from benchmarks.harness import traffic  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--rates', required=True)
    ap.add_argument('--seconds', type=float, default=15.0)
    ap.add_argument('--seed', type=int, default=1)
    ap.add_argument('--rehearse', action='store_true')
    args = ap.parse_args()
    man = manifest.manifest()
    cell = manifest.cell(man, args.workload)
    config = manifest.config_of(man, cell['config'])
    mix = manifest.traffic_of(cell['traffic'])
    if args.rehearse:
        run_lib.shrink_for_rehearsal(config, mix)
    devices = run_lib.claim_devices(cell['chips'], args.rehearse)
    if not args.rehearse:
        from skypilot_tpu.utils import compile_cache
        compile_cache.enable()
    family = families.load(config)
    dims = family.dims(config)
    t0 = time.perf_counter()
    engine = serve.build_engine(family, config, dims, args.seed, devices[0])
    print(json.dumps({'build_s': time.perf_counter() - t0,
                      'device': devices[0].device_kind}), flush=True)
    engine.start()
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(',')):
            m = copy.deepcopy(mix)
            m['arrivals']['rate_per_s'] = rate
            plan = traffic.plan_requests(m, args.seed + i, args.seconds,
                                         dims.vocab)
            out = loadgen.drive(engine.submit, plan, seconds=args.seconds,
                                traced=False, drain=True, drain_limit_s=60.0)
            recs = serve.measured(m, out)
            samples, whole = serve.samples_of(m, out, recs)
            print(json.dumps({
                'rate_per_s': rate, 'due_in_window': len(recs),
                'whole': len(whole), 'queue_at_open': out.queue_at_open,
                'queue_at_close': out.queue_at_close,
                'ttft_p50_ms': reducers.percentile(samples['ttft_ms'], 50),
                'ttft_p95_ms': reducers.percentile(samples['ttft_ms'], 95),
                'tpot_p50_ms': reducers.percentile(samples['tpot_ms'], 50),
                'gen_late_p99_ms': reducers.percentile(
                    samples['gen_late_ms'], 99),
                'tokens_per_s': out.token_rate,
            }), flush=True)
            time.sleep(float(m.get('tail_s', 2.0)))     # the tail runs out
    finally:
        engine.stop()
    return 0


if __name__ == '__main__':
    sys.exit(main())
