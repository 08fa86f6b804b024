"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Claims the cell's chips, builds the configuration with weights made on the
device from the seed, warms the shapes the cell uses, measures for
`--seconds`, checks what the timed path produced against the plain reference
and prints one JSON object as its last line.  Without a TPU, or on a device
kind that `benchmarks/peaks.json` does not list, it fails: there is no
fall-back.  `--rehearse` runs the same control flow at tiny widths on
whatever device there is (the CPU); its line says `"rehearsal": true`, names
the real device and carries no metric under a device metric's name.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault('SKYTPU_TRACE_RING_SIZE', '65536')
os.environ.setdefault('TPU_LOG_DIR', 'disabled')

from benchmarks import families  # noqa: E402
from benchmarks.harness import manifest, reducers  # noqa: E402
from benchmarks.harness import trace as trace_lib  # noqa: E402


def claim_devices(chips: int, rehearse: bool):
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f'benchmark: no accelerator: {e}')
    if not rehearse and devs[0].platform != 'tpu':
        raise SystemExit(f'benchmark: platform is {devs[0].platform!r}, '
                         f'not a TPU; nothing is measured on it')
    if len(devs) < chips:
        raise SystemExit(f'benchmark: the cell needs {chips} chip(s), '
                         f'JAX finds {len(devs)}')
    return devs[:chips]


def shrink_for_rehearsal(config: dict, mix: dict) -> None:
    """Tiny widths and lengths, in place: control flow only.  The widths
    are the family's, the rest `rehearsal.json`'s."""
    tiny = manifest.load_json(manifest.BENCH_DIR, 'rehearsal.json')
    config.update(families.load(config).REHEARSAL)
    for group in ('serve', 'train', 'check'):    # limits read at this size
        if group in config:
            config[group].update({k: v for k, v in tiny.get(group, {}).items()
                                  if group != 'check' or k in config[group]})
    for k, v in tiny['traffic'].items():
        if k in mix:
            if isinstance(v, dict):
                mix[k].update(v)
            else:
                mix[k] = v


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    ap.add_argument('--rehearse', action='store_true')
    ap.add_argument('--dump-trace', default=None,
                    help='write the head of the reduced trace here (JSON): '
                    'how the recorded trace of the tests was made')
    ap.add_argument('--control', type=int, choices=(0, 1), default=0,
                    help='also read the lower-precision control (the '
                    'driver never asks for it)')
    args = ap.parse_args(argv)

    man = manifest.manifest()
    cell = manifest.cell(man, args.workload)
    config = manifest.config_of(man, cell['config'])
    mix = manifest.traffic_of(cell['traffic'])
    if args.rehearse:
        shrink_for_rehearsal(config, mix)
    devices = claim_devices(cell['chips'], args.rehearse)
    import jax
    kind = devices[0].device_kind
    peaks = None if args.rehearse else manifest.peaks_for(kind)
    # The program's own switch: JAX_COMPILATION_CACHE_DIR where it is set,
    # else <checkout>/.jax_cache.  Small programs are cached too.
    if not args.rehearse:           # CPU entries are of no use to a chip run
        from skypilot_tpu.utils import compile_cache
        compile_cache.enable()
        jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
        jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
    from skypilot_tpu.perf import compile_telemetry
    compile_telemetry.install()

    family = families.load(config)
    dims = family.dims(config)
    if mix['kind'] == 'train':
        from benchmarks.harness import train as driver
    else:
        from benchmarks.harness import serve as driver
    ctx, info = driver.run_cell(
        family=family, config=config, mix=mix, dims=dims, seed=args.seed,
        seconds=args.seconds, traced=bool(args.trace), devices=devices,
        control=bool(args.control))
    ctx.update(family=family, dims=dims, config=config, mix=mix, peaks=peaks,
               chips=cell['chips'], seconds=args.seconds, values={})
    ctx['samples']['setup_s'] = [info['t_open'] - _T0]

    group = 'per_layer' if args.trace else 'end_to_end'
    metrics = {}
    for m in manifest.metrics_of(man, args.workload, group):
        value = reducers.reduce_metric(m['name'], ctx)
        if value is None:
            continue
        ctx['values'][m['name']] = value
        metrics[m['name']] = {'value': value, 'unit': m['unit']}
    device = {'platform': devices[0].platform, 'kind': kind,
              'count': len(devices),
              'memory_peak_bytes': ctx['memory_peak_bytes']}
    line = {'correct': info['correct'], 'attempted': info['attempted'],
            'failed': info['failed'], 'metrics': metrics, 'device': device,
            'workload': args.workload, 'seed': args.seed}
    for k in ('queue_at_open', 'queue_at_close'):
        if k in info:
            line[k] = info[k]
    for series in ('ttft_ms', 'tpot_ms'):
        if series in ctx['samples']:
            line.setdefault('sample_counts', {})[series] = len(
                ctx['samples'][series])
    if ctx.get('trace'):
        busy = trace_lib.busy(ctx['trace'])
        device.update(busy_s=busy['busy_s'], window_s=busy['window_s'])
        line['breakdown'] = {
            'device_ops': trace_lib.op_seconds(ctx['trace']),
            'idle_gaps': trace_lib.idle_gaps(ctx['trace'])}
        line['programs'] = trace_lib.module_names(ctx['trace'])
        if args.dump_trace:
            with open(args.dump_trace, 'w', encoding='utf-8') as f:
                json.dump(trace_lib.head(ctx['trace'], 400), f)
    if args.rehearse:
        line['rehearsal'] = True
        line['rehearsal_metrics'] = line.pop('metrics')
        line['metrics'] = {}
    # Each number compared, beside its limit: the line's last key, and the
    # last line of standard error.
    line['check'] = info['check']
    print(json.dumps(line), flush=True)
    print(f'check: {json.dumps(info["check"])}', file=sys.stderr, flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
