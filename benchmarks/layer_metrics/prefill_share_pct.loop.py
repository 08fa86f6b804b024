"""The share of the device's time that went to prefill, over the whole
window, as the engine's own ledger has it: over the `engine.call` spans
that are device time (`harness/calls.py`), the part of an interval in
front of its decode call (interval - what the nearest call that carried
nothing took), summed over the calls that carried a `prefill` or a `chunk`
program, over the sum of all those intervals; both clipped at the window's
edges.  Prints the seconds by what was carried, and the same share over
the traced seconds alone (`ctx['trace_span']`) beside the trace's own
`prefill_share_pct` where this run has it.  A program without the span
gives None."""
from benchmarks.harness import calls as calls_lib

NAME = 'prefill_share_pct.loop'


def reduce(ctx):
    calls = calls_lib.load(ctx)
    if not calls:
        return None
    told = calls_lib.split(calls, 0.0, ctx['seconds'])
    if not told['device_s']:
        return None
    by_kind = ', '.join(f'{k} {v:.4f}' for k, v in sorted(
        told['by_kind'].items())) or 'nothing'
    print(f'{NAME}: device-bound intervals {told["device_s"]:.4f} s of the '
          f'window\'s {ctx["seconds"]:.1f} s (host-bound '
          f'{told["host_s"]:.4f} s; the rest the engine was idle); in front '
          f'of the decode call, by what was carried: {by_kind}')
    span = ctx.get('trace_span')
    if span:
        part = calls_lib.split(calls, span[0], span[1])
        theirs = ctx['values'].get('prefill_share_pct')
        print(f'{NAME}: over the traced seconds {span[0]:.2f}-{span[1]:.2f} '
              f'alone: ' + (
                  f'{100.0 * part["prefill_s"] / part["device_s"]:.3f} % '
                  f'({part["prefill_s"]:.4f} of {part["device_s"]:.4f} s)'
                  if part['device_s'] else 'no device-bound call') +
              (f'; the trace\'s prefill_share_pct {theirs:.3f}'
               if theirs is not None else ''))
    return 100.0 * told['prefill_s'] / told['device_s']
