"""A decode step's device time as the engine's own ledger has it, over the
whole window: the median interval of the `engine.call` spans that are
device time (the fetch waited, and so did the next one) and that carried
nothing, over the call's `steps` (passes, for generation by blocks).  `harness/calls.py` says how
the window's opening is found on the recorder's clock and what a span is.
Prints how many calls, their p5 and p95, and the same reading over the
traced seconds alone (`ctx['trace_span']`) beside the trace's own
`decode_step_ms`: the same seconds by two instruments.

One clock: in a traced run every such span ends where an
`engine.loop.fetch` phase event of the trace ends.  The reader pairs them
(`calls.clock_offset`), prints the one offset between the profiler's
clock and the window's, and for the calls of the traced seconds that
carried nothing the median of (interval - the duration of the `jit_decode`
program that ended inside it, on the `XLA Modules` line): the part of a
call's interval that is not its program, which is how far a host stamp
can be trusted as a device time.  A program without the span gives
None."""
import statistics

from benchmarks.harness import calls as calls_lib
from benchmarks.harness import phases, trace as trace_lib
from benchmarks.harness.reducers import percentile

NAME = 'decode_step_ms.loop'


def step_ms(calls):
    """(median, p5, p95, count) of a step over the calls alone."""
    own = [c['s'] * 1e3 / c['steps'] for c in calls_lib.alone(calls)]
    if not own:
        return None
    return (statistics.median(own), percentile(own, 5),
            percentile(own, 95), len(own))


def not_the_program(ctx, calls, traced):
    """The clocks' offset, and interval - program over `traced`."""
    fetches = phases.phase_events(ctx['trace']).get('engine.loop.fetch')
    paired = fetches and calls_lib.clock_offset(calls, sorted(fetches))
    if not paired:
        print(f'{NAME}: no pairing of the trace\'s '
              f'{len(fetches or ())} engine.loop.fetch events with the calls')
        return
    off = paired['offset_s']
    print(f'{NAME}: {paired["pairs"]} engine.loop.fetch events of the trace '
          f'end where calls {calls[paired["first"]]["seq"]}.. end (waits '
          f'agree to {paired["wait_miss_s"] * 1e6:.1f} us); the profiler\'s '
          f'clock less the window\'s {off:.6f} s, middle half within '
          f'{paired["spread_s"] * 1e6:.1f} us')
    programs = sorted(
        ((s + d) / 1e9 - off, d / 1e9)
        for lines in ctx['trace']['device'].values()
        for name, s, d in lines.get(trace_lib.MODULES_LINE, [])
        if 'jit_decode' in name)
    rest, lag = [], []
    for c in calls_lib.alone(traced):
        inside = [(e, d) for e, d in programs
                  if c['start'] < e <= c['end'] + 1e-3]
        if inside:
            e, d = inside[-1]
            rest.append((c['s'] - d) * 1e3)
            lag.append((c['end'] - e) * 1e3)
    if rest:
        print(f'{NAME}: over {len(rest)} calls of the traced seconds that '
              f'carried nothing, interval - the jit_decode program\'s '
              f'duration: median {statistics.median(rest):.4f} ms (p5 '
              f'{percentile(rest, 5):.4f}, p95 '
              f'{percentile(rest, 95):.4f}); fetch\'s return - '
              f'the program\'s end on the device line: median '
              f'{statistics.median(lag):.4f} ms')


def reduce(ctx):
    calls = calls_lib.load(ctx)
    if not calls:
        return None
    read = step_ms(calls_lib.overlapping(calls, 0.0, ctx['seconds']))
    if not read:
        return None
    print(f'{NAME}: {read[3]} device-bound calls of the window carried '
          f'nothing: a step {read[0]:.4f} ms (p5 {read[1]:.4f}, p95 '
          f'{read[2]:.4f})')
    span = ctx.get('trace_span')
    if span:
        traced = calls_lib.overlapping(calls, *span)
        part = step_ms(traced)
        theirs = ctx['values'].get('decode_step_ms')
        print(f'{NAME}: over the traced seconds {span[0]:.2f}-{span[1]:.2f} '
              f'alone: ' + (f'{part[0]:.4f} ms over {part[3]} calls'
                            if part else 'no such call') +
              (f'; the trace\'s decode_step_ms {theirs:.4f}'
               if theirs is not None else ''))
        if ctx.get('trace'):
            not_the_program(ctx, calls, traced)
    return read[0]
