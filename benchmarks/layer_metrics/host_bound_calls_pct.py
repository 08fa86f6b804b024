"""The share of the window's fetched decode calls that were done before
the host asked for them: `engine.call` spans with `bound` = `host` (the
fetch returned in under a millisecond, the interval since the fetch
before it is under half a decode call, or the host came for it later than
a decode call takes: the program's rule, `SPAN_HELP`)
over all of them (`harness/calls.py`; a call is the window's if it ended
in it).  The pipelined loop dispatches call k+1 before it fetches call k,
so such a call means the loop thread was held until the device ran out
of work: in a traced run, the profiler's stop.  An engine that runs
out of requests dispatches nothing and fetches nothing, and is not
counted.  Prints the longest stretch that such a call closes, its length
and its place in the window: the call and the one before it, whose
fetch's return the same hold may have kept back (`harness/calls.py`); and
the two waits nearest the line between `host` and `device`.  A program
without the span gives None."""
from benchmarks.harness import calls as calls_lib

NAME = 'host_bound_calls_pct'


def reduce(ctx):
    calls = calls_lib.load(ctx)
    if not calls:
        return None
    window = [c for c in calls if 0.0 <= c['end'] < ctx['seconds']]
    if not window:
        return None
    late = [c for c in window if c['bound'] == 'host']
    waited = [c['waited_s'] for c in window if c['bound'] == 'device']
    line = (f'the shortest wait of a device-bound call '
            f'{min(waited) * 1e3:.3f} ms' if waited else
            'no device-bound call')
    if late:
        by_seq = {c['seq']: c for c in calls}
        start, worst = min(
            ((by_seq.get(c['seq'] - 1, c)['start'], c) for c in late),
            key=lambda sc: sc[0] - sc[1]['end'])
        span = ctx.get('trace_span')
        where = (f' (the traced seconds are {span[0]:.2f}-{span[1]:.2f})'
                 if span else '')
        print(f'{NAME}: {len(late)} of {len(window)} calls found done; the '
              f'longest stretch one closes, with the call before it, '
              f'{(worst["end"] - start) * 1e3:.1f} ms, from {start:.3f} s '
              f'to {worst["end"]:.3f} s of the window{where}, seq '
              f'{worst["seq"]}; the longest wait of a host-bound call '
              f'{max(c["waited_s"] for c in late) * 1e3:.3f} ms, {line}')
    else:
        print(f'{NAME}: 0 of {len(window)} calls found done; {line}')
    return 100.0 * len(late) / len(window)
