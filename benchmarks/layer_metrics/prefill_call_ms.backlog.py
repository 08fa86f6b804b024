"""What a turnover's prefill costs the device, as the engine's own ledger
has it: over the `engine.call` spans of the window that are device time
and that carried `prefill` programs and nothing else
(`harness/calls.py`), the median of (interval - what the nearest call
that carried nothing took).  In the cells whose slots
fill in waves that is one program of 32 rows a call; where prompts of
several buckets are admitted together (`yi-6b.batch-backlog`) a call
carries a program a bucket, and the reading is their sum.  Prints it by
the shapes carried (`bucket`, `rows` as compiled) with counts, and how
many such calls the window holds, an interval cut by an edge counting by
the part inside: that number times the median is what
`prefill_share_pct.loop` times the window's device-bound seconds comes to
where the calls are alike.  A program without the span gives None."""
import statistics

from benchmarks.harness import calls as calls_lib

NAME = 'prefill_call_ms.backlog'


def reduce(ctx):
    calls = calls_lib.load(ctx)
    if not calls:
        return None
    only = [c for c in calls_lib.overlapping(calls, 0.0, ctx['seconds'])
            if c['device'] and c['front'] is not None and
            calls_lib.kinds(c) == 'prefill']
    if not only:
        return None
    by_shape, inside = {}, 0.0
    for c in only:
        front = c['front']
        shape = ' + '.join(f'b{p["bucket"]}_n{p["rows"]}'
                           for p in c['carried'])
        by_shape.setdefault(shape, []).append(front * 1e3)
        if front > 0.0:
            inside += calls_lib.clipped(c['start'], c['start'] + front, 0.0,
                                        ctx['seconds']) / front
    shapes = '; '.join(f'{shape}: {statistics.median(ms):.3f} ms x {len(ms)}'
                       for shape, ms in sorted(by_shape.items()))
    print(f'{NAME}: {len(only)} calls carried prefill programs alone, '
          f'{inside:.3f} of them inside the window; by shapes {shapes}')
    return statistics.median(ms for group in by_shape.values()
                             for ms in group)
