"""The share of the window that the engine's loop thread spent working
on the host: the delta of `skytpu_engine_loop_busy_seconds_total` (the
phases engine.loop.dispatch + emit + admit) over the window's seconds.
The program flushes its sums every half second, so the delta is good to
about a sixtieth of a 30 s window."""
BUSY = 'skytpu_engine_loop_busy_seconds_total'
WAIT = 'skytpu_engine_loop_wait_seconds_total'


def reduce(ctx):
    busy = ctx['counters'].get(BUSY)
    if busy is None or not ctx.get('seconds'):
        return None
    wait = ctx['counters'].get(WAIT, 0.0)
    print(f'loop_host_busy_pct: busy {busy:.4f} s, waiting (device and '
          f'idle) {wait:.4f} s, together {busy + wait:.4f} s of the '
          f'window\'s {ctx["seconds"]:.1f} s')
    return 100.0 * busy / ctx['seconds']
