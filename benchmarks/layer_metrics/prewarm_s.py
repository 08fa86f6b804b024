"""Seconds in `DecodeEngine.prewarm()`: the span `engine.setup.prewarm`
of the flight recorder's request `engine-setup`.  Prints how many
programs it compiled (the `engine.setup.compile` spans inside it), their
sum and the slowest, and the layout pass that ran before it."""
SETUP_RID = 'engine-setup'


def reduce(ctx):
    from skypilot_tpu.server import tracing
    events = tracing.events_for(SETUP_RID)
    whole = [e for e in events if e['name'] == 'engine.setup.prewarm']
    if not whole:
        return None
    lo = whole[0]['ts']
    hi = whole[-1]['ts'] + whole[-1]['dur_ms'] / 1e3
    inside = [e for e in events if e['name'] == 'engine.setup.compile'
              and lo <= e['ts'] <= hi]
    layouts = sum(e['dur_ms'] for e in events
                  if e['name'] == 'engine.setup.layouts')
    if inside:
        slowest = max(inside, key=lambda e: e['dur_ms'])
        print(f'prewarm_s: {len(inside)} programs compiled in '
              f'{sum(e["dur_ms"] for e in inside) / 1e3:.2f} s; the slowest '
              f'{slowest["dur_ms"] / 1e3:.2f} s {slowest["attrs"]}; the '
              f'layout pass before them {layouts / 1e3:.2f} s')
    return sum(e['dur_ms'] for e in whole) / 1e3
