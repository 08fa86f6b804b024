"""Host milliseconds a training step costs outside the wait for the
device: the trace's `train.feed` + `train.dispatch` + `train.export`
events, summed, over the number of `train.dispatch` events."""
from benchmarks.harness import phases

PARTS = ('train.feed', 'train.dispatch', 'train.export')


def reduce(ctx):
    trace = ctx.get('trace')
    if not trace:
        return None
    phases.note_host_events('train_host_per_step_ms', trace)
    events = phases.phase_events(trace)
    steps = len(events.get('train.dispatch', ()))
    if not steps:
        return None
    ms = {name: sum(b - a for a, b in events.get(name, ())) / 1e6
          for name in PARTS + ('train.fetch',)}
    print('train_host_per_step_ms: over ' + str(steps) + ' steps, ms a '
          'step: ' + ', '.join(f'{k} {v / steps:.4f}' for k, v in ms.items())
          + ' (train.fetch is the wait for the device, not counted)')
    return sum(ms[name] for name in PARTS) / steps
