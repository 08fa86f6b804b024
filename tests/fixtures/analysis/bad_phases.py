"""Known-bad and known-good loop-phase call sites: a phase's name is
held to the span registry like a recorded span's (metric-naming rule)."""
from skypilot_tpu.server import tracing


def loop(sleep):
    with tracing.phase('engine.loop.rogue'):      # BAD: no SPAN_HELP
        sleep()
    with tracing.phase('Loop'):                   # BAD: illegal name
        sleep()
    with tracing.phase('engine.loop.fetch') as ph:    # registered: clean
        sleep()
    return ph.seconds
