"""See `benchmarks/harness/phases.py idle_attributed_pct`."""
from benchmarks.harness import phases


def reduce(ctx):
    return phases.idle_attributed_pct('idle_attributed_pct.train', ctx)
