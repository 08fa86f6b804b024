"""The share of the held experts that decode steps reached which the
grouped decode kernel multiplied (`ops/pallas/grouped_experts.py`), of all
decode steps since the engine was built: the two series of
`skytpu_moe_expert_trips_total` (path="kernel", path="loop") as the
program's /metrics registry renders them.  100 where the kernel engaged on
every step, 0 where every step went through the block loop: a change of
shapes that falls back to the loop reads 0, not "no gain".  Prints both
counts.  A program without the counter (the parent) gives nothing."""
import re

SERIES = re.compile(r'^skytpu_moe_expert_trips_total\{path="(\w+)"\} (\S+)$')


def reduce(ctx):
    from skypilot_tpu.server import metrics as metrics_lib
    trips = {m.group(1): float(m.group(2))
             for m in map(SERIES.match, metrics_lib.render().splitlines())
             if m}
    total = sum(trips.values())
    if not total or 'kernel' not in trips:
        return None
    print(f'moe_kernel_trips_pct: experts multiplied by path {trips}')
    return 100.0 * trips['kernel'] / total
