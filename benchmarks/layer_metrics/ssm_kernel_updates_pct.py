"""The share of the Mamba-2 head-states that decode steps updated which
the Pallas decode kernel updated (`ops/pallas/ssm_state_update.py`: a
head's float32 tile read once and written once in place), of all decode
steps since the engine was built: the two series of
`skytpu_ssm_state_updates_total` (path="kernel", path="xla") as the
program's /metrics registry renders them.  100 where the kernel engaged on
every step, 0 where every step went through XLA's `ssm_step`: a change of
shapes that falls back reads 0, not "no gain".  Prints both counts.  A
program without the counter (the parent) gives nothing."""
import re

SERIES = re.compile(r'^skytpu_ssm_state_updates_total\{path="(\w+)"\} (\S+)$')


def reduce(ctx):
    from skypilot_tpu.server import metrics as metrics_lib
    updates = {m.group(1): float(m.group(2))
               for m in map(SERIES.match, metrics_lib.render().splitlines())
               if m}
    total = sum(updates.values())
    if not total or 'kernel' not in updates:
        return None
    print(f'ssm_kernel_updates_pct: head-states updated by path {updates}')
    return 100.0 * updates['kernel'] / total
