"""The busiest held expert's token-expert pairs over the mean of the held
experts', over decode steps since the engine was built and summed over
layers: the series of `skytpu_moe_expert_tokens_total{expert=...}` as the
program's /metrics registry renders them (an expert that no token reached
has no series and counts as 0).  1 is even routing.  A program without the
counter (the parent) gives nothing."""
import re

SERIES = re.compile(r'^skytpu_moe_expert_tokens_total\{expert="(\d+)"\} '
                    r'(\S+)$')


def reduce(ctx):
    from skypilot_tpu.server import metrics as metrics_lib
    load = [float(m.group(2))
            for m in map(SERIES.match, metrics_lib.render().splitlines())
            if m]
    if not load or not sum(load):
        return None
    held = ctx['dims'].held
    print(f'moe_load_max_over_mean: {len(load)} of {held} held experts were '
          f'reached; busiest {max(load):.0f}, least busy of those reached '
          f'{min(load):.0f} pairs')
    return max(load) / (sum(load) / held)
