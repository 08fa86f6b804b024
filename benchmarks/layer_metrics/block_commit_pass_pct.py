"""The share of slot-passes that were commit passes (generation by blocks:
the clean block run once more so that its K and V are in the cache; such a
pass decides no token), of all passes since the engine was built: the two
series of `skytpu_engine_block_passes_total` (kind="commit",
kind="denoise") as the program's /metrics registry renders them.  1 of
s + 1 at s denoising steps: 20 at 4; what fusing a commit with the next
block's first pass would take out.  Prints both counts.  A program without
the counter (the parent, a model that generates a token a step) gives
nothing."""
import re

SERIES = re.compile(
    r'^skytpu_engine_block_passes_total\{kind="(\w+)"\} (\S+)$')


def reduce(ctx):
    from skypilot_tpu.server import metrics as metrics_lib
    passes = {m.group(1): float(m.group(2))
              for m in map(SERIES.match, metrics_lib.render().splitlines())
              if m}
    total = sum(passes.values())
    if not total or 'commit' not in passes:
        return None
    print(f'block_commit_pass_pct: slot-passes by kind {passes}')
    return 100.0 * passes['commit'] / total
