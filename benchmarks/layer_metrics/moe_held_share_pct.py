"""The share of token-expert pairs routed to an expert held here, of all
pairs routed by decode steps since the engine was built: the two series of
`skytpu_moe_pairs_total` (where="held", where="elsewhere") as the
program's /metrics registry renders them.  held / experts of the
configuration (12.5%) if routing is even.  A program without the counter
(the parent) gives nothing."""
import re

SERIES = re.compile(r'^skytpu_moe_pairs_total\{where="(\w+)"\} (\S+)$')


def reduce(ctx):
    from skypilot_tpu.server import metrics as metrics_lib
    pairs = {m.group(1): float(m.group(2))
             for m in map(SERIES.match, metrics_lib.render().splitlines())
             if m}
    total = sum(pairs.values())
    if not total or 'held' not in pairs:
        return None
    return 100.0 * pairs['held'] / total
