"""What the decode steps' window layers fetched of their K and V, as a
share of what layers that kept the whole context would have read, of the
contiguous decode calls since the engine was built: the two series of
`skytpu_engine_window_kv_positions_total` (kind="fetched": a ring of the
window's positions a slot and step; kind="context": the positions the
slots' contexts held at those steps) as the program's /metrics registry
renders them.  About 100 x window / mean context; 100 and more where a
window layer reads as much as its context holds.  Prints both counts.  A
program without the counter (the parent), or a model without a window
layer, gives nothing."""
import re

SERIES = re.compile(
    r'^skytpu_engine_window_kv_positions_total\{kind="(\w+)"\} (\S+)$')


def reduce(ctx):
    from skypilot_tpu.server import metrics as metrics_lib
    positions = {m.group(1): float(m.group(2))
                 for m in map(SERIES.match, metrics_lib.render().splitlines())
                 if m}
    if not positions.get('context') or 'fetched' not in positions:
        return None
    print(f'window_kv_fetched_pct: positions by kind {positions}')
    return 100.0 * positions['fetched'] / positions['context']
