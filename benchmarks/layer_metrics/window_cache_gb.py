"""Gigabytes of window layers' rings in the engine's cache (a window layer
keeps its window's positions a slot, whatever the context): the gauge
`skytpu_engine_cache_bytes{kind="window"}` that the engine sets when it is
built, which is slots x window x window layers x KV heads x (key + value
widths) x 2 B.  Prints the other kinds beside it.  A program without the
gauge's kind (the parent), or a model without a window layer, gives
nothing."""
import re

SERIES = re.compile(r'^skytpu_engine_cache_bytes\{kind="(\w+)"\} (\S+)$')


def reduce(ctx):
    from skypilot_tpu.server import metrics as metrics_lib
    sized = {m.group(1): float(m.group(2))
             for m in map(SERIES.match, metrics_lib.render().splitlines())
             if m}
    if 'window' not in sized:
        return None
    print(f'window_cache_gb: cache bytes by kind {sized}')
    return sized['window'] / 1e9
