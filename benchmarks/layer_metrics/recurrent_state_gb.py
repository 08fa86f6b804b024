"""Gigabytes of per-slot recurrent state in the engine's cache (a linear
layer's float32 matrix a head and its convolution taps, every slot): the
gauge `skytpu_engine_cache_bytes{kind="recurrent"}` that the engine sets
when it is built.  Prints the keys and values beside it.  A program
without the gauge (the parent), or a model without such state, gives
nothing."""
import re

SERIES = re.compile(r'^skytpu_engine_cache_bytes\{kind="(\w+)"\} (\S+)$')


def reduce(ctx):
    from skypilot_tpu.server import metrics as metrics_lib
    sized = {m.group(1): float(m.group(2))
             for m in map(SERIES.match, metrics_lib.render().splitlines())
             if m}
    if 'recurrent' not in sized:
        return None
    print(f'recurrent_state_gb: cache bytes by kind {sized}')
    return sized['recurrent'] / 1e9
