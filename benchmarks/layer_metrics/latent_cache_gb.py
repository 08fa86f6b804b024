"""Gigabytes of latent in the engine's cache (latent attention: one
vector and its rotated part a position and layer, every slot): the gauge
`skytpu_engine_cache_bytes{kind="latent"}` that the engine sets when it is
built, which is slots x max_seq_len x layers x (kv_lora_rank +
qk_rope_head_dim) x 2 B.  Prints the other kinds beside it.  A program
without the gauge's kind (the parent), or a model without a latent, gives
nothing."""
import re

SERIES = re.compile(r'^skytpu_engine_cache_bytes\{kind="(\w+)"\} (\S+)$')


def reduce(ctx):
    from skypilot_tpu.server import metrics as metrics_lib
    sized = {m.group(1): float(m.group(2))
             for m in map(SERIES.match, metrics_lib.render().splitlines())
             if m}
    if 'latent' not in sized:
        return None
    print(f'latent_cache_gb: cache bytes by kind {sized}')
    return sized['latent'] / 1e9
