"""The share of the cache's K/V positions that the decode steps' attention
asked for, of the contiguous decode calls since the engine was built: the
two series of `skytpu_engine_decode_kv_positions_total` (kind="fetched",
kind="held") as the program's /metrics registry renders them.  `held` is
slots x max_seq_len a step; `fetched` counts whole tiles up to each slot's
length where the attention is bounded by the lengths, and equals `held`
where it reads every slot whole.  Prints both counts.  A program without
the counter (the parent) gives nothing."""
import re

SERIES = re.compile(
    r'^skytpu_engine_decode_kv_positions_total\{kind="(\w+)"\} (\S+)$')


def reduce(ctx):
    from skypilot_tpu.server import metrics as metrics_lib
    positions = {m.group(1): float(m.group(2))
                 for m in map(SERIES.match, metrics_lib.render().splitlines())
                 if m}
    if not positions.get('held') or 'fetched' not in positions:
        return None
    print(f'decode_kv_fetched_pct: positions by kind {positions}')
    return 100.0 * positions['fetched'] / positions['held']
