"""Experts that at least one row of a pass reached, a layer and pass, over
the window (generation by blocks): the delta of
`skytpu_moe_experts_touched_total` (summed over layers and passes on the
device) over the window's layer-passes.  A layer-pass routes n_slots *
block_length * top_k pairs (every row of every slot's block, a free slot's
too), so the layer-passes are the delta of `skytpu_moe_pairs_total` over
that; `moe_experts_touched_per_step` divides by n_slots * top_k, a token a
slot, and would read a quarter here.  It is what `decode_step_cost` counts
expert bytes by.  A family without a block length, or a program without
the counters (the parent), gives nothing."""
TOUCHED = 'skytpu_moe_experts_touched_total'
PAIRS = 'skytpu_moe_pairs_total'


def reduce(ctx):
    touched, pairs = ctx['counters'].get(TOUCHED), ctx['counters'].get(PAIRS)
    dims = ctx['dims']
    block = getattr(dims, 'block', None)
    if touched is None or not pairs or not block:
        return None
    rows = ctx['config']['serve']['n_slots'] * block
    layer_passes = pairs / (rows * dims.top_k)
    even = ctx['family'].touched_experts(dims, rows)
    print(f'moe_experts_touched_per_pass: {touched:.0f} touched in '
          f'{layer_passes:.0f} layer-passes of {rows} rows; even routing '
          f'would touch {even:.2f} of {dims.held}')
    return touched / layer_passes
