"""Held experts that at least one token of a decode step reached, a layer
and step, over the window: the delta of `skytpu_moe_experts_touched_total`
(summed over layers and steps on the device) over the window's layer-steps.
A layer-step routes n_slots * top_k pairs, held here or elsewhere, so the
layer-steps are the delta of `skytpu_moe_pairs_total` over that.  It is
what `decode_step_cost` counts expert bytes by; the program counts every
row of the decode batch, a free slot's too.  A program without the
counters (the parent) gives nothing."""
TOUCHED = 'skytpu_moe_experts_touched_total'
PAIRS = 'skytpu_moe_pairs_total'


def reduce(ctx):
    touched, pairs = ctx['counters'].get(TOUCHED), ctx['counters'].get(PAIRS)
    if touched is None or not pairs:
        return None
    dims = ctx['dims']
    layer_steps = pairs / (ctx['config']['serve']['n_slots'] * dims.top_k)
    even = ctx['family'].touched_experts(
        dims, ctx['config']['serve']['n_slots'])
    print(f'moe_experts_touched_per_step: {touched:.0f} touched in '
          f'{layer_steps:.0f} layer-steps; even routing would touch '
          f'{even:.2f} of {dims.held}')
    return touched / layer_steps
