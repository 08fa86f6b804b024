"""The share of token-expert pairs that the router sent past the experts
(its output that is no expert: the token reads no expert's weights), of
all pairs routed by decode steps in the window: the delta of
`skytpu_moe_skipped_pairs_total` over it plus the delta of
`skytpu_moe_pairs_total`, whose two series hold the pairs that went to an
expert.  1 / (experts + 1) if every output of the router is as likely as
another.  It is a reading of the router the weights were seeded with, and
of nothing a program does: a program that skips more has other
mathematics, not a faster step.  A program without the counter (the
parent), or a router without such an output, gives nothing.

It also prints the experts a layer-step reached with the skipped pairs IN
the layer-steps (all pairs / slots): `moe_experts_touched_per_step`
takes its layer-steps from `skytpu_moe_pairs_total` alone, which leaves
the skipped pairs out, and is not listed for a cell whose router skips
(`cells_with` in this reader's manifest: the sizes' attribute that such a
family has).  `routing.touched_over_even` of the configuration is this
printed number over the even count."""
SKIPPED = 'skytpu_moe_skipped_pairs_total'
PAIRS = 'skytpu_moe_pairs_total'
TOUCHED = 'skytpu_moe_experts_touched_total'


def reduce(ctx):
    skipped = ctx['counters'].get(SKIPPED)
    if skipped is None:
        return None
    total = skipped + ctx['counters'].get(PAIRS, 0.0)
    if not total:
        return None
    print(f'moe_skipped_pairs_pct: {skipped:.0f} of {total:.0f} pairs')
    touched = ctx['counters'].get(TOUCHED)
    if touched is not None:
        slots = ctx['config']['serve']['n_slots']
        layer_steps = total / (slots * ctx['dims'].top_k)
        even = ctx['family'].touched_experts(ctx['dims'], slots)
        print(f'moe_skipped_pairs_pct: {touched:.0f} experts touched in '
              f'{layer_steps:.0f} layer-steps, the skipped pairs counted '
              f'in: {touched / layer_steps:.3f} a layer-step; even routing '
              f'over the experts and the skip would touch {even:.2f} of '
              f'{ctx["dims"].held}')
    return 100.0 * skipped / total
