"""Tokens emitted a slot-pass, over the window (generation by blocks): the
delta of `skytpu_engine_decode_tokens_total` over the delta of
`skytpu_engine_block_passes_total` (both kinds: the passes, denoising and
commit, of slots that hold a request).  A block of B positions at s
denoising steps is s + 1 passes for B tokens: 0.8 at 4 and 4;
`tpot_p50_ms` is a pass's time over this.  Prints both counts.  A program
without the counter (the parent, a model that generates a token a step)
gives nothing."""
TOKENS = 'skytpu_engine_decode_tokens_total'
PASSES = 'skytpu_engine_block_passes_total'


def reduce(ctx):
    tokens, passes = ctx['counters'].get(TOKENS), ctx['counters'].get(PASSES)
    if tokens is None or not passes:
        return None
    print(f'block_tokens_per_pass: {tokens:.0f} tokens in {passes:.0f} '
          f'slot-passes')
    return tokens / passes
