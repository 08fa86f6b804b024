"""The latent decode kernel's share of its roofline
(`ops/pallas/latent_decode_attention.py`, `latent_decode_attention` in the
device trace: one call a layer and decode step): the least time the chip
could take for one call, the larger of its bytes over the peak bytes/s and
its operations over the peak FLOP/s by the family's
`latent_attention_cost` at the live slots and positions of the traced
seconds (the client's stamps), over the kernel's mean device time a call.
What the kernel fetches beyond the live positions (whole tiles, an empty
slot's one tile) is in its time and not in its cost, so the share stays
under 100.  Prints the bound, the calls and the time a call.  A trace
without the kernel (the parent, a family without a latent) gives
nothing."""
from benchmarks.harness import costs, reducers
from benchmarks.harness import trace as trace_lib

KERNEL = 'latent_decode_attention'


def reduce(ctx):
    cost = getattr(ctx['family'], 'latent_attention_cost', None)
    if (cost is None or not ctx.get('trace') or not ctx.get('trace_span')
            or not ctx.get('peaks')):
        return None
    own_ns = [own for lines in ctx['trace']['device'].values()
              for name, own, _ in trace_lib.self_times(
                  lines.get(trace_lib.OPS_LINE, []))
              if trace_lib.op_group(name) == KERNEL]
    if not own_ns:
        return None
    load = reducers.live_load(ctx['records'], ctx['trace_span'])
    least = costs.least_seconds(
        cost(ctx['dims'], load['slots'], load['positions']), ctx['peaks'])
    a_call = sum(own_ns) / len(own_ns) / 1e9
    print(f'latent_attention_roofline_pct: bound by {least["bound"]}; '
          f'{len(own_ns)} calls of {a_call * 1e6:.2f} us; live slots '
          f'{load["slots"]:.2f}, live positions {load["positions"]:.0f}, '
          f'least {least["seconds"] * 1e6:.2f} us a call')
    return 100.0 * least['seconds'] / a_call
