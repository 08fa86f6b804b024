"""The decode attention kernel's share of its roofline where it reads the
compressed latent's K and V (`ops/pallas/decode_attention.py`,
`decode_attention` in the device trace: one call a layer and decode step,
2 KV heads under 8 query heads of 128): the least time the chip could take
for one step's calls, the larger of their bytes over the peak bytes/s and
their operations over the peak FLOP/s by the family's `cca_attention_cost`
at the live slots and positions of the traced seconds (the client's
stamps), over the kernels' device time a step (their mean time a call,
times the layers).  What a kernel fetches beyond the live positions (whole
tiles, an empty slot's one tile) is in its time and not in its cost, so
the share stays under 100.  Prints the bound, the calls and the time a
step.  A trace without the kernel, or a family without
`cca_attention_cost`, gives nothing."""
from benchmarks.harness import costs, reducers
from benchmarks.harness import trace as trace_lib

KERNEL = 'decode_attention'


def reduce(ctx):
    cost = getattr(ctx['family'], 'cca_attention_cost', None)
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
    a_step = sum(own_ns) / len(own_ns) * ctx['dims'].layers / 1e9
    print(f'cca_attention_roofline_pct: bound by {least["bound"]}; '
          f'{len(own_ns)} calls, {a_step * 1e6:.2f} us a step of '
          f'{ctx["dims"].layers} calls; live slots {load["slots"]:.2f}, '
          f'live positions {load["positions"]:.0f}, least '
          f'{least["seconds"] * 1e6:.2f} us a step')
    return 100.0 * least['seconds'] / a_step
