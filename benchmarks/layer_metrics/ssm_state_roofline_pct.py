"""The Mamba-2 state kernel's share of its roofline
(`ops/pallas/ssm_state_update.py`, `ssm_state_update` in the device
trace: one call a Mamba layer and decode step): the least time the chip
could take for one call, the larger of its bytes over the peak bytes/s and
its operations over the peak FLOP/s by the family's `ssm_state_cost` at
the live slots of the traced seconds (the client's stamps), over the
kernel's mean device time a call.  The kernel updates every slot of the
engine, live or not, and reads and writes at once (about 650 GB/s of the
data sheet's 819, PERF.md section 6, PR 38): both are in its time and not
in its cost, so the share stays under 100.  Prints the bound, the calls
and the time a call.  A trace without the kernel (the parent, a family
without such state) gives nothing."""
from benchmarks.harness import costs, reducers
from benchmarks.harness import trace as trace_lib

KERNEL = 'ssm_state_update'


def reduce(ctx):
    cost = getattr(ctx['family'], 'ssm_state_cost', None)
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
    least = costs.least_seconds(cost(ctx['dims'], load['slots']),
                                ctx['peaks'])
    a_call = sum(own_ns) / len(own_ns) / 1e9
    print(f'ssm_state_roofline_pct: bound by {least["bound"]}; '
          f'{len(own_ns)} calls of {a_call * 1e6:.2f} us; live slots '
          f'{load["slots"]:.2f}, least {least["seconds"] * 1e6:.2f} us a '
          f'call')
    return 100.0 * least['seconds'] / a_call
