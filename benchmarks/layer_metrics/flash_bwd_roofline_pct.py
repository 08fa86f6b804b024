"""The flash backward's share of its roofline
(`ops/pallas/flash_attention.py flash_attention_bwd`; the device trace
groups its kernels, one or two, under that name): the least time the chip
could take for a step's backward attention over the group's self time a
step, the trace's `jit_step` programs counted and only what ran inside
one of them summed.

The least time is the mathematics' and not the kernel's, so every
implementation is read alike and none can pass 100: a layer's five
products over the causal triangle (q k^T, dO v^T, p^T dO, ds^T q, ds k:
5 x 2 x B x H x D x S (S + 1) / 2 operations) over the peak FLOP/s, or,
where it is more, what has to cross HBM once (q, dO and O read and dq
written at the query heads, k and v read and dk and dv written at the KV
heads, lse read) over the peak bytes/s; times the layers.  Prints the
bound, the calls and the time a call.  A trace without the group (a
serving cell, a trainer on another attention) gives nothing."""
from benchmarks.harness import costs
from benchmarks.harness import trace as trace_lib

KERNEL = 'flash_attention_bwd'
PROGRAM = 'jit_step'


def layer_cost(dims, rows: int, seq: int, itemsize: int = 2) -> dict:
    """One layer's backward attention over `rows` sequences of `seq`."""
    at_q = rows * dims.heads * seq * dims.head_dim
    at_kv = rows * dims.kv_heads * seq * dims.head_dim
    return {
        'flops': 5 * 2 * rows * dims.heads * dims.head_dim *
        seq * (seq + 1) // 2,
        'bytes': (4 * at_q + 4 * at_kv) * itemsize + 4 * rows * dims.heads *
        seq,
    }


def reduce(ctx):
    if not ctx.get('trace') or not ctx.get('peaks'):
        return None
    mix = ctx['mix']
    if 'seq_len' not in mix or 'sequences_per_step' not in mix:
        return None
    steps, own_ns, calls = 0, 0, 0
    for lines in ctx['trace']['device'].values():
        spans = [(s, s + d) for name, s, d in
                 lines.get(trace_lib.MODULES_LINE, []) if PROGRAM in name]
        steps += len(spans)
        events = lines.get(trace_lib.OPS_LINE, [])
        for (name, start, _), (_, own, _) in zip(
                events, trace_lib.self_times(events)):
            if (trace_lib.op_group(name) == KERNEL and
                    any(a <= start < b for a, b in spans)):
                own_ns += own
                calls += 1
    if not steps or not calls:
        return None
    least = costs.least_seconds(
        layer_cost(ctx['dims'], int(mix['sequences_per_step']),
                   int(mix['seq_len'])), ctx['peaks'])
    least_step = ctx['dims'].layers * least['seconds']
    a_step = own_ns / steps / 1e9
    print(f'flash_bwd_roofline_pct: bound by {least["bound"]}; {calls} '
          f'calls in {steps} steps, {own_ns / calls / 1e3:.2f} us a call, '
          f'{a_step * 1e3:.3f} ms a step; least {least_step * 1e3:.3f} ms '
          f'a step')
    return 100.0 * least_step / a_step
