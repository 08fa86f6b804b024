"""`flash_bwd_roofline_pct` (ISSUE 44) on a hand-made trace whose answer
is known, through `reduce_metric` as the harness calls it: the count is
the mathematics', the time is the group's self time inside the traced
`jit_step` programs, and a trace without the group reads as nothing."""
import pytest

from benchmarks import families
from benchmarks.harness import manifest, reducers

CELL = 'yi-coder-1.5b-1chip.pretrain-4k'
MS = 1_000_000


def ctx_of(ops, modules):
    man = manifest.manifest()
    cell = manifest.cell(man, CELL)
    config = manifest.config_of(man, cell['config'])
    return {'trace': {'device': {'/device:TPU:0': {
        'XLA Ops': ops, 'XLA Modules': modules}}, 'host': []},
            'dims': families.load(config).dims(config),
            'mix': manifest.traffic_of(cell['traffic']),
            'peaks': manifest.peaks_for('TPU v5 lite'), 'values': {}}


def two_steps(kernels_a_layer, ms_a_kernel):
    """Two whole steps of 8 layers, and the tail of a third step that the
    trace caught without its program: its kernels are not counted."""
    ops, t = [], 0
    for step in range(3):
        for _ in range(8 * kernels_a_layer):
            ops.append([f'%flash_attention_bwd.{len(ops)}', t,
                        int(ms_a_kernel * MS)])
            ops.append([f'%fusion.{len(ops)}', t + int(ms_a_kernel * MS),
                        MS])
            t += int(ms_a_kernel * MS) + MS
    step_ns = t // 3
    return ops, [['jit_step(7)', 0, step_ns], ['jit_step(7)', step_ns,
                                               step_ns]]


@pytest.mark.parametrize('kernels, ms', [(2, 5.0), (1, 6.0)])
def test_the_share_is_the_least_time_over_the_groups_time_a_step(
        capsys, kernels, ms):
    share = reducers.reduce_metric('flash_bwd_roofline_pct',
                                   ctx_of(*two_steps(kernels, ms)))
    # 5 x 2 x 4 x 16 x 128 x 4096 x 4097 / 2 x 8 layers = 5.499 TFLOP
    # a step, 27.9 ms at 197 TFLOP/s; the bytes are 0.4 ms.
    least_ms = 5 * 2 * 4 * 16 * 128 * 4096 * 4097 // 2 * 8 / 197e12 * 1e3
    assert share == pytest.approx(100 * least_ms / (8 * kernels * ms))
    assert 0 < share < 100
    said = capsys.readouterr().out
    assert 'bound by compute' in said and f'{16 * kernels} calls' in said
    assert 'in 2 steps' in said


def test_a_trace_without_the_kernel_reads_as_nothing():
    ops, modules = two_steps(1, 6.0)
    others = [ev for ev in ops if 'fusion' in ev[0]]
    assert reducers.reduce_metric('flash_bwd_roofline_pct',
                                  ctx_of(others, modules)) is None
    assert reducers.reduce_metric('flash_bwd_roofline_pct',
                                  ctx_of(ops, [])) is None
    assert reducers.reduce_metric(
        'flash_bwd_roofline_pct', dict(ctx_of(ops, modules),
                                       trace=None)) is None
