"""The ZAYA1-8B cell end to end on the CPU, beside MiMo's cases:
`--rehearse` of `zaya1-8b-pp2.decode-backlog-10k` ends with a `check` and
`correct` true through `run.main` (no branch for the family in the
harness), reports the expert layer's metrics with the skip's share and the
fixed leaves' bytes, and `correct` comes out false with the control (fp8) in
the program's place and with one served token altered.  The family's count
of the parameters is the file's arithmetic, and the attention's cost is a
count by hand.

The limits of the rehearsal's size are read at this size (`LIMITS`, with
the readings): the configuration file's own are for the chip.
"""
import copy
import json
import re

import jax

from benchmarks import families, run as run_lib
from benchmarks.harness import manifest, serve

CELL = 'zaya1-8b-pp2.decode-backlog-10k'
MAN = manifest.manifest()
SEED = 4000000007
# Read at this size over 48 positions (widest gap, mean gap), bfloat16 as
# served: seed 4000000007 sound 0.00023, 0.0000049, int8 control 0.0064,
# 0.00038; seed 2147484047 sound 0.0027, 0.000055, control 0.0185, 0.0013;
# seed 3500000077 sound 0.0047, 0.000099, control 0.0125, 0.00046; seed 13
# sound 0.0, 0.0, control 0.0034, 0.000071 (the control is weak there).
# Those with the merges' biases at 0.02; with the weights as they are now
# (0.0004) and the configuration's control, fp8: seed 4000000007 sound
# 0.00043, 0.000018, control 0.0908, 0.0095.
# (With the expert sublayer's merge at full scale the same seeds read
# sound 0.005-0.070 and control 0.025-0.097: a router of width 8 over 5
# outputs flips a near-tie in 4 to 9 of 48 positions, a flipped choice was
# a whole sublayer, and the widest gaps overlapped: the family's
# docstring.)  At the published widths a run compares 2,048 positions
# (PERF.md section 6, PR 47); the tests here pin the seed.
LIMITS = dict(served_gap_limit=0.003, mean_gap_limit=0.0001)


def small():
    cell = manifest.cell(MAN, CELL)
    config = copy.deepcopy(manifest.config_of(MAN, cell['config']))
    mix = copy.deepcopy(manifest.traffic_of(cell['traffic']))
    run_lib.shrink_for_rehearsal(config, mix)
    config['check'].update(LIMITS)
    return config, mix, families.load(config)


def serve_once(wrapper=None, control=False):
    config, mix, family = small()
    _, info = serve.run_cell(
        family=family, config=config, mix=mix, dims=family.dims(config),
        seed=SEED, seconds=15.0, traced=False, devices=jax.devices()[:1],
        control=control, submit_wrapper=wrapper)
    return info


def test_rehearsal_of_the_cell_ends_correct(capsys):
    assert run_lib.main(['--workload', CELL, '--seed', str(SEED),
                         '--seconds', '15', '--trace', '1',
                         '--rehearse']) == 0
    printed = capsys.readouterr().out.strip().splitlines()
    line = json.loads(printed[-1])
    assert line['correct'] is True and line['rehearsal'] is True, line
    assert line['failed'] == 0 and line['attempted'] >= 8
    assert list(line)[-1] == 'check' and line['check']['finite']
    read = line['rehearsal_metrics']
    for name in ('moe_load_max_over_mean', 'moe_held_share_pct', 'moe_kernel_trips_pct',
                 'moe_skipped_pairs_pct', 'decode_kv_fetched_pct',
                 'recurrent_state_gb', 'dispatch_wait_p50_ms.backlog'):
        assert name in read, (name, sorted(read))
    # 4 slots x 3 layers x (two rows of taps of 96 and a half value of 16),
    # bfloat16.
    assert read['recurrent_state_gb']['value'] == 4 * 3 * 208 * 2 / 1e9
    # Every expert is held: the skip's pairs are in neither series.
    assert read['moe_held_share_pct']['value'] == 100.0
    assert 0 < read['moe_skipped_pairs_pct']['value'] < 60   # 1 of 5 outputs
    # The experts a layer-step reached are printed by the skip's reader,
    # with the skipped pairs in the layer-steps: the per-step reader
    # leaves them out and is not listed for this cell.
    assert 'moe_experts_touched_per_step' not in read
    touched = re.search(r'the skipped pairs counted in: ([0-9.]+) a '
                        r'layer-step', '\n'.join(printed))
    assert touched and 0 < float(touched.group(1)) <= 4
    # The CPU reads every slot whole and multiplies through the loop.
    assert read['decode_kv_fetched_pct']['value'] == 100.0
    assert read['moe_kernel_trips_pct']['value'] == 0.0


def test_the_familys_count_is_the_files_arithmetic():
    config = manifest.config_of(MAN, manifest.cell(MAN, CELL)['config'])
    family = families.load(config)
    dims = family.dims(config)
    assert dims.num_params() == config['params_total'] == 4688810364
    assert family.serve_model(dims, config, 'bfloat16').cfg.num_params() \
        == 4688810364
    # One step's 20 calls of the attention kernel at 16 slots of 10,000
    # positions, by hand: 2 KV heads' keys and values of 128 a position
    # and layer, 8 query heads' queries read and sums written a slot,
    # bfloat16; a product of 128 for a score and one for the sum, a head.
    cost = family.cca_attention_cost(dims, 16, 160000)
    assert cost['bytes'] == 20 * (2 * 2 * 128 * 160000 +
                                  2 * 8 * 128 * 16) * 2 == \
        160000 * 20480 + 20 * 65536
    assert cost['flops'] == 20 * 8 * 160000 * 2 * (128 + 128)
    step = family.decode_step_cost(dims, 16, 160000)
    assert step['bytes'] > cost['bytes'] and step['flops'] > cost['flops']
    # Even routing over 17 outputs reaches 16 x (1 - (16/17)^16) experts.
    assert abs(family.touched_experts(dims, 16) - 9.9346) < 1e-3
    outside = (20 * dims.fixed_layer_params() + 262272 * 2048) * 2
    assert step['bytes'] == outside + cost['bytes'] + \
        20 * family.touched_experts(dims, 16) * dims.touched_over_even * \
        12582912 * 2 + 2 * 16 * 107520


def test_the_control_in_the_programs_place_is_not_correct():
    check = serve_once(control=True)['check']
    print(f'the control: {check}')
    sound, low = check, check['control']
    assert sound['widest_gap'] <= LIMITS['served_gap_limit'] and \
        sound['mean_gap'] <= LIMITS['mean_gap_limit'], sound
    assert low['widest_gap'] > LIMITS['served_gap_limit'] or \
        low['mean_gap'] > LIMITS['mean_gap_limit'], low


class _Altered:
    """A request's handle whose third token is not the one produced."""

    def __init__(self, handle, vocab):
        self._inner, self._n, self._vocab = handle.out, 0, vocab
        self.out = self

    def get_nowait(self):
        tok = self._inner.get_nowait()
        if tok is not None:
            self._n += 1
            if self._n == 3:
                return (tok + 1) % self._vocab
        return tok


def test_one_altered_token_is_not_correct():
    def wrapper(submit):
        return lambda p, n, rid: _Altered(submit(p, n, rid), 256)
    info = serve_once(wrapper)
    assert not info['correct']
    assert info['check']['widest_gap'] > LIMITS['served_gap_limit']


def test_the_parent_of_the_cell_would_say_no_workload():
    """A manifest without the cell ends the run at once, with a message;
    and the parent's program under this benchmark's files (no
    models/zaya.py) ends in the family's `serve_model`, with the reason."""
    import sys
    import pytest
    man = copy.deepcopy(MAN)
    man['workloads'] = [w for w in man['workloads'] if w['name'] != CELL]
    with pytest.raises(SystemExit, match='no workload'):
        manifest.cell(man, CELL)
    config, _, family = small()
    hidden = sys.modules.pop('skypilot_tpu.models.zaya', None)
    sys.modules['skypilot_tpu.models.zaya'] = None        # import fails
    try:
        with pytest.raises(SystemExit, match='cannot run configuration'):
            family.serve_model(family.dims(config), config, 'bfloat16')
    finally:
        del sys.modules['skypilot_tpu.models.zaya']
        if hidden is not None:
            sys.modules['skypilot_tpu.models.zaya'] = hidden
