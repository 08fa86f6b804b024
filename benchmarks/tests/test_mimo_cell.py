"""The MiMo-V2.5 cell end to end on the CPU, beside openPangu's cases:
`--rehearse` of `mimo-v2.5-ep16.decode-backlog-8k` ends with a `check` and
`correct` true through `run.main` (no branch for the family in the
harness), reports the expert layer's, the two caches' and the window
layers' metrics, and `correct` comes out false with the int8 control in
the program's place and with one served token altered.  The family's
count of the parameters is the file's arithmetic.

The limits of the rehearsal's size are read at this size (`LIMITS`, with
the readings): the configuration file's own are for the chip.
"""
import copy
import json

import jax

from benchmarks import families, run as run_lib
from benchmarks.harness import manifest, serve

CELL = 'mimo-v2.5-ep16.decode-backlog-8k'
MAN = manifest.manifest()
SEED = 2147484041
# Read at this size over 48 positions (widest gap, mean gap), bfloat16 as
# served: seed 2147484041 sound 0.0016, 0.000034, int8 control 0.0202,
# 0.00046; seed 3500000077 sound 0.0025, 0.000051, control 0.0466, 0.0020;
# seed 13 sound 0.0, 0.0, control 0.599, 0.0161.  At the published widths a
# run compares 2,048 positions (PERF.md section 6, PR 41); the tests here
# pin the seed.
LIMITS = dict(served_gap_limit=0.01, mean_gap_limit=0.0002)


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
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line['correct'] is True and line['rehearsal'] is True, line
    assert line['failed'] == 0 and line['attempted'] >= 8
    assert list(line)[-1] == 'check' and line['check']['finite']
    read = line['rehearsal_metrics']
    for name in ('moe_experts_touched_per_step', 'moe_load_max_over_mean',
                 'moe_held_share_pct', 'moe_kernel_trips_pct',
                 'decode_kv_fetched_pct', 'window_cache_gb',
                 'window_kv_fetched_pct', 'dispatch_wait_p50_ms.backlog'):
        assert name in read, (name, sorted(read))
    # 4 slots x a ring of 16 x 2 window layers x 4 KV heads x (24 + 16)
    # values, bfloat16.
    assert read['window_cache_gb']['value'] == 4 * 16 * 2 * 4 * 40 * 2 / 1e9
    # A ring of 16 a slot and step against contexts of 8-64 positions.
    assert 20 < read['window_kv_fetched_pct']['value'] < 100
    assert 0 < read['moe_experts_touched_per_step']['value'] <= 4
    assert 5 < read['moe_held_share_pct']['value'] < 60     # 4 of 16 held
    # The CPU reads every slot whole and multiplies through the loop.
    assert read['decode_kv_fetched_pct']['value'] == 100.0
    assert read['moe_kernel_trips_pct']['value'] == 0.0


def test_the_familys_count_is_the_files_arithmetic():
    config = manifest.config_of(MAN, manifest.cell(MAN, CELL)['config'])
    family = families.load(config)
    dims = family.dims(config)
    assert dims.num_params() == config['params_total'] == 3429955392
    assert family.serve_model(dims, config, 'bfloat16').cfg.num_params() \
        == 3429955392
    # The cost of a step counts two kinds of layer: past the window a
    # window layer's bytes stand still, a full layer's grow.
    short = family.mixed_attention_cost(dims, 32, 32 * 1000)
    long = family.mixed_attention_cost(dims, 32, 32 * 6000)
    grown = 32 * 5000 * dims.kv_bytes_per_position(False)
    assert long['bytes'] - short['bytes'] == grown
    rings = 32 * dims.window * dims.kv_bytes_per_position(True)
    assert short['bytes'] == 32 * 1000 * 5120 + rings + \
        7 * 64 * 320 * 32 * 2
    step = family.decode_step_cost(dims, 32, 32 * 6000)
    assert step['bytes'] > long['bytes'] and step['flops'] > long['flops']


def test_int8_control_in_the_programs_place_is_not_correct():
    check = serve_once(control=True)['check']
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
    models/mimo_v2.py) ends in the family's `serve_model`, with the
    reason."""
    import sys
    import pytest
    man = copy.deepcopy(MAN)
    man['workloads'] = [w for w in man['workloads'] if w['name'] != CELL]
    with pytest.raises(SystemExit, match='no workload'):
        manifest.cell(man, CELL)
    config, _, family = small()
    hidden = sys.modules.pop('skypilot_tpu.models.mimo_v2', None)
    sys.modules['skypilot_tpu.models.mimo_v2'] = None     # import fails
    try:
        with pytest.raises(SystemExit, match='cannot run configuration'):
            family.serve_model(family.dims(config), config, 'bfloat16')
    finally:
        del sys.modules['skypilot_tpu.models.mimo_v2']
        if hidden is not None:
            sys.modules['skypilot_tpu.models.mimo_v2'] = hidden
