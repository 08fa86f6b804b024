"""The Solar-Open2 cell end to end on the CPU, beside the toy family's
cases: `--rehearse` of `solar-open2-250b-ep8.decode-backlog-512` ends with
a `check` and `correct` true through `run.main` (no branch for the family
in the harness), reports the expert layer's and the state's metrics, and
`correct` comes out false with the int8 control in the program's place and
with one served token altered.

The limits of the rehearsal's size are read at this size (`LIMITS`, with
the readings), as `test_correct.py` reads Llama's: the configuration
file's own are for the chip.
"""
import copy
import json

import jax

from benchmarks import families, run as run_lib
from benchmarks.harness import manifest, serve

CELL = 'solar-open2-250b-ep8.decode-backlog-512'
MAN = manifest.manifest()
SEED = 2147483999
# Read at this size over 48 positions (widest gap, mean gap), with the
# seeded weights as they are since the mixing layers' output projections
# are drawn residual-scaled: seed 2147483999 sound 0.0, 0.0, int8 control
# 0.062, 0.0028; seed 13 sound 0.0, 0.0, control 0.52, 0.0119; seed
# 3000000077 sound 0.0, 0.0, control 0.21, 0.0043.  48 positions are too
# few to hold every seed (seed 2147483678: sound 0.013, 0.00027, control
# 0.006, 0.00013: one near-tie each); at the published widths a run
# compares 2048 (PERF.md section 6, PR 30); the tests here pin the seed.
LIMITS = dict(served_gap_limit=0.04, mean_gap_limit=0.0015)


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
                 'moe_held_share_pct', 'recurrent_state_gb'):
        assert name in read, (name, sorted(read))
    # 4 slots x 3 linear layers x (4 heads x 16 x 16 float32 + 3 taps x 3
    # x 64 bfloat16).
    assert read['recurrent_state_gb']['value'] == \
        4 * 3 * (4 * 16 * 16 * 4 + 3 * 3 * 64 * 2) / 1e9
    assert 0 < read['moe_experts_touched_per_step']['value'] <= 4
    assert 5 < read['moe_held_share_pct']['value'] < 60     # 4 of 16 held


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
    """A manifest without the cell ends the run at once, with a message."""
    import pytest
    man = copy.deepcopy(MAN)
    man['workloads'] = [w for w in man['workloads'] if w['name'] != CELL]
    with pytest.raises(SystemExit, match='no workload'):
        manifest.cell(man, CELL)
