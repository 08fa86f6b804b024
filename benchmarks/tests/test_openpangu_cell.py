"""The openPangu-Ultra-MoE cell end to end on the CPU, beside Solar-Open2's
cases: `--rehearse` of `openpangu-ultra-moe-718b-ep16.decode-backlog-4k`
ends with a `check` and `correct` true through `run.main` (no branch for
the family in the harness), reports the expert layer's and the latent
cache's metrics, and `correct` comes out false with the int8 control in
the program's place and with one served token altered.

The limits of the rehearsal's size are read at this size (`LIMITS`, with
the readings): the configuration file's own are for the chip.
"""
import copy
import json

import jax

from benchmarks import families, run as run_lib
from benchmarks.harness import manifest, serve

CELL = 'openpangu-ultra-moe-718b-ep16.decode-backlog-4k'
MAN = manifest.manifest()
SEED = 2147483999
# Read at this size over 48 positions (widest gap, mean gap), the seeded
# norms as the family draws them (branches 0.3, queries 3.0): seed
# 2147483999 sound 0.0, 0.0, int8 control 0.0318, 0.00084; seed 3500000077
# sound 0.0019, 0.000048, control 0.0443, 0.0011; seed 13 sound 0.0008,
# 0.000017, control 0.0029, 0.000078.  48 positions are too few to hold
# every seed apart by one pair of limits (seed 13's control lies under
# seed 3500000077's sound run); at the published widths a run compares
# 2,048 positions (PERF.md section 6, PR 35); the tests here pin the seed.
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
                 'decode_kv_fetched_pct', 'latent_cache_gb',
                 'dispatch_wait_p50_ms.backlog'):
        assert name in read, (name, sorted(read))
    # 4 slots x 128 positions x 3 layers x (32 + 8) values, bfloat16.
    assert read['latent_cache_gb']['value'] == 4 * 128 * 3 * 40 * 2 / 1e9
    assert 0 < read['moe_experts_touched_per_step']['value'] <= 4
    assert 5 < read['moe_held_share_pct']['value'] < 60     # 4 of 16 held
    # The CPU reads every slot whole and multiplies through the loop.
    assert read['decode_kv_fetched_pct']['value'] == 100.0
    assert read['moe_kernel_trips_pct']['value'] == 0.0
    # A wave's prefill and the call its first token rides, by the span.
    assert read['dispatch_wait_p50_ms.backlog']['value'] > 0


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
