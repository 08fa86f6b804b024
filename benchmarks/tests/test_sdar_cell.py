"""The SDAR-MoE cell end to end on the CPU, beside Solar-Open2's and
openPangu's cases: `--rehearse` of `sdar-30b-a3b-chat-pp8.decode-backlog-256`
ends with a `check` and `correct` true through `run.main` (no branch for
the family in the harness: `check._pack` asks for the state from which
each served token was taken, and the family's reference returns that for
generation by blocks), reports the passes' and the expert layer's metrics,
and `correct` comes out false with the int8 control in the program's place
and with one served token altered.

The limits of the rehearsal's size are read at this size (`LIMITS`, with
the readings): the configuration file's own are for the chip.
"""
import copy
import json

import jax

from benchmarks import families, run as run_lib
from benchmarks.harness import manifest, serve

CELL = 'sdar-30b-a3b-chat-pp8.decode-backlog-256'
MAN = manifest.manifest()
SEED = 2147483999
# Read at this size over 48 positions (widest gap, mean gap): seed
# 2147483999 sound 0.0, 0.0, int8 control 0.0465, 0.00151; seed 3500000077
# sound 0.0, 0.0, control 0.0239, 0.00093.  48 positions are too few to
# hold every seed apart by one pair of limits; at the published widths a
# run compares 1,024 positions (PERF.md section 6, PR 37); the tests here
# pin the seed.
LIMITS = dict(served_gap_limit=0.01, mean_gap_limit=0.0003)


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


def test_the_family_meets_the_contract():
    """The manifest has nothing the driver would refuse, the cell is one
    chip, and the family counts the parameters of ISSUE 37's arithmetic
    and declares generation by blocks as the configuration states it."""
    assert manifest.problems(MAN) == []
    cell = manifest.cell(MAN, CELL)
    assert cell['chips'] == 1 and cell['traffic'] == 'decode-backlog-256'
    config = manifest.config_of(MAN, cell['config'])
    family = families.load(config)
    dims = family.dims(config)
    assert dims.num_params() == config['params_total'] == 4_361_055_744
    assert config['reduced'] == ['num_hidden_layers']
    assert config['published'] == {'num_hidden_layers': 48}
    assert (dims.experts, dims.vocab, dims.layers) == (128, 151_936, 6)
    assert (dims.block, dims.steps, dims.remasking) == (4, 4, 'sequential')
    for name in ('layer_weights', 'outer_weights', 'make_params',
                 'serve_model', 'reference', 'decode_step_cost',
                 'train_flops_per_token', 'touched_experts', 'REHEARSAL'):
        assert hasattr(family, name), name
    assert not hasattr(family, 'train_model')
    listed = {m['name'] for m in manifest.metrics_of(MAN, CELL, 'per_layer')}
    assert {'block_tokens_per_pass', 'block_commit_pass_pct',
            'moe_experts_touched_per_pass', 'decode_step_ms',
            'decode_roofline_pct', 'moe_kernel_trips_pct'} <= listed
    assert 'moe_experts_touched_per_step' not in listed
    assert {m['name'] for m in manifest.metrics_of(
        MAN, CELL, 'end_to_end')} == {'tpot_p50_ms', 'out_tokens_per_s',
                                      'setup_s'}


def test_rehearsal_of_the_cell_ends_correct(capsys):
    assert run_lib.main(['--workload', CELL, '--seed', str(SEED),
                         '--seconds', '15', '--trace', '1',
                         '--rehearse']) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line['correct'] is True and line['rehearsal'] is True, line
    assert line['failed'] == 0 and line['attempted'] >= 8
    assert list(line)[-1] == 'check' and line['check']['finite']
    read = line['rehearsal_metrics']
    for name in ('block_tokens_per_pass', 'block_commit_pass_pct',
                 'moe_experts_touched_per_pass', 'moe_load_max_over_mean',
                 'moe_held_share_pct', 'moe_kernel_trips_pct',
                 'decode_kv_fetched_pct', 'dispatch_wait_p50_ms.backlog'):
        assert name in read, (name, sorted(read))
    # 12 tokens a request from a prompt of any `L % 4`: 3-4 blocks of 2-5
    # passes; a commit pass a block.
    assert 0.6 < read['block_tokens_per_pass']['value'] <= 0.8
    assert 20.0 <= read['block_commit_pass_pct']['value'] < 26.0
    assert 4 < read['moe_experts_touched_per_pass']['value'] <= 8
    assert read['moe_held_share_pct']['value'] == 100.0     # all held
    # The CPU reads every slot whole and multiplies through the loop.
    assert read['decode_kv_fetched_pct']['value'] == 100.0
    assert read['moe_kernel_trips_pct']['value'] == 0.0
    # A wave's prefill and the calls up to its first committed block.
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
    """A manifest without the cell ends the run at once, with a message;
    and with the manifest laid over a program that lacks the model, the
    family's `serve_model` ends it."""
    import builtins
    import pytest
    man = copy.deepcopy(MAN)
    man['workloads'] = [w for w in man['workloads'] if w['name'] != CELL]
    with pytest.raises(SystemExit, match='no workload'):
        manifest.cell(man, CELL)
    config, _, family = small()
    real = builtins.__import__

    def no_model(name, *args, **kw):
        if name == 'skypilot_tpu.models.sdar_moe':
            raise ImportError(f'No module named {name!r}')
        return real(name, *args, **kw)

    builtins.__import__ = no_model
    try:
        with pytest.raises(SystemExit, match='cannot run configuration'):
            family.serve_model(family.dims(config), config, None)
    finally:
        builtins.__import__ = real
