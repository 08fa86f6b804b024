"""The Granite-4.0-H cell end to end on the CPU, beside the other
families' cases: `--rehearse` of `granite-4.0-h-micro.decode-backlog-short`
ends with a `check` and `correct` true through `run.main` (no branch for the
family in the harness), reports the state's metrics, and `correct` comes out
false with the int8 control in the program's place and with one served
token altered; the configuration is the catalog's row, nothing cut.

The limits of the rehearsal's size are read at this size (`LIMITS`, with
the readings), as `test_correct.py` reads Llama's: the configuration
file's own are for the chip.
"""
import copy
import json

import jax

from benchmarks import families, run as run_lib
from benchmarks.harness import manifest, serve

CELL = 'granite-4.0-h-micro.decode-backlog-short'
MAN = manifest.manifest()
SEED = 2147483678
# Read at this size over 48 positions (widest gap, mean gap; logits of
# size 0.01, the tied table being drawn at a standard deviation of 0.003):
# seed 2147483678 sound 0.0, 0.0, int8 control 2.8e-4, 1.3e-5; seed
# 2147483999 sound 0.0, 0.0, control 1.2e-4, 3.6e-6; seed 13 sound 1.7e-5,
# 3.4e-7 (one near-tie), control 2.9e-4, 1.8e-5; seed 3000000077 sound
# 5.8e-5, 1.2e-6 (one near-tie), control 1.8e-4, 1.7e-5.  48 positions are
# too few for limits that hold every seed; at the published widths a run
# compares 1024 (PERF.md section 6, PR 43); the tests here pin the seed.
LIMITS = dict(served_gap_limit=1e-4, mean_gap_limit=5e-6)


def small():
    cell = manifest.cell(MAN, CELL)
    config = copy.deepcopy(manifest.config_of(MAN, cell['config']))
    mix = copy.deepcopy(manifest.traffic_of(cell['traffic']))
    run_lib.shrink_for_rehearsal(config, mix)
    config['check'].update(LIMITS)
    return config, mix, families.load(config)


def serve_once(wrapper=None, control=False, seed=SEED):
    config, mix, family = small()
    _, info = serve.run_cell(
        family=family, config=config, mix=mix, dims=family.dims(config),
        seed=seed, seconds=15.0, traced=False, devices=jax.devices()[:1],
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
    for name in ('recurrent_state_gb', 'ssm_kernel_updates_pct',
                 'decode_kv_fetched_pct'):
        assert name in read, (name, sorted(read))
    # 4 slots x 3 Mamba layers x (4 heads x 16 x 16 float32 + 3 taps x 96
    # bfloat16).
    assert read['recurrent_state_gb']['value'] == \
        4 * 3 * (4 * 16 * 16 * 4 + 3 * 96 * 2) / 1e9
    assert read['ssm_kernel_updates_pct']['value'] == 0.0    # the CPU: XLA
    assert 'ssm_state_roofline_pct' not in read              # no device trace


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


def test_the_configuration_is_the_catalogs_row_with_nothing_cut():
    """`num_params()` against the file's arithmetic, every size the
    published one, the cell and its traffic as ISSUE 43 sets them, the
    state's cost a call."""
    cell = manifest.cell(MAN, CELL)
    entry = next(c for c in MAN['configs'] if c['name'] == cell['config'])
    config = manifest.config_of(MAN, cell['config'])
    dims = families.load(config).dims(config)
    assert dims.num_params() == 3191396096 == config['params_total']
    assert entry['reduced'] == config['reduced'] == []
    assert entry['source'] == config['source']
    # The driver refuses a `why` of more than 200 characters (this PR's
    # first hand-in had one of 203), or one off a single printable line.
    for why in (entry['why'], cell['why']):
        assert 1 <= len(why) <= 200 and why.isprintable(), len(why)
    assert (dims.layers, dims.vocab, dims.hidden, dims.ffn) == (
        40, 100352, 2048, 8192)
    assert (dims.ssm_heads, dims.ssm_head_dim, dims.ssm_state, dims.conv,
            dims.chunk) == (64, 64, 128, 4, 256)
    assert (dims.heads, dims.kv_heads, dims.head_dim) == (32, 8, 64)
    assert cell['chips'] == 1 and cell['traffic'] == 'decode-backlog-short'
    mix = manifest.traffic_of(cell['traffic'])
    assert mix['kind'] == 'backlog' and mix['sharing'] == 'none'
    assert mix['prompt_tokens'] == {'dist': 'lognormal', 'median': 256,
                                    'sigma': 0.6, 'min': 64, 'max': 512}
    assert mix['output_tokens'] == {'dist': 'fixed', 'value': 256}
    assert config['serve']['n_slots'] in (64, 48)
    assert config['serve']['max_seq_len'] >= 512 + 256
    family = families.load(config)
    # A call of the state kernel at 64 live slots: 2 MB a slot read and
    # written, and what comes with it.
    cost = family.ssm_state_cost(dims, 64)
    assert cost['bytes'] == 64 * (2 * 2097152 + 4 * (4 * 4096 + 256))
    # A step at 64 slots of 400 positions: the state is 59% of the bytes.
    step = family.decode_step_cost(dims, 64, 64 * 400)
    state = 2.0 * dims.state_bytes_per_slot() * 64
    assert 0.58 < state / step['bytes'] < 0.60


def test_the_parent_of_the_cell_would_say_no_workload():
    """A manifest without the cell ends the run at once, with a message."""
    import pytest
    man = copy.deepcopy(MAN)
    man['workloads'] = [w for w in man['workloads'] if w['name'] != CELL]
    with pytest.raises(SystemExit, match='no workload'):
        manifest.cell(man, CELL)
