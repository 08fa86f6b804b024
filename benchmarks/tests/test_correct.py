"""`correct` has to be able to come out false.

Two kinds of test, both on the CPU at a size a test run can hold, both
skipping run.py's look for a chip and driving the rest of a run:

* the control: the reference in the next lower precision, in the program's
  place, reads worse than the program does, on three seeds;
* the timed path broken underneath (a served token altered where it is
  produced; a training step that returns its state unchanged; a part of the
  batch left out): `correct` comes out false.

Every run goes through the configuration's family, as `run.py` does.
"""
import copy

import jax

from benchmarks import families, run as run_lib
from benchmarks.harness import manifest, serve, train

MAN = manifest.manifest()
SEEDS = (11, 2**31 + 12, 13)


def small(cell_name):
    cell = manifest.cell(MAN, cell_name)
    config = copy.deepcopy(manifest.config_of(MAN, cell['config']))
    mix = copy.deepcopy(manifest.traffic_of(cell['traffic']))
    run_lib.shrink_for_rehearsal(config, mix)
    return config, mix, families.load(config)


def serve_once(seed, wrapper=None, control=False, cell='yi-6b.batch-backlog'):
    config, mix, family = small(cell)
    # A little wider than the rehearsal's, and every request compared, so
    # that a run reads some hundreds of positions.  Read at this size on
    # seeds 11, 2**31 + 12, 13: sound runs 0.017-0.024, control 0.061-0.071.
    config.update(hidden_size=128, num_hidden_layers=4, head_dim=32,
                  intermediate_size=256, vocab_size=8192)
    mix['check_sample'] = 24
    config['check'].update(served_gap_limit=0.04, mean_gap_limit=1.0)
    _, info = serve.run_cell(family=family, config=config, mix=mix,
                             dims=family.dims(config), seed=seed,
                             seconds=8.0, traced=False,
                             devices=jax.devices()[:1], control=control,
                             submit_wrapper=wrapper)
    return info


def train_once(seed, wrapper=None, control=False):
    config, mix, family = small('yi-coder-1.5b-1chip.pretrain-4k')
    # Limits read at this size (sound runs: loss 6e-5, gradient 2e-3,
    # change 1e-3; control: gradient 9e-3 and more).
    config['check'].update(loss_rel_limit=1e-3, grad_norm_limit=5e-3,
                           grad_norm_mean_limit=1.0, delta_norm_limit=0.3)
    _, info = train.run_cell(family=family, config=config, mix=mix,
                             dims=family.dims(config), seed=seed,
                             seconds=1.0, traced=False,
                             devices=jax.devices()[:1], control=control,
                             step_wrapper=wrapper)
    return info


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


def test_sound_serving_run_is_correct():
    info = serve_once(SEEDS[0])
    assert info['correct'], info
    assert info['check']['positions'] >= 24


def test_altered_token_is_not_correct():
    def wrapper(submit):
        return lambda p, n, rid: _Altered(submit(p, n, rid), 8192)
    info = serve_once(SEEDS[0], wrapper)
    assert not info['correct']
    assert info['check']['widest_gap'] > 0.04


def test_short_answer_is_not_correct():
    def wrapper(submit):
        return lambda p, n, rid: submit(p, n - 1, rid)
    assert not serve_once(SEEDS[0], wrapper)['correct']


def test_int8_control_reads_worse_than_the_served_tokens():
    sound, control = [], []
    for seed in SEEDS:
        check = serve_once(seed, control=True)['check']
        sound.append(check['widest_gap'])
        control.append(check['control']['widest_gap'])
    assert max(sound) < 0.04 < min(control), (sound, control)


def test_sound_training_run_is_correct_and_fp8_control_is_not():
    info = train_once(SEEDS[1], control=True)
    assert info['correct'], info['check']
    low = info['check']['control']
    assert low['grad_norm_gap'] > 5e-3 > info['check']['grad_norm_gap']


def test_step_that_returns_its_state_unchanged_is_not_correct():
    def wrapper(step):
        def unchanged(state, batch):
            kept = jax.tree.map(lambda a: a.copy(), state)  # step donates
            return kept, step(state, batch)[1]
        return unchanged
    info = train_once(SEEDS[2], wrapper)
    assert not info['correct']
    assert info['check']['delta_norm_gap'] > 0.3


def test_part_of_the_batch_left_out_is_not_correct():
    def wrapper(step):
        def half(state, batch):
            batch = batch.copy()
            batch[len(batch) // 2:] = batch[:len(batch) // 2]
            return step(state, batch)
        return half
    info = train_once(SEEDS[2], wrapper)
    assert not info['correct']
