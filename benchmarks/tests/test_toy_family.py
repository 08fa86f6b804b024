"""A second family needs no edit to the harness.

The toy family of `benchmarks/tests/toy/` (sizes of its own kind, a tree
with one leaf more, its own program module, reference and cost) and its
configuration are copied into a temporary directory; the loader's search
path and a patched manifest point at them; `run.main` then drives a whole
`--rehearse` run of a training cell on the CPU to `correct: true`.  No file
of `benchmarks/harness/` and not `benchmarks/run.py` knows of the toy.
"""
import copy
import json
import os
import shutil
import subprocess

import jax
import pytest

from benchmarks import families, run as run_lib
from benchmarks.harness import manifest, reducers, weights

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), 'toy')
CELL = 'toy-mlp.pretrain-4k'


@pytest.fixture
def toy(tmp_path, monkeypatch):
    for name in ('ToyMlpLM.py', 'toy-mlp.json'):
        shutil.copy(os.path.join(TOY, name), tmp_path / name)
    man = copy.deepcopy(manifest.manifest())
    man['configs'].append({
        'name': 'toy-mlp', 'source': 'benchmarks/tests/toy',
        'file': str(tmp_path / 'toy-mlp.json'), 'reduced': [],
        'why': 'a family that is not Llama'})
    man['workloads'].append({
        'name': CELL, 'config': 'toy-mlp', 'traffic': 'pretrain-4k',
        'chips': 1, 'why': 'the toy family through Trainer.run'})
    for m in man['end_to_end'] + man['per_layer']:
        if 'yi-coder-1.5b-1chip.pretrain-4k' in m.get('workloads', []):
            m['workloads'].append(CELL)
    monkeypatch.setattr(manifest, 'manifest', lambda: man)
    monkeypatch.setattr(families, 'SEARCH_PATH',
                        [str(tmp_path)] + families.SEARCH_PATH)
    return man


def last_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_toy_family_runs_a_cell_to_correct(toy, capsys):
    assert manifest.problems(toy) == []
    assert run_lib.main(['--workload', CELL, '--seed', str(2**31 + 5),
                         '--seconds', '1', '--trace', '0',
                         '--rehearse']) == 0
    line = last_line(capsys)
    assert line['correct'] is True and line['rehearsal'] is True, line
    assert line['failed'] == 0 and line['attempted'] >= 4
    assert set(line['rehearsal_metrics']) == {'train_tokens_per_s',
                                              'setup_s'}
    check = line['check']
    assert list(line)[-1] == 'check' and len(check['losses']) == 3
    assert 0 < check['delta_norm_gap'] <= check['limits']['delta_norm_gap']
    cfg = manifest.config_of(toy, 'toy-mlp')
    family = families.load(cfg)
    dims = family.dims(cfg)
    # The leaf that Llama's tree lacks was followed: `compare` reads every
    # leaf of the reference in the program's norms.
    assert 'layer_0/gain/scale' in weights.flat(jax.eval_shape(
        lambda: family.make_params(weights.seed_key(1), dims, 'float32')))
    assert type(dims).__name__ == 'ToyDims'
    assert dims.num_params() == cfg['params_total']
    ctx = {'samples': {'train_tokens_per_s': [1000.0]}, 'chips': 1,
           'peaks': {'bf16_flops_per_s': 1e9}, 'family': family,
           'dims': dims, 'mix': {'seq_len': 128}}
    assert reducers.train_mfu_pct(ctx) == pytest.approx(
        100.0 * 6.0 * dims.multiplied() * 1000.0 / 1e9)


def test_a_broken_step_is_not_correct_through_the_toy_family(toy):
    """The timed path broken underneath, read through a family that is not
    Llama's: a state returned unchanged."""
    from benchmarks.harness import train
    cfg = manifest.config_of(toy, 'toy-mlp')
    mix = manifest.traffic_of('pretrain-4k')
    run_lib.shrink_for_rehearsal(cfg, mix)
    family = families.load(cfg)

    def wrapper(step):
        def unchanged(state, batch):
            kept = jax.tree.map(lambda a: a.copy(), state)  # step donates
            return kept, step(state, batch)[1]
        return unchanged
    _, info = train.run_cell(
        family=family, config=cfg, mix=mix, dims=family.dims(cfg), seed=7,
        seconds=0.5, traced=False, devices=jax.devices()[:1],
        step_wrapper=wrapper)
    assert not info['correct']
    assert info['check']['delta_norm_gap'] > 0.3


def test_a_serving_mix_on_a_family_without_serve_model_ends(toy):
    toy['workloads'].append({
        'name': 'toy-mlp.batch-backlog', 'config': 'toy-mlp',
        'traffic': 'batch-backlog', 'chips': 1, 'why': 'no serve_model'})
    with pytest.raises(SystemExit, match='has no serve_model'):
        run_lib.main(['--workload', 'toy-mlp.batch-backlog', '--seed', '1',
                      '--seconds', '1', '--rehearse'])


def test_the_harness_does_not_know_the_toy():
    files = [os.path.join(manifest.BENCH_DIR, 'run.py')]
    for sub in ('harness', 'families', 'tools', 'reference'):
        files += [os.path.join(manifest.BENCH_DIR, sub, f) for f in
                  os.listdir(os.path.join(manifest.BENCH_DIR, sub))
                  if f.endswith('.py')]
    found = subprocess.run(['grep', '-l', '-i', 'toy'] + files,
                           capture_output=True, text=True, check=False)
    assert found.stdout == ''
