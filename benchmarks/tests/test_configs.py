"""Each configuration's parameter count against the arithmetic of its
source, and against the program's own count; the manifest against the
contract's schema."""
import json
import os

import pytest

from benchmarks import families
from benchmarks.harness import costs, manifest, weights

MAN = manifest.manifest()
# hidden, layers, heads, kv, ffn, vocab -> total, worked out by hand from
# the public config.json files (ISSUE 24 states the same totals).
PUBLISHED = {
    'yi-6b': 32 * (2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 11008
                   + 2 * 4096) + 2 * 64000 * 4096 + 4096,
    'yi-coder-1.5b-chat': 24 * (4 * 2048 * 2048 + 3 * 2048 * 5504
                                + 2 * 2048) + 2 * 64000 * 2048 + 2048,
    'yi-coder-1.5b': 24 * (4 * 2048 * 2048 + 3 * 2048 * 5504
                           + 2 * 2048) + 2 * 64000 * 2048 + 2048,
    'yi-coder-1.5b-1chip': 8 * (4 * 2048 * 2048 + 3 * 2048 * 5504
                                + 2 * 2048) + 2 * 64000 * 2048 + 2048,
}


def config_file(name):
    """Every file under configs/, in the manifest yet or not."""
    return manifest.load_json(manifest.BENCH_DIR, 'configs', f'{name}.json')


def dims_of(name):
    cfg = config_file(name)
    return families.load(cfg).dims(cfg)


LLAMA = families.load({'architecture': 'LlamaForCausalLM'})


@pytest.mark.parametrize('name', sorted(PUBLISHED))
def test_param_count(name):
    cfg = config_file(name)
    dims = dims_of(name)
    assert dims.num_params() == PUBLISHED[name] == cfg['params_total']
    from skypilot_tpu.models.llama import LlamaConfig
    prog = LlamaConfig(vocab_size=dims.vocab, dim=dims.hidden,
                       n_layers=dims.layers, n_heads=dims.heads,
                       n_kv_heads=dims.kv_heads, ffn_dim=dims.ffn)
    assert prog.num_params() == dims.num_params()
    assert prog.head_dim == dims.head_dim


def test_round_totals():
    assert round(PUBLISHED['yi-6b'] / 1e9, 3) == 6.061
    assert round(PUBLISHED['yi-coder-1.5b'] / 1e9, 3) == 1.476
    six = dims_of('yi-6b')
    assert six.kv_bytes_per_position() == 65536
    chat = dims_of('yi-coder-1.5b-chat')
    assert chat.kv_bytes_per_position() == 196608


def test_weights_match_the_programs_tree():
    import jax
    import jax.numpy as jnp
    import flax.linen as nn
    from skypilot_tpu.models.llama import Llama, LlamaConfig
    dims = LLAMA.Dims(hidden=64, layers=2, heads=4, kv_heads=2,
                      head_dim=16, ffn=128, vocab=256, rope_theta=1e4,
                      eps=1e-5)
    model = Llama(LlamaConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                              n_kv_heads=2, ffn_dim=128, max_seq_len=32,
                              remat=False))
    theirs = nn.meta.unbox(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))))['params']
    ours = jax.eval_shape(lambda: LLAMA.make_params(
        weights.seed_key(2**31 + 7), dims, jnp.float32))
    assert jax.tree.structure(theirs) == jax.tree.structure(ours)
    assert jax.tree.map(lambda a: a.shape, theirs) == \
        jax.tree.map(lambda a: a.shape, ours)
    # A layer made alone (as the reference makes it) is the tree's layer.
    key = weights.seed_key(11)
    whole = LLAMA.make_params(key, dims, jnp.bfloat16)
    alone = LLAMA.layer_weights(key, dims, 1, jnp.bfloat16)
    assert all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool((a == b).all()), whole['layer_1'], alone)))


def test_manifest_meets_the_contract():
    assert manifest.problems(MAN) == []
    assert set(MAN) == {'command', 'paths', 'run_seconds', 'configs',
                        'workloads', 'end_to_end', 'per_layer'}
    assert len(json.dumps(MAN)) < 64 * 1024
    n = len(MAN['workloads'])
    assert 43200 >= 1200 + (2 + 14 * 24) * (MAN['run_seconds'] + 60) \
        + 24 * 2 * 90, 'run_seconds must fit the full 24 cells'
    assert n >= 1
    for m in MAN['end_to_end'] + MAN['per_layer']:
        assert set(m) <= {'name', 'unit', 'better', 'bound', 'source',
                          'layer', 'moves', 'workloads'}
        spec = manifest.reducer_spec(m['name'])
        from benchmarks.harness import reducers
        own = os.path.join(manifest.reducer_dir(m['name']),
                           m['name'] + '.py')
        assert os.path.exists(own) or hasattr(reducers, spec['reducer'])
    layers = {m['layer'] for m in MAN['per_layer']}
    assert all('\n' not in name and len(name) <= 200 for name in layers)


def test_decode_cost_counts_live_positions_only():
    dims = dims_of('yi-6b')
    none = LLAMA.decode_step_cost(dims, 8, 0)
    some = LLAMA.decode_step_cost(dims, 8, 8 * 500)
    assert none['bytes'] == 2 * dims.matmul_params()
    assert some['bytes'] - none['bytes'] == 65536 * 4000
    least = costs.least_seconds(some, manifest.peaks_for('TPU v5 lite'))
    assert least['bound'] == 'memory'
    with pytest.raises(SystemExit):
        manifest.peaks_for('TPU v9 imaginary')


def test_without_a_chip_the_run_fails_and_prints_no_result():
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    proc = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, 'run.py'),
         '--workload', MAN['workloads'][0]['name'], '--seed', '1',
         '--seconds', '1', '--trace', '0'],
        env=env, capture_output=True, text=True, timeout=300, check=False)
    assert proc.returncode != 0
    assert not any(ln.startswith('{') for ln in proc.stdout.splitlines())
    assert 'not a TPU' in proc.stderr
