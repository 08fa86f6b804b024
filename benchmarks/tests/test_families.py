"""The family dispatch: the loader, and the Llama family's arithmetic and
weights held to constants read on the tree before the move (commit cb2d076,
`harness/costs.py` and `harness/weights.py` as they were, on the CPU; the
generator is the same on every backend), so that moving them changed no
digit."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import families
from benchmarks.harness import manifest, serve, weights

CONFIGS = sorted(os.path.basename(p)[:-len('.json')] for p in glob.glob(
    os.path.join(manifest.BENCH_DIR, 'configs', '*.json')))

# decode_step_cost at 7.04 live slots and 3,101 live positions (PERF.md
# section 5's load) and train_flops_per_token(seq_len=4096), by `repr`.
PINNED = {
    'yi-6b': dict(
        bytes='11800477696', flops='83270461030.4', train='41234202624.0',
        params=6061035520, kv=65536, matmul=5798625280),
    'yi-coder-1.5b-chat': dict(
        bytes='3300327424', flops='19551829360.64', train='10487857152.0',
        params=1476495360, kv=196608, matmul=1345323008),
    'yi-coder-1.5b-1chip': dict(
        bytes='1274871808', flops='7747605626.88', train='4020240384.0',
        params=666929152, kv=65536, matmul=535822336),
}

# The first 8 values of four leaves of make_params(seed_key(2**31 + 7), ...)
# at the small sizes below, as `float.hex` gave them.
PINNED_LEAVES = {
    ('float32', 'layer_1/attn/k_proj/kernel'): [
        '0x1.c2dbae0000000p-3', '-0x1.ddd6b00000000p-9',
        '0x1.a447280000000p-5', '-0x1.c416460000000p-4',
        '0x1.2b22f40000000p-4', '0x1.fdb4520000000p-4',
        '0x1.fbdbe60000000p-4', '-0x1.914e020000000p-5'],
    ('float32', 'layer_0/mlp/down_proj/kernel'): [
        '-0x1.ad87040000000p-3', '-0x1.0ca03c0000000p-2',
        '0x1.abb4aa0000000p-4', '0x1.a3ca300000000p-5',
        '-0x1.ae40ea0000000p-6', '-0x1.297b9c0000000p-4',
        '0x1.94b02c0000000p-4', '-0x1.7fe3440000000p-7'],
    ('bfloat16', 'lm_head/kernel'): [
        '-0x1.d800000000000p-3', '0x1.6200000000000p-3',
        '0x1.2800000000000p-6', '-0x1.1800000000000p-3',
        '-0x1.4200000000000p-5', '-0x1.b800000000000p-3',
        '-0x1.2e00000000000p-5', '0x1.1400000000000p-4'],
    ('bfloat16', 'embed/embedding'): [
        '-0x1.d600000000000p-7', '-0x1.fa00000000000p+0',
        '-0x1.dc00000000000p-2', '-0x1.d200000000000p+0',
        '0x1.4e00000000000p+0', '0x1.4200000000000p-2',
        '-0x1.2600000000000p-2', '-0x1.3600000000000p-2'],
}


def config_file(name):
    return manifest.load_json(manifest.BENCH_DIR, 'configs', f'{name}.json')


@pytest.mark.parametrize('name', sorted(PINNED))
def test_costs_and_counts_are_the_parents(name):
    cfg = config_file(name)
    family = families.load(cfg)
    dims, pin = family.dims(cfg), PINNED[name]
    cost = family.decode_step_cost(dims, 7.04, 3101)
    assert repr(cost['bytes']) == pin['bytes']
    assert repr(cost['flops']) == pin['flops']
    assert repr(family.train_flops_per_token(dims, 4096)) == pin['train']
    assert dims.num_params() == pin['params'] == cfg['params_total']
    assert dims.kv_bytes_per_position() == pin['kv']
    assert dims.matmul_params() == pin['matmul']


@pytest.mark.parametrize('dtype,leaf', sorted(PINNED_LEAVES))
def test_seeded_weights_are_the_parents(dtype, leaf):
    family = families.load({'architecture': 'LlamaForCausalLM'})
    dims = family.Dims(hidden=64, layers=2, heads=4, kv_heads=2, head_dim=16,
                       ffn=128, vocab=256, rope_theta=1e4, eps=1e-5)
    tree = weights.flat(family.make_params(
        weights.seed_key(2**31 + 7), dims, jnp.dtype(dtype)))
    got = np.asarray(tree[leaf].astype(jnp.float32)).ravel()[:8]
    assert [float(x).hex() for x in got] == PINNED_LEAVES[(dtype, leaf)]


@pytest.mark.parametrize('name', CONFIGS)
def test_every_configuration_has_its_family(name):
    """What ISSUE 28 asked of a tier-1 test, kept here: a benchmark PR adds
    no file outside `benchmarks/`."""
    cfg = config_file(name)
    family = families.load(cfg)
    assert os.path.basename(family.__file__) == cfg['architecture'] + '.py'
    dims = family.dims(cfg)
    assert dims.num_params() == cfg['params_total']
    assert dims.vocab == cfg['vocab_size']
    assert dims.layers == cfg['num_hidden_layers']
    for name_ in ('layer_weights', 'outer_weights', 'make_params',
                  'reference', 'decode_step_cost', 'train_flops_per_token',
                  'REHEARSAL'):
        assert hasattr(family, name_), name_


def test_loading_twice_gives_one_module():
    cfg = config_file('yi-6b')
    assert families.load(cfg) is families.load(cfg)


def test_an_architecture_without_a_file_ends_the_run(tmp_path, monkeypatch):
    monkeypatch.setattr(families, 'SEARCH_PATH',
                        [str(tmp_path)] + families.SEARCH_PATH)
    with pytest.raises(SystemExit) as e:
        families.load({'name': 'x', 'architecture': 'NoSuchForCausalLM'})
    assert str(tmp_path / 'NoSuchForCausalLM.py') in str(e.value)
    assert os.path.join(manifest.BENCH_DIR, 'families',
                        'NoSuchForCausalLM.py') in str(e.value)
    with pytest.raises(SystemExit, match='architecture'):
        families.load({'name': 'x'})


def test_a_family_without_a_builder_ends_the_cell(tmp_path, monkeypatch):
    (tmp_path / 'Bare.py').write_text('REHEARSAL = {}\n', encoding='utf-8')
    monkeypatch.setattr(families, 'SEARCH_PATH', [str(tmp_path)])
    bare = families.load({'architecture': 'Bare'})
    with pytest.raises(SystemExit, match=r'Bare\.py has no serve_model'):
        serve.build_engine(bare, {'serve': {}}, None, 1, jax.devices()[0])


def test_engine_options_go_by_field_name(monkeypatch):
    """Every key of `serve` that is a field of `EngineConfig` reaches it;
    `max_seq_len` is the family's."""
    from skypilot_tpu.inference import engine as engine_lib
    seen = {}

    class Engine:
        def __init__(self, model, params, ecfg):
            seen.update(model=model, params=params, ecfg=ecfg)

        def prewarm(self):
            pass

    monkeypatch.setattr(engine_lib, 'DecodeEngine', Engine)
    cfg = config_file('yi-6b')
    family = families.load(cfg)
    cfg.update(family.REHEARSAL)
    cfg['serve'].update(max_seq_len=128, prefill_buckets=[32, 64],
                        max_prompt_len=64, eos_id=7, not_a_field='ignored')
    serve.build_engine(family, cfg, family.dims(cfg), 3, jax.devices()[0])
    ecfg = seen['ecfg']
    assert (ecfg.n_slots, ecfg.steps_per_call, ecfg.prefill_buckets,
            ecfg.max_prompt_len, ecfg.kv_page_size, ecfg.eos_id) == (
                8, 8, (32, 64), 64, None, 7)
    assert seen['model'].cfg.max_seq_len == 128
    assert seen['params']['layer_1']['attn']['k_proj']['kernel'].dtype == \
        jnp.bfloat16
