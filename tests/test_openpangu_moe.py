"""openPangu-Ultra-MoE on the serving path, at a tiny size on the CPU,
against the plain reference (benchmarks/reference/openpangu_moe_ref.py):
latent attention whose cache is one latent a position (a third kind of
engine cache leaf), absorbed in the decode step and expanded in prefill,
sandwich norms, a leading dense layer, and a dropless expert layer that
holds a share of the experts.

Sizes (the family's rehearsal size): hidden 64, 4 heads of 16 + 8 with a
latent of 32, 3 layers of which the first is dense, 16 experts of width 32
with 4 held and 2 a token, vocabulary 256; float32 weights from the
family's seed, so that the program and the reference differ by rounding
order only.
"""
import copy
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import families  # noqa: E402
from benchmarks.harness import check, manifest, weights  # noqa: E402
from benchmarks.reference import solar_open2_ref  # noqa: E402
from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig  # noqa: E402
from skypilot_tpu.models import moe as moe_lib  # noqa: E402
from skypilot_tpu.models.openpangu_moe import (LatentAttention,  # noqa: E402
                                               OpenPanguMoEConfig)
from skypilot_tpu.ops import attention as attn_lib  # noqa: E402
from skypilot_tpu.ops.pallas import latent_decode_attention as pallas_la  # noqa: E402
from skypilot_tpu.perf import cost_model as cost_model_lib  # noqa: E402

SEED = 2**31 + 35
DTYPE = jnp.float32
CONFIG_FILE = 'openpangu-ultra-moe-718b-ep16'
# float32 program against float32 reference: what is left is the order of
# the sums (the absorbed products sum over the latent first), 1e-6 of
# logits of order 1; 1e-4 leaves two digits of room.
ROUNDING = 1e-4


def published_config():
    return manifest.load_json(manifest.BENCH_DIR, 'configs',
                              f'{CONFIG_FILE}.json')


@pytest.fixture(scope='module')
def tiny():
    """(family, dims, config) at the family's rehearsal size."""
    config = copy.deepcopy(published_config())
    family = families.load(config)
    config.update(family.REHEARSAL)
    config['serve'].update(max_seq_len=64)
    return family, family.dims(config), config


@pytest.fixture(scope='module')
def seeded(tiny):
    family, dims, config = tiny
    model = family.serve_model(dims, config, DTYPE)
    params = jax.jit(lambda k: family.make_params(k, dims, DTYPE))(
        weights.seed_key(SEED))
    return model, params


@pytest.fixture(scope='module')
def served(tiny, seeded):
    """An engine over the seeded weights, and what it answered to prompts
    of every path: alone in a bucket, three of different lengths admitted
    as one padded group (which the engine runs a row at a time:
    `prefill_rows`), and one longer than the largest bucket."""
    _, dims, _ = tiny
    model, params = seeded
    engine = DecodeEngine(model, params, EngineConfig(
        n_slots=4, prefill_buckets=(8, 16), steps_per_call=3))
    rng = np.random.default_rng(SEED)
    answers = {}
    for name, lengths in (('alone', [7]), ('group', [9, 12, 16]),
                          ('chunked', [37])):
        prompts = [rng.integers(0, dims.vocab, n).tolist() for n in lengths]
        requests = [engine.submit(p, 6) for p in prompts]
        for _ in range(200):
            if all(r.finished_at is not None for r in requests):
                break
            engine.step_pipelined()
        answers[name] = [(p, r.tokens()) for p, r in zip(prompts, requests)]
    return engine, answers


@pytest.mark.parametrize('path', ['alone', 'group', 'chunked'])
def test_served_tokens_are_the_references(tiny, served, path):
    """Prefill then decode through DecodeEngine's cache, a padded group of
    different lengths, a chunked prefill: every served token is the
    reference's own choice, up to float32 rounding."""
    family, dims, _ = tiny
    samples = served[1][path]
    assert all(len(tokens) == 6 for _, tokens in samples)
    verdict = check.served_gap(family, dims, SEED, DTYPE, samples, (64, 6))
    assert verdict['finite'] and verdict['positions'] == 6 * len(samples)
    assert verdict['widest_gap'] < 1e-3, verdict


def test_prefill_then_decode_gives_the_references_logits(tiny, seeded):
    """One padded prefill of rows of different lengths, then decode steps
    through the cache: the logits at each row's last valid position and
    at every step after it are the reference's full forward over the
    unpadded row (ROUNDING says why 1e-4)."""
    family, dims, _ = tiny
    model, params = seeded
    rng = np.random.default_rng(5)
    lengths = np.array([16, 5, 11, 2])
    rows = rng.integers(0, dims.vocab, (4, 20))
    ref = family.reference(dims, SEED, DTYPE)
    want = np.asarray(ref.logits_at(ref.hidden(jnp.asarray(rows))))
    padded = np.where(np.arange(16)[None, :] < lengths[:, None],
                      rows[:, :16], 0)
    logits, out = model.apply(
        {'params': params}, jnp.asarray(padded), decode=True,
        lengths=jnp.asarray(lengths), mutable=['cache'])
    assert logits.shape == (4, 1, dims.vocab)
    at = np.arange(4)
    np.testing.assert_allclose(np.asarray(logits[:, 0]),
                               want[at, lengths - 1], atol=ROUNDING)
    cache = out['cache']
    for t in range(4):
        step, out = model.apply(
            {'params': params, 'cache': cache},
            jnp.asarray(rows[at, lengths + t])[:, None],
            positions=jnp.asarray(lengths + t)[:, None], decode=True,
            mutable=['cache', 'stats'])
        cache = out['cache']
        np.testing.assert_allclose(np.asarray(step[:, 0]),
                                   want[at, lengths + t], atol=ROUNDING)
    assert set(out['stats']) == {'layer_1', 'layer_2'}   # layer 0 is dense


@pytest.fixture(scope='module')
def attention():
    cfg = OpenPanguMoEConfig(
        vocab_size=256, dim=64, n_layers=1, n_dense_layers=1, n_heads=4,
        q_rank=32, kv_rank=32, nope_dim=16, rope_dim=8, v_dim=16,
        max_seq_len=32, dtype=DTYPE, param_dtype=DTYPE)
    layer = LatentAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 12, 64), DTYPE)
    positions = jnp.broadcast_to(jnp.arange(12)[None, :], (2, 12))
    params = layer.init(jax.random.PRNGKey(4), x, positions, False)['params']
    return cfg, layer, params, x, positions


def test_absorbed_decode_equals_the_expanded_form(attention):
    """The decode step (W_kvb absorbed into the query and the output,
    attention over the latent) gives what the expanded form gives over
    per-head keys and values, position by position."""
    _, layer, params, x, positions = attention
    want = layer.apply({'params': params}, x, positions, False)
    _, out = layer.apply({'params': params}, x[:, :8], positions[:, :8],
                         True, mutable=['cache'])
    cache = out['cache']
    for t in range(8, 12):
        got, out = layer.apply({'params': params, 'cache': cache},
                               x[:, t:t + 1], positions[:, t:t + 1], True,
                               mutable=['cache'])
        cache = out['cache']
        np.testing.assert_allclose(np.asarray(got[:, 0]),
                                   np.asarray(want[:, t]), atol=1e-5)


def test_the_cache_holds_a_latent_and_the_step_never_expands_it(attention):
    """What is cached a position is kv_rank + rope values (here 32 + 8; at
    the published widths 512 + 64 = 576), not heads x (keys + values), and
    no array of the decode step has a head axis beside the cache's
    positions."""
    cfg, layer, params, x, positions = attention
    _, out = layer.apply({'params': params}, x[:, :8], positions[:, :8],
                         True, mutable=['cache'])
    cache = out['cache']
    assert jax.tree.map(lambda a: a.shape, cache) == {
        'c_kv': (2, 32, 32), 'k_pe': (2, 32, 8)}
    jaxpr = jax.make_jaxpr(lambda c: layer.apply(
        {'params': params, 'cache': c}, x[:, 8:9], positions[:, 8:9], True,
        mutable=['cache']))(cache)

    def shapes(jaxpr):
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                yield tuple(v.aval.shape)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from shapes(sub)

    expanded = [s for s in shapes(jaxpr.jaxpr) if len(s) == 4 and
                cfg.n_heads in s and cfg.max_seq_len in s]
    assert not expanded, expanded


# ----- the decode kernel ------------------------------------------------------
# Lengths [B] over S = 384 positions in tiles of 128 or 256 (the second
# leaves the last tile ragged, as 4,736 = 9 x 512 + 128 does): an empty
# slot, one position, a tile's edge on both sides, a full slot.
KERNEL_LENGTHS = {
    'empty_one_edge': [0, 1, 128],
    'past_edge_full_mid': [129, 384, 256],
    'before_edge': [127, 383, 5],
}


@pytest.mark.parametrize('block', [128, 256])
@pytest.mark.parametrize('case', list(KERNEL_LENGTHS))
def test_the_latent_kernel_gives_what_the_jnp_path_gives(case, block):
    """`ops/pallas/latent_decode_attention.py` in interpret mode against
    `latent_attention_reference` (what the CPU and a mesh run): equal up
    to the bfloat16 rounding of the probabilities (one part in 256 of
    sums of order 1), and zeros for an empty slot."""
    b, h, c_dim, r_dim, s = 3, 8, 128, 64, 384
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q_lat = jax.random.normal(keys[0], (b, h, c_dim), jnp.bfloat16) * 0.2
    q_pe = jax.random.normal(keys[1], (b, h, r_dim), jnp.bfloat16) * 0.2
    c_kv = jax.random.normal(keys[2], (b, s, c_dim), jnp.bfloat16)
    k_pe = jax.random.normal(keys[3], (b, s, r_dim), jnp.bfloat16)
    lengths = jnp.asarray(KERNEL_LENGTHS[case])
    want = attn_lib.latent_attention_reference(q_lat, q_pe, c_kv, k_pe,
                                               lengths)
    got = pallas_la.latent_decode_attention_fwd(
        q_lat, q_pe, c_kv, k_pe, lengths, block=block, interpret=True)
    assert got.shape == want.shape == (b, h, c_dim)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0.03)
    empty = np.asarray(lengths) == 0
    assert not np.asarray(got, np.float32)[empty].any()


def test_the_kernel_is_engaged_by_backend_mesh_and_shapes(monkeypatch):
    """No flag: the CPU reads the cache through XLA; on one TPU device the
    tile follows the shapes, and widths or lengths the tiling cannot take
    fall back to XLA."""
    assert attn_lib.latent_kv_block(512, 4736) is None          # the CPU
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    assert attn_lib.latent_kv_block(512, 4736) == 1024
    assert attn_lib.latent_kv_block(512, 256) == 256
    assert attn_lib.latent_kv_block(32, 4736) is None
    assert attn_lib.latent_kv_block(512, 4700) is None
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1] * 2).reshape(2),
                             ('tensor',))
    assert attn_lib.latent_kv_block(512, 4736, mesh) is None


# ----- the expert layer's shares ---------------------------------------------
def moe_layer(held, n_shared=1):
    return moe_lib.DroplessMoE(
        dim=64, ffn_dim=32, n_experts=16, held=tuple(held),
        router=moe_lib.LinearRouter(top_k=2, scaling=2.5),
        n_shared=n_shared, dtype=DTYPE,
        param_dtype=DTYPE, block=16)


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Sixteen chips share a layer: sixteen shares of one expert each (a
    sixteenth of the 16 experts, as 16 of 256), the shared expert counted
    once, give what the uncut reference gives for the whole layer under
    this model's routing (sigmoid, 2 a token, normalised, times 2.5)."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 64), DTYPE)
    params = moe_layer(range(16)).init(jax.random.PRNGKey(2), x)['params']
    total, held_pairs = 0.0, 0
    for j in range(16):
        share = {'router': params['router'],
                 **{k: params[k][j:j + 1]
                    for k in ('w_gate', 'w_up', 'w_down')}}
        if j == 0:
            share.update({k: v for k, v in params.items() if 'shared' in k})
        out, stats = moe_layer([j], n_shared=int(j == 0)).apply(
            {'params': share}, x, mutable=['stats'])
        counts = np.asarray(stats['stats']['expert_tokens'][0])
        assert counts.sum() == 80 * 2
        held_pairs += counts[0]
        total = total + out
    assert held_pairs == 80 * 2
    with jax.default_matmul_precision('highest'):
        want = solar_open2_ref.expert_layer(
            params, x, held=tuple(range(16)), top_k=2, scaling=2.5,
            matmul=solar_open2_ref.plain_matmul)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-4)


# ----- the configuration, the engine's refusals, the cache's kind ------------
def test_held_parameters_are_the_files_arithmetic_and_the_programs_tree(
        tiny):
    """The configuration file's total, its arithmetic worked out here, the
    family's count, the program's count and the seeded tree."""
    config = published_config()
    family = families.load(config)
    dims = family.dims(config)
    attention = (7680 * 1536 + 1536 + 1536 * 128 * 192 + 7680 * 576 + 512 +
                 512 * 128 * 256 + 128 * 128 * 7680)
    dense = attention + 3 * 7680 * 18432 + 4 * 7680
    expert = attention + 7680 * 256 + 17 * 3 * 7680 * 2048 + 4 * 7680
    total = dense + 4 * expert + 2 * 19200 * 7680 + 7680
    assert (attention, dense, expert) == (196577280, 621281280, 1000734720)
    assert total == config['params_total'] == dims.num_params() == \
        4919139840
    model = family.serve_model(dims, config, jnp.bfloat16)
    assert model.cfg.num_params() == total
    tree = jax.eval_shape(lambda: family.make_params(
        weights.seed_key(1), dims, jnp.bfloat16))
    assert sum(leaf.size for leaf in jax.tree.leaves(tree)) == total
    serve = config['serve']
    assert serve['n_slots'] * serve['max_seq_len'] * \
        dims.latent_bytes_per_position() == 872939520
    assert (config['published'], config['reduced']) == (
        {'num_hidden_layers': 61, 'first_k_dense_replace': 3,
         'n_routed_experts': 256, 'vocab_size': 153600,
         'num_nextn_predict_layers': 1},
        ['num_hidden_layers', 'first_k_dense_replace', 'n_routed_experts',
         'vocab_size', 'num_nextn_predict_layers'])
    # The tree the family makes is the tree the program initialises.
    import flax.linen as nn
    family, dims, config = tiny
    model = family.serve_model(dims, config, DTYPE)
    theirs = nn.meta.unbox(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))))['params']
    ours = jax.eval_shape(lambda: family.make_params(
        weights.seed_key(1), dims, DTYPE))
    assert jax.tree.map(lambda a: a.shape, theirs) == \
        jax.tree.map(lambda a: a.shape, ours)
    assert model.cfg.num_params() == dims.num_params()


def test_paging_speculation_and_transfer_are_refused(seeded, served):
    """A latent in the page manager is a later PR (ROADMAP B3): refused at
    construction with the reason, never a silent fall-back."""
    model, params = seeded
    for options in (dict(kv_page_size=8),
                    dict(kv_page_size=8, speculation=2)):
        with pytest.raises(ValueError, match='caches a latent a position '
                           'in place of keys and values a head.*KV '
                           'transfer'):
            DecodeEngine(model, params, EngineConfig(
                n_slots=2, prefill_buckets=(8, 16), **options))
    with pytest.raises(RuntimeError, match='requires the paged KV cache'):
        served[0].submit_prefill([1, 2, 3])


def test_cache_and_cost_model_carry_a_latent(tiny, served):
    """The engine's cache holds kv_rank + rope values a position and layer
    and nothing a head, and the cost model reads a position's bytes from
    those leaves as the third kind."""
    _, dims, _ = tiny
    engine = served[0]
    shapes = {'/'.join(str(getattr(p, 'key', p)) for p in path): leaf.shape
              for path, leaf in jax.tree_util.tree_flatten_with_path(
                  engine._cache)[0]}
    assert shapes == {f'layer_{i}/attn/{name}': (4, 64, width)
                      for i in range(3)
                      for name, width in (('c_kv', 32), ('k_pe', 8))}
    latent = engine.model.served().latent_leaves
    assert latent == ('c_kv', 'k_pe')
    assert cost_model_lib.cache_bytes_by_kind(engine._cache, latent) == {
        'latent': 4 * 64 * dims.latent_bytes_per_position(4)}
    cm = engine.perf_cost_model
    assert cm.n_kv_layers == 3 and cm.state_bytes_per_slot == 0
    assert cm.kv_bytes_per_pos() == dims.latent_bytes_per_position(4) == \
        3 * (32 + 8) * 4
    assert cm.decode_hbm_bytes_per_token(10, 2) == (
        cm.param_bytes / 2 + 11 * cm.kv_bytes_per_pos())
    from skypilot_tpu.server import metrics as metrics_lib
    text = metrics_lib.render()
    assert 'skytpu_engine_cache_bytes{kind="latent"}' in text
    assert 'skytpu_moe_pairs_total{where="held"}' in text
    assert 'skytpu_engine_decode_kv_positions_total{kind="held"}' in text
