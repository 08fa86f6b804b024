"""MiMo-V2 on the serving path, at a tiny size on the CPU, against the plain
reference (benchmarks/reference/mimo_v2_ref.py): window layers that keep a
ring of their window's positions beside full layers that keep the context
(a fourth kind of engine cache leaf), two KV head counts, keys wider than
values, a sink in the window softmax, a leading dense layer and a dropless
expert layer whose router chooses by a correction bias.

Sizes (the family's rehearsal size, the window cut to 8): hidden 64, 4
query heads of 24 (8 rotated) with values of 16, 2 KV heads in the full
layer and 4 in the two window layers, 3 layers [full + dense, window,
window], 16 experts of width 32 with 4 held and 2 a token, vocabulary 256;
float32 weights from the family's seed, so that the program and the
reference differ by rounding order only.
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
from benchmarks.reference import mimo_v2_ref  # noqa: E402
from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig  # noqa: E402
from skypilot_tpu.models import moe as moe_lib  # noqa: E402
from skypilot_tpu.models.mimo_v2 import (Attention, MiMoV2Config,  # noqa: E402
                                         published_pattern, ring_source)
from skypilot_tpu.ops import attention as attn_lib  # noqa: E402
from skypilot_tpu.perf import cost_model as cost_model_lib  # noqa: E402

SEED = 2**31 + 41
DTYPE = jnp.float32
CONFIG_FILE = 'mimo-v2.5-ep16'
WINDOW = 8
# float32 program against float32 reference: what is left is the order of
# the sums (the ring holds a window's positions in another order than the
# reference's band), 3e-6 of logits of order 1; 1e-4 leaves a digit and a
# half of room.
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
    config.update(sliding_window=WINDOW, sliding_window_size=WINDOW)
    config['serve'].update(max_seq_len=64)
    return family, family.dims(config), config


@pytest.fixture(scope='module')
def seeded(tiny):
    family, dims, config = tiny
    model = family.serve_model(dims, config, DTYPE)
    params = jax.jit(lambda k: family.make_params(k, dims, DTYPE))(
        weights.seed_key(SEED))
    return model, params


def answers_of(engine, prompts, n_new):
    requests = [engine.submit(p, n_new) for p in prompts]
    for _ in range(400):
        if all(r.finished_at is not None for r in requests):
            break
        engine.step_pipelined()
    return [(p, r.tokens()) for p, r in zip(prompts, requests)]


# Prompt lengths against the window of 8: below it, at it, a group of
# different lengths past it (run a row at a time: `prefill_rows`), and
# one several windows long that is longer than the largest bucket (a
# chunked prefill).  Every request then decodes 20 tokens, so every ring
# wraps at least twice in the decode steps too.
PATHS = {'below': [5], 'at': [8], 'group': [9, 12, 16], 'chunked': [37]}


@pytest.fixture(scope='module')
def served(tiny, seeded):
    _, dims, _ = tiny
    model, params = seeded
    engine = DecodeEngine(model, params, EngineConfig(
        n_slots=4, prefill_buckets=(8, 16), steps_per_call=3))
    rng = np.random.default_rng(SEED)
    answers = {}
    for name, lengths in PATHS.items():
        prompts = [rng.integers(0, dims.vocab, n).tolist() for n in lengths]
        answers[name] = answers_of(engine, prompts, 20)
    return engine, answers


@pytest.mark.parametrize('path', list(PATHS))
def test_served_tokens_are_the_references(tiny, served, path):
    """(a) Prefill then decode through DecodeEngine's cache at contexts
    below, at and several times past the window: every served token is
    the reference's own choice, up to float32 rounding (a gap of 1e-3
    below the reference's best logit is a near-tie decided by the order
    of a sum, not another token)."""
    family, dims, _ = tiny
    samples = served[1][path]
    assert all(len(tokens) == 20 for _, tokens in samples)
    verdict = check.served_gap(family, dims, SEED, DTYPE, samples, (64, 20))
    assert verdict['finite'] and verdict['positions'] == 20 * len(samples)
    assert verdict['widest_gap'] < 1e-3, verdict


def test_prefill_then_decode_gives_the_references_logits(tiny, seeded):
    """(a) One padded prefill of rows whose lengths lie below, at and past
    the window, then 20 decode steps through both kinds of cache (every
    ring wraps): the logits at each row's last valid position and at
    every step after it are the reference's full forward over the
    unpadded row (ROUNDING says why 1e-4)."""
    family, dims, _ = tiny
    model, params = seeded
    rng = np.random.default_rng(5)
    lengths = np.array([16, 5, 8, 11])
    rows = rng.integers(0, dims.vocab, (4, 36))
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
    for t in range(20):
        step, out = model.apply(
            {'params': params, 'cache': cache},
            jnp.asarray(rows[at, lengths + t])[:, None],
            positions=jnp.asarray(lengths + t)[:, None], decode=True,
            live=jnp.ones((4,), bool), mutable=['cache', 'stats'])
        cache = out['cache']
        np.testing.assert_allclose(np.asarray(step[:, 0]),
                                   want[at, lengths + t], atol=ROUNDING)
    assert set(out['stats']) == {'layer_1', 'layer_2'}   # layer 0 is dense


def test_a_ring_row_holds_the_last_position_of_its_residue():
    """`ring_source`: once a sequence has L positions, ring row r holds
    the last position that is r modulo the window, and none (negative)
    where the sequence has not reached the row."""
    got = np.asarray(ring_source(jnp.asarray([3, 8, 21]), 8))
    assert got[0].tolist() == [0, 1, 2, -5, -4, -3, -2, -1]
    assert got[1].tolist() == list(range(8))
    assert got[2].tolist() == [16, 17, 18, 19, 20, 13, 14, 15]


# ----- the sink --------------------------------------------------------------
def test_the_sink_takes_weight_and_gives_no_value():
    """(b) A window layer whose sinks are -inf is the plain windowed
    softmax; at their seeded value (normal(0, 1)) it is not, by far more
    than rounding; and a sink's share of a head's weight is what its
    logit says."""
    cfg = MiMoV2Config(
        vocab_size=256, dim=64, n_layers=2, layer_pattern=(0, 1),
        n_heads=4, qk_dim=24, v_dim=16, rope_dim=8, n_kv_heads=2,
        window_kv_heads=4, window=WINDOW, max_seq_len=32, dtype=DTYPE,
        param_dtype=DTYPE)
    layer = Attention(cfg, True)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 20, 64), DTYPE)
    positions = jnp.broadcast_to(jnp.arange(20)[None, :], (2, 20))
    params = layer.init(jax.random.PRNGKey(4), x, positions, False)['params']
    assert params['sink'].shape == (4,)
    sinks = jax.random.normal(jax.random.PRNGKey(5), (4,), DTYPE)

    def out(sink):
        return layer.apply({'params': dict(params, sink=sink)}, x, positions,
                           False)

    q, k, v = jax.random.normal(jax.random.PRNGKey(6), (3, 2, 4, 20, 16))
    plain = attn_lib.mha_reference(q, k, v, causal=True, window=WINDOW)
    gone = attn_lib.mha_reference(q, k, v, causal=True, window=WINDOW,
                                  sink=jnp.full((4,), -jnp.inf))
    np.testing.assert_allclose(np.asarray(gone), np.asarray(plain),
                               atol=1e-6)
    # By hand for one row: position 12 of head 1 sees positions 5..12.
    a = np.asarray(q[0, 1, 12] @ k[0, 1, 5:13].T) * 16 ** -0.5
    e = np.exp(a - a.max())
    by_hand = (e / (e.sum() + np.exp(float(sinks[1]) - a.max()))
               ) @ np.asarray(v[0, 1, 5:13])
    with_sink = attn_lib.mha_reference(q, k, v, causal=True, window=WINDOW,
                                       sink=sinks)
    np.testing.assert_allclose(np.asarray(with_sink[0, 1, 12]), by_hand,
                               atol=1e-5)
    # The module: -inf sinks are no sinks, the seeded ones are not.
    no_sink = out(jnp.full((4,), -jnp.inf))
    assert np.isfinite(np.asarray(no_sink)).all()
    assert np.abs(np.asarray(out(sinks)) - np.asarray(no_sink)).max() > 1e-2


# ----- the expert layer's shares and the correction bias ---------------------
def moe_layer(held):
    return moe_lib.DroplessMoE(
        dim=64, ffn_dim=32, n_experts=16, held=tuple(held),
        router=moe_lib.LinearRouter(top_k=2, bias=True), n_shared=0, dtype=DTYPE, param_dtype=DTYPE,
        block=16)


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """(c) Sixteen chips share a layer: sixteen shares of one expert each
    (a sixteenth of the 16 experts, as 16 of 256) give what the uncut
    reference gives for the whole layer under this model's routing
    (sigmoid, the 2 largest of score + bias, weighted by the scores,
    normalised); and the bias decides: some token's choice is not its 2
    largest scores."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 64), DTYPE)
    params = dict(moe_layer(range(16)).init(jax.random.PRNGKey(2),
                                            x)['params'])
    params['correction_bias'] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(7), (16,), DTYPE)
    total, held_pairs = 0.0, 0
    for j in range(16):
        share = {'router': params['router'],
                 'correction_bias': params['correction_bias'],
                 **{k: params[k][j:j + 1]
                    for k in ('w_gate', 'w_up', 'w_down')}}
        out, stats = moe_layer([j]).apply({'params': share}, x,
                                          mutable=['stats'])
        counts = np.asarray(stats['stats']['expert_tokens'][0])
        assert counts.sum() == 80 * 2
        held_pairs += counts[0]
        total = total + out
    assert held_pairs == 80 * 2
    with jax.default_matmul_precision('highest'):
        want = jnp.concatenate([mimo_v2_ref.expert_layer(
            params, x[r:r + 1], held=tuple(range(16)), top_k=2,
            matmul=mimo_v2_ref.plain_matmul) for r in range(2)])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-4)
    scores = jax.nn.sigmoid(x.reshape(80, 64) @ params['router'])
    biased, _ = moe_lib.route_top_k(scores, 2, bias=params['correction_bias'])
    unbiased, weights_ = moe_lib.route_top_k(scores, 2)
    moved = (np.sort(np.asarray(biased)) != np.sort(np.asarray(unbiased))
             ).any(axis=1)
    assert 0 < moved.sum() < 80
    # The weights are the chosen scores', whoever chose.
    idx, w = moe_lib.route_top_k(scores, 2, bias=params['correction_bias'])
    picked = np.take_along_axis(np.asarray(scores), np.asarray(idx), axis=1)
    np.testing.assert_allclose(np.asarray(w),
                               picked / picked.sum(axis=1, keepdims=True),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(weights_).sum(axis=1), 1.0,
                               rtol=1e-6)


def test_a_padded_prompts_rows_are_nobodys_to_multiply():
    """`valid` takes a padded prompt's rows past its length out of the
    expert loop: their pairs count as routed elsewhere, the rows come back
    zero, and the real rows' sums are what they were."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 64), DTYPE)
    layer = moe_layer(range(4))
    params = layer.init(jax.random.PRNGKey(2), x)['params']
    valid = jnp.arange(40)[None, :] < jnp.asarray([25, 40])[:, None]
    whole, stats = layer.apply({'params': params}, x, mutable=['stats'])
    cut, cut_stats = layer.apply({'params': params}, x, valid,
                                 mutable=['stats'])
    np.testing.assert_allclose(np.asarray(cut)[np.asarray(valid)],
                               np.asarray(whole)[np.asarray(valid)],
                               atol=1e-6)
    assert not np.asarray(cut)[~np.asarray(valid)].any()
    before = np.asarray(stats['stats']['expert_tokens'][0])
    after = np.asarray(cut_stats['stats']['expert_tokens'][0])
    assert before.sum() == after.sum() == 80 * 2
    assert after[:4].sum() < before[:4].sum()
    assert after[4] - before[4] == before[:4].sum() - after[:4].sum()


# ----- the two kinds of cache ------------------------------------------------
def test_each_kind_of_layer_keeps_a_cache_of_its_own(tiny, served):
    """(d) A full layer keeps max_seq_len positions of its 2 KV heads, a
    window layer a ring of the window's 8 of its 4: keys as their 16
    unrotated values and, two KV heads a row, their 8 rotated ones; the
    cost model and the gauge read the rings as kind "window"."""
    _, dims, _ = tiny
    engine = served[0]
    shapes = {'/'.join(str(getattr(p, 'key', p)) for p in path): leaf.shape
              for path, leaf in jax.tree_util.tree_flatten_with_path(
                  engine._cache)[0]}
    ring = {f'layer_{i}/attn/{name}': shape for i in (1, 2)
            for name, shape in (('ring_k/nope', (4, 4, WINDOW, 16)),
                                ('ring_k/rope', (4, 2, WINDOW, 16)),
                                ('ring_v', (4, 4, WINDOW, 16)))}
    assert shapes == {'layer_0/attn/k/nope': (4, 2, 64, 16),
                      'layer_0/attn/k/rope': (4, 1, 64, 16),
                      'layer_0/attn/v': (4, 2, 64, 16), **ring}
    names = engine.model.served().window_leaves
    assert names == ('ring_k', 'ring_v')
    full = 4 * 64 * dims.kv_bytes_per_position(False, 4)
    rings = 4 * WINDOW * dims.kv_bytes_per_position(True, 4)
    assert (full, rings) == (4 * 64 * 2 * 40 * 4, 4 * 8 * 8 * 40 * 4)
    assert cost_model_lib.cache_bytes_by_kind(
        engine._cache, window=names) == {'kv': full, 'window': rings}
    assert cost_model_lib.window_len(engine._cache, names) == WINDOW
    cm = engine.perf_cost_model
    assert (cm.n_kv_layers, cm.n_window_layers, cm.window_len) == (
        1, 2, WINDOW)
    assert cm.kv_bytes_per_pos() == 2 * 40 * 4
    assert cm.window_bytes_per_pos == 8 * 40 * 4
    # A context of 30: the full layer reads 30 positions, a ring 8.
    assert cm.decode_hbm_bytes_per_token(30, 2) == (
        cm.param_bytes / 2 + 31 * cm.kv_bytes_per_pos() +
        (8 + 1) * cm.window_bytes_per_pos)
    assert cm.decode_flops_per_token(30) == (
        2.0 * cm.n_params + 2.0 * 64 * (30 + 2 * 8))
    assert cm.decode_flops_per_token(3) == (
        2.0 * cm.n_params + 2.0 * 64 * (3 + 2 * 3))
    from skypilot_tpu.server import metrics as metrics_lib
    text = metrics_lib.render()
    assert 'skytpu_engine_cache_bytes{kind="window"}' in text
    assert 'skytpu_engine_cache_bytes{kind="kv"}' in text
    assert 'skytpu_moe_pairs_total{where="held"}' in text


def test_a_reused_slot_reads_nothing_of_the_request_before(tiny, seeded):
    """(d) One slot: a request several windows long, then a short one in
    the same slot.  The second's tokens are the reference's: its ring
    starts over (rows the short prompt has not reached are bounded out),
    and the full layer reads up to its own length."""
    family, dims, _ = tiny
    model, params = seeded
    engine = DecodeEngine(model, params, EngineConfig(
        n_slots=1, prefill_buckets=(8, 16), steps_per_call=3))
    rng = np.random.default_rng(9)
    long = answers_of(engine, [rng.integers(0, dims.vocab, 16).tolist()], 30)
    short = answers_of(engine, [rng.integers(0, dims.vocab, 3).tolist()], 12)
    for samples, n in ((long, 30), (short, 12)):
        verdict = check.served_gap(family, dims, SEED, DTYPE, samples,
                                   (64, 30))
        assert verdict['positions'] == n and verdict['widest_gap'] < 1e-3


def test_the_window_counter_counts_rings_against_contexts(served):
    """A decode call's window layers: `fetched` is a ring a slot and step
    (the CPU reads every slot's, held or not), `context` the positions
    the held slots' contexts hold, which is what a layer that kept the
    context would have read."""
    from skypilot_tpu.server import metrics as metrics_lib
    engine = served[0]
    assert engine._window == WINDOW
    engine._window_fetched = engine._window_context = 0
    held = np.array([True, False, True, False])
    read = np.array([[30, 31, 32], [1, 2, 3], [9, 10, 11], [1, 2, 3]])
    engine._count_kv_read(read, held)
    assert engine._window_fetched == 4 * 3 * WINDOW
    assert engine._window_context == 30 + 31 + 32 + 9 + 10 + 11
    engine._window_fetched = engine._window_context = 0
    text = metrics_lib.render()
    assert 'skytpu_engine_window_kv_positions_total{kind="fetched"}' in text
    assert 'skytpu_engine_window_kv_positions_total{kind="context"}' in text


def test_paging_speculation_and_transfer_are_refused(seeded, served):
    """A ring in the page manager is a later PR (ROADMAP B2): refused at
    construction with this model's reason, never a silent fall-back."""
    model, params = seeded
    for options in (dict(kv_page_size=8),
                    dict(kv_page_size=8, speculation=2)):
        with pytest.raises(ValueError, match='keeps a ring of its window.*'
                           'KV transfer'):
            DecodeEngine(model, params, EngineConfig(
                n_slots=2, prefill_buckets=(8, 16), **options))
    with pytest.raises(RuntimeError, match='requires the paged KV cache'):
        served[0].submit_prefill([1, 2, 3])


# ----- the HTTP server -------------------------------------------------------
def test_the_http_server_serves_the_references_tokens(tiny, seeded):
    """(e) `inference/server.py` over the engine's loop thread: a
    completion over HTTP whose context passes the window is the
    reference's choice at every token."""
    import asyncio
    from aiohttp.test_utils import TestClient, TestServer
    from skypilot_tpu.inference.server import build_app
    family, dims, _ = tiny
    model, params = seeded
    engine = DecodeEngine(model, params, EngineConfig(
        n_slots=2, prefill_buckets=(8, 16), steps_per_call=3))
    prompt = np.random.default_rng(11).integers(0, dims.vocab, 13).tolist()
    engine.start()

    async def drive():
        client = TestClient(TestServer(build_app(engine)))
        await client.start_server()
        try:
            r = await client.post('/v1/completions', json={
                'prompt_ids': prompt, 'max_tokens': 12})
            assert r.status == 200
            return (await r.json())['ids']
        finally:
            await client.close()

    try:
        ids = asyncio.new_event_loop().run_until_complete(drive())
    finally:
        engine.stop()
    assert engine.healthy and len(ids) == 12
    verdict = check.served_gap(family, dims, SEED, DTYPE, [(prompt, ids)],
                               (64, 12))
    assert verdict['widest_gap'] < 1e-3


# ----- the control -----------------------------------------------------------
def test_a_lower_precision_fails_the_tolerance_the_sound_run_passes(
        tiny, served):
    """(f) The int8 control (W8A8 products in the reference's place) puts
    tokens first that lie a mean 9e-4 below the float32 reference's best
    at this size, where the served tokens lie 1e-6 below it: a tolerance
    of 1e-4 on the mean gap passes the sound run with two digits of room
    and fails the control by nearly one."""
    family, dims, _ = tiny
    samples = [s for name in PATHS for s in served[1][name]]
    verdict = check.served_gap(family, dims, SEED, DTYPE, samples, (64, 20),
                               control='int8')
    assert verdict['mean_gap'] < 1e-5, verdict
    assert verdict['mean_gap'] < 1e-4 < verdict['control']['mean_gap']
    assert verdict['control']['off_best'] > verdict['off_best']


# ----- the configuration -----------------------------------------------------
def test_held_parameters_are_the_files_arithmetic_and_the_programs_tree(
        tiny):
    """The configuration file's total, its arithmetic worked out here, the
    family's count, the program's count and the seeded tree; the published
    pattern; the cache's arithmetic."""
    config = published_config()
    family = families.load(config)
    dims = family.dims(config)
    full = 4096 * 12288 + 4096 * 768 + 4096 * 512 + 8192 * 4096
    window = 4096 * 12288 + 4096 * 1536 + 4096 * 1024 + 8192 * 4096 + 64
    router = 4096 * 256 + 256
    experts = 16 * 3 * 4096 * 2048
    layer0 = full + 3 * 4096 * 16384 + 8192
    assert (full, window, layer0) == (89128960, 94371904, 290463744)
    assert window + router + experts + 8192 == 498082112
    assert full + router + experts + 8192 == 492839168
    total = layer0 + 5 * 498082112 + 492839168 + 2 * 19072 * 4096 + 4096
    assert total == config['params_total'] == dims.num_params() == \
        3429955392
    model = family.serve_model(dims, config, jnp.bfloat16)
    assert model.cfg.num_params() == total
    assert model.cfg.layer_pattern == (0, 1, 1, 1, 1, 0, 1) == \
        published_pattern(48)[:7]
    assert tuple(config['hybrid_layer_pattern']) == published_pattern(48)
    tree = jax.eval_shape(lambda: family.make_params(
        weights.seed_key(1), dims, jnp.bfloat16))
    assert sum(leaf.size for leaf in jax.tree.leaves(tree)) == total
    serve = config['serve']
    assert serve['n_slots'] * serve['max_seq_len'] * \
        dims.kv_bytes_per_position(False) == 1509949440
    assert serve['n_slots'] * dims.window * \
        dims.kv_bytes_per_position(True) == 104857600
    assert (config['published'], config['reduced']) == (
        {'num_hidden_layers': 48, 'n_routed_experts': 256,
         'vocab_size': 152576},
        ['num_hidden_layers', 'n_routed_experts', 'vocab_size'])
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
