"""ZAYA1 on the serving path, at a tiny size on the CPU, against the plain
reference (benchmarks/reference/zaya_ref.py): attention in a compressed,
convolved latent whose slot keeps three leaves of fixed size beside K and
V, every residual sum a scaled merge, and one expert a token behind an MLP
router with a state carried from layer to layer and an output that is no
expert.

Sizes (the family's rehearsal size): hidden 64, 4 query heads over 2 KV
heads of 16 (8 rotated), 3 layers, 4 experts of width 32 and the skip,
router width 8, vocabulary 256; float32 weights from the family's seed, so
that the program and the reference differ by rounding order only.
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
from benchmarks.reference import zaya_ref  # noqa: E402
from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig  # noqa: E402
from skypilot_tpu.models import moe as moe_lib  # noqa: E402
from skypilot_tpu.models import zaya as zaya_lib  # noqa: E402
from skypilot_tpu.perf import cost_model as cost_model_lib  # noqa: E402

SEED = 2**31 + 47
DTYPE = jnp.float32
CONFIG_FILE = 'zaya1-8b-pp2'
# float32 program against float32 reference: what is left is the order of
# the sums (a cached step against the reference's whole row), 2e-6 of
# logits of order 0.5; 1e-4 leaves a digit and a half of room.  A part of
# the model taken out moves the logits by 0.1 or more (the cases of `PARTS`
# print their distance): one expert a token, so a moved choice is a whole
# sublayer.
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


def answers_of(engine, prompts, n_new):
    requests = [engine.submit(p, n_new) for p in prompts]
    for _ in range(400):
        if all(r.finished_at is not None for r in requests):
            break
        engine.step_pipelined()
    return [(p, r.tokens()) for p, r in zip(prompts, requests)]


# Prompts of one token and of two (position 0 sees zeros, position 1 the
# taps of position 0), a group of different lengths in one padded prefill
# (run a row at a time: `prefill_rows`), and one longer than the largest
# bucket (a chunked prefill: the taps and the shifted value cross chunks).
PATHS = {'one': [1], 'two': [2], 'group': [1, 2, 9, 16], 'chunked': [37]}


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
    """(a) Prefill then decode through DecodeEngine's cache: every served
    token is the reference's own choice, up to float32 rounding (a gap of
    1e-3 below the reference's best logit is a near-tie decided by the
    order of a sum, not another token)."""
    family, dims, _ = tiny
    samples = served[1][path]
    assert all(len(tokens) == 20 for _, tokens in samples)
    verdict = check.served_gap(family, dims, SEED, DTYPE, samples, (64, 20))
    assert verdict['finite'] and verdict['positions'] == 20 * len(samples)
    assert verdict['widest_gap'] < 1e-3, verdict


@pytest.fixture(scope='module')
def rows_and_logits(tiny, seeded):
    """Rows of 1, 2, 16 and 11 tokens in ONE padded prefill, then 20
    decode steps: (rows, lengths, the program's logits at each row's last
    valid position and at every step after it, the last step's stats)."""
    _, dims, _ = tiny
    model, params = seeded
    rng = np.random.default_rng(5)
    lengths = np.array([1, 2, 16, 11])
    rows = rng.integers(0, dims.vocab, (4, 36))
    padded = np.where(np.arange(16)[None, :] < lengths[:, None],
                      rows[:, :16], 0)
    logits, out = model.apply(
        {'params': params}, jnp.asarray(padded), decode=True,
        lengths=jnp.asarray(lengths), mutable=['cache'])
    assert logits.shape == (4, 1, dims.vocab)
    got, cache, at = [np.asarray(logits[:, 0])], out['cache'], np.arange(4)
    for t in range(20):
        step, out = model.apply(
            {'params': params, 'cache': cache},
            jnp.asarray(rows[at, lengths + t])[:, None],
            positions=jnp.asarray(lengths + t)[:, None], decode=True,
            live=jnp.ones((4,), bool), mutable=['cache', 'stats'])
        cache = out['cache']
        got.append(np.asarray(step[:, 0]))
    return rows, lengths, np.stack(got, axis=1), out['stats']


def reference_logits(tiny, rows, lengths, alter=None):
    """The reference's logits at the positions `rows_and_logits` reads,
    [4, 21, vocab]; `alter(layer index, weights)` changes a layer's."""
    family, dims, _ = tiny
    sound = family.reference(dims, SEED, DTYPE)
    make = sound._make_layer if alter is None else \
        (lambda i: alter(i, sound._make_layer(i)))
    ref = zaya_ref.LayerwiseModel(dims, make, sound._make_outer)
    want = np.asarray(ref.logits_at(ref.hidden(jnp.asarray(rows))))
    at = (lengths - 1)[:, None] + np.arange(21)[None, :]
    return np.take_along_axis(want, at[:, :, None], axis=1)


def test_prefill_then_decode_gives_the_references_logits(tiny,
                                                         rows_and_logits):
    """(a) Prompts of 1, 2 and many tokens in one padded group, then 20
    decode steps through K, V and the three fixed leaves: the logits at
    each row's last valid position and at every step after it are the
    reference's full forward over the unpadded row, so padding reached
    neither the taps nor the shifted value and position 0 saw zeros."""
    rows, lengths, got, stats = rows_and_logits
    want = reference_logits(tiny, rows, lengths)
    np.testing.assert_allclose(got, want, atol=ROUNDING)
    assert set(stats) == {'layer_0', 'layer_1', 'layer_2'}


# ----- (c) each part matters -------------------------------------------------
def _weights(path, value):
    """A layer's weights with the leaf at `path` replaced (every layer)."""
    def alter(_, w):
        w = jax.tree.map(lambda a: a, w)
        node = w
        for key in path[:-1]:
            node = node[key]
        old = node[path[-1]]
        node[path[-1]] = value(old) if callable(value) else \
            jnp.full_like(old, value)
        return w
    return alter


def _identity_conv2(w2):
    eye = jnp.broadcast_to(jnp.eye(w2.shape[-1], dtype=w2.dtype),
                           w2[:, 1].shape)
    return jnp.stack([jnp.zeros_like(eye), eye], axis=1)


def _first_tap_only(w1):
    return jnp.stack([jnp.zeros_like(w1[0]), jnp.ones_like(w1[1])])


# name -> (functions of the reference put in another's place, a change to
# every layer's weights): the reference WITHOUT the part, or with the part
# at its identity.
PARTS = {
    'convolution_1': ({}, lambda i, w: _weights(
        ('attn', 'conv1_b'), 0.0)(i, _weights(
            ('attn', 'conv1_w'), _first_tap_only)(i, w))),
    'convolution_2': ({}, lambda i, w: _weights(
        ('attn', 'conv2_b'), 0.0)(i, _weights(
            ('attn', 'conv2_w'), _identity_conv2)(i, w))),
    'qk_mean': ({'qk_mean': lambda q, k: (0.0, 0.0)}, None),
    # `before` shifts the taps (wide) and the half value (narrow): the
    # value alone is left where it is.
    'value_shift': ({'before': lambda rows, shift=zaya_ref.before: (
        rows if rows.shape[-1] == 16 else shift(rows))}, None),
    'temperature': ({}, _weights(('attn', 'temp'), 0.0)),
    'l2_norm': ({'unit': lambda t: t}, None),
    'gamma': ({}, _weights(('router', 'gamma'), 0.0)),
    'beta': ({}, _weights(('router', 'balance'), 0.0)),
    'skip_term': ({'experts': lambda w, z, weight, matmul,
                   whole=zaya_ref.experts: whole(
                       w, z, weight.at[:, -1].set(0.0), matmul=matmul)},
                  None),
    'unnormalised_weight': ({'route': lambda w, z, carried, eps,
                             route=zaya_ref.route: (
                                 lambda weight, state: (
                                     (weight > 0).astype(jnp.float32),
                                     state))(*route(w, z, carried,
                                                    eps=eps))}, None),
    'attn_merge_scale': ({}, _weights(('attn_merge', 'stream_scale'), 1.0)),
    'attn_merge_bias': ({}, _weights(('attn_merge', 'branch_bias'), 0.0)),
    'ffn_merge_scale': ({}, _weights(('ffn_merge', 'branch_scale'), 1.0)),
    'ffn_merge_bias': ({}, _weights(('ffn_merge', 'stream_bias'), 0.0)),
}


@pytest.mark.parametrize('part', list(PARTS))
def test_each_part_matters(tiny, rows_and_logits, monkeypatch, part):
    """(c) The program's logits agree with the reference (the test
    above) and with no reference that lacks a part: with either
    convolution at its identity, the q-k mean, the value's shift, the L2
    norm or the skip's w * z taken out, the temperature at 1, gamma or
    beta at 0, the chosen expert weighted by 1 and not by its
    probability, or a merge's scale at 1 or bias at 0, the logits leave
    the tolerance by a factor of ten or more.  (beta at the seeded 0.02
    flips near-ties alone, so its case sets it a hundred times larger in
    the program's own weights.)"""
    rows, lengths, got, _ = rows_and_logits
    patches, alter = PARTS[part]
    for name, fn in patches.items():
        monkeypatch.setattr(zaya_ref, name, fn)
    if part == 'beta':
        family, dims, config = tiny
        big = lambda i, w: _weights(  # noqa: E731
            ('router', 'balance'), lambda b: 100.0 * b)(i, w)
        model = family.serve_model(dims, config, DTYPE)
        params = jax.jit(lambda k: family.make_params(k, dims, DTYPE))(
            weights.seed_key(SEED))
        params = {k: big(0, v) if k.startswith('layer_') else v
                  for k, v in params.items()}
        logits = model.apply({'params': params}, jnp.asarray(rows))
        at = (lengths - 1)[:, None] + np.arange(21)[None, :]
        got = np.take_along_axis(np.asarray(logits), at[:, :, None], axis=1)
        np.testing.assert_allclose(
            got, reference_logits(tiny, rows, lengths, big), atol=ROUNDING)
    away = np.abs(got - reference_logits(tiny, rows, lengths, alter)).max()
    print(f'{part}: the logits move by {away:.2e}')
    assert away > 10 * ROUNDING, (part, away)


# ----- (d) the skip ----------------------------------------------------------
def test_tokens_that_choose_the_skip_run_no_expert(tiny, seeded):
    """(d) With beta on the skip's output large every token chooses it:
    the counts say that no expert ran (no pair held or elsewhere, none
    touched, all of them skipped), and the tokens get w * z: the logits
    are the reference's, which adds it, and not those of a reference that
    leaves it out."""
    family, dims, _ = tiny
    model, params = seeded
    to_skip = _weights(('router', 'balance'), lambda b: b.at[-1].set(10.0))
    params = {k: to_skip(0, v) if k.startswith('layer_') else v
              for k, v in params.items()}
    rows = np.random.default_rng(3).integers(0, dims.vocab, (2, 12))
    logits, out = model.apply({'params': params}, jnp.asarray(rows),
                              decode=True, mutable=['cache', 'stats'])
    for layer in out['stats'].values():
        moe = layer['moe']
        assert int(moe['skipped'][0]) == 24
        assert not np.asarray(moe['expert_tokens'][0]).any()
        assert int(moe['touched'][0]) == 0
    sound = family.reference(dims, SEED, DTYPE)
    ref = zaya_ref.LayerwiseModel(
        dims, lambda i: to_skip(i, sound._make_layer(i)), sound._make_outer)
    want = np.asarray(ref.logits_at(ref.hidden(jnp.asarray(rows))))
    np.testing.assert_allclose(np.asarray(logits), want, atol=ROUNDING)


def test_skipped_pairs_are_counted_apart_and_multiplied_by_nobody():
    """`DroplessMoE` handed its routing: an id past the experts (the
    skip) is neither held nor elsewhere, its rows come back zero, and
    under `valid` a padded row's skip is not counted; the others' sums
    are what a layer without the skipping tokens gives them."""
    layer = moe_lib.DroplessMoE(dim=64, ffn_dim=32, n_experts=4,
                                held=(0, 1, 2), n_shared=0, n_skip=1,
                                dtype=DTYPE, param_dtype=DTYPE, block=8)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 10, 64), DTYPE)
    idx = jnp.asarray([0, 4, 1, 3, 4, 2, 4, 0, 3, 4])[:, None]
    w = jnp.linspace(0.2, 0.9, 10)[:, None]
    params = layer.init(jax.random.PRNGKey(2), x, routed=(idx, w))['params']
    out, stats = layer.apply({'params': params}, x, routed=(idx, w),
                             mutable=['stats'])
    stats = stats['stats']
    assert np.asarray(stats['expert_tokens'][0]).tolist() == [2, 1, 1, 2]
    assert int(stats['skipped'][0]) == 4 and int(stats['touched'][0]) == 3
    gone = np.asarray(idx[:, 0]) >= 3            # skipped, or held elsewhere
    assert not np.asarray(out)[0, gone].any()
    assert np.abs(np.asarray(out)[0, ~gone]).min(axis=1).max() > 0
    valid = (jnp.arange(10) < 6)[None, :]
    _, cut = layer.apply({'params': params}, x, valid, routed=(idx, w),
                         mutable=['stats'])
    assert int(cut['stats']['skipped'][0]) == 2
    assert np.asarray(cut['stats']['expert_tokens'][0]).tolist() == [
        1, 1, 1, 1 + 4]         # the padded rows count as elsewhere
    with pytest.raises(ValueError, match='handed its routing once'):
        layer.apply({'params': params}, x)


# ----- (e) the state along the depth -----------------------------------------
def test_the_router_state_crosses_layers(tiny, seeded):
    """(e) Layer 1 with the same stream and another state from layer 0:
    the state it hands on is d + gamma * the state it was handed, and
    some token's choice changes, so the stream it returns does."""
    _, dims, _ = tiny
    model, params = seeded
    cfg = model.cfg
    block = zaya_lib.Block(cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 40, cfg.dim), DTYPE)
    positions = jnp.broadcast_to(jnp.arange(40)[None, :], (2, 40))
    carried = jax.random.normal(jax.random.PRNGKey(6),
                                (2, 40, cfg.router_dim), DTYPE)

    def run(state):
        return block.apply({'params': params['layer_1']}, x, state,
                           positions, False, None, None)

    (y0, r0), (y1, r1) = run(carried), run(-carried)
    gamma = np.asarray(params['layer_1']['router']['gamma'])
    np.testing.assert_allclose(np.asarray(r0 - r1),
                               2 * gamma * np.asarray(carried), atol=1e-5)
    moved = np.abs(np.asarray(y0 - y1)).max(axis=-1) > 1e-3
    assert 0 < moved.sum()
    # The first layer is handed none, and takes its own d.
    _, first = zaya_lib.Block(cfg).apply(
        {'params': params['layer_0']}, x, None, positions, False, None, None)
    assert first.shape == carried.shape


# ----- (b) the cache ---------------------------------------------------------
def test_a_slot_keeps_k_v_and_three_leaves_of_fixed_size(tiny, served):
    """A layer's K and V of the 2 latent heads a position, and the last
    position's two rows of taps and half value a slot; the cost model and
    the gauge read the three as kind "recurrent" with no case of their
    own, and the bytes a token at 4 slots are the file's arithmetic at
    this size."""
    _, dims, _ = tiny
    engine = served[0]
    shapes = {'/'.join(str(getattr(p, 'key', p)) for p in path): leaf.shape
              for path, leaf in jax.tree_util.tree_flatten_with_path(
                  engine._cache)[0]}
    assert shapes == {f'layer_{i}/attn/{name}': shape for i in range(3)
                      for name, shape in (('k', (4, 2, 64, 16)),
                                          ('v', (4, 2, 64, 16)),
                                          ('tap0', (4, 96)),
                                          ('tap1', (4, 96)),
                                          ('v_shift', (4, 16)))}
    kv = 4 * 64 * dims.kv_bytes_per_position(4)
    fixed = 4 * dims.fixed_bytes_per_slot(4)
    assert (kv, fixed) == (4 * 64 * 3 * 2 * 2 * 16 * 4,
                           4 * 3 * (96 + 96 + 16) * 4)
    assert cost_model_lib.cache_bytes_by_kind(engine._cache) == {
        'kv': kv, 'recurrent': fixed}
    from skypilot_tpu.server import metrics as metrics_lib
    text = metrics_lib.render()
    assert 'skytpu_engine_cache_bytes{kind="recurrent"}' in text
    assert 'skytpu_moe_skipped_pairs_total' in text
    assert metrics_lib.help_registry()['skytpu_moe_skipped_pairs_total']


def test_a_reused_slot_reads_nothing_of_the_request_before(tiny, seeded):
    """(b) One slot: a long request, then a short one in the same slot.
    The second's tokens are the reference's: its K and V are read up to
    its own length, and its taps and shifted value start from zeros, not
    from the request before."""
    family, dims, _ = tiny
    model, params = seeded
    engine = DecodeEngine(model, params, EngineConfig(
        n_slots=1, prefill_buckets=(8, 16), steps_per_call=3))
    rng = np.random.default_rng(9)
    long = answers_of(engine, [rng.integers(0, dims.vocab, 16).tolist()], 30)
    short = answers_of(engine, [rng.integers(0, dims.vocab, 1).tolist()], 12)
    for samples, n in ((long, 30), (short, 12)):
        verdict = check.served_gap(family, dims, SEED, DTYPE, samples,
                                   (64, 30))
        assert verdict['positions'] == n and verdict['widest_gap'] < 1e-3


def test_paging_speculation_and_transfer_are_refused(seeded, served):
    """Leaves of fixed size in the page manager are a later PR: refused
    at construction with this model's reason, never a silent fall-back."""
    model, params = seeded
    for options in (dict(kv_page_size=8),
                    dict(kv_page_size=8, speculation=2)):
        with pytest.raises(ValueError, match='convolution taps.*'
                           'KV transfer'):
            DecodeEngine(model, params, EngineConfig(
                n_slots=2, prefill_buckets=(8, 16), **options))
    with pytest.raises(RuntimeError, match='requires the paged KV cache'):
        served[0].submit_prefill([1, 2, 3])


# ----- (f) the HTTP server ---------------------------------------------------
def test_the_http_server_serves_the_references_tokens(tiny, seeded):
    """(f) `inference/server.py` over the engine's loop thread: a
    completion over HTTP is the reference's choice at every token."""
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


# ----- (g) the controls ------------------------------------------------------
def _bf16_router(w, z, carried, *, eps, route=zaya_ref.route):
    """The reference's router with every operand rounded to bfloat16."""
    low = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    return route(jax.tree.map(low, w), low(z),
                 None if carried is None else low(carried), eps=eps)


@pytest.mark.parametrize('control', ['int8', zaya_ref.WRONG_EXPERT])
def test_a_control_fails_the_tolerance_the_sound_run_passes(tiny, served,
                                                            control):
    """(g) The served tokens lie a mean 1e-6 below the float32 reference's
    best; the tokens a control puts first lie further below it by two
    orders or more: the int8 control (W8A8 products in the hook's place),
    and the control of the expert path alone (float32, every routed token
    met by its expert's neighbour under its own weight).  A tolerance of
    1e-4 on the mean gap passes the sound run with room and fails both."""
    family, dims, _ = tiny
    samples = [s for name in PATHS for s in served[1][name]]
    verdict = check.served_gap(family, dims, SEED, DTYPE, samples, (64, 20),
                               control=control)
    print(f'{control}: {verdict}')
    assert verdict['mean_gap'] < 1e-5, verdict
    assert verdict['mean_gap'] < 1e-4 < verdict['control']['mean_gap']
    assert verdict['control']['off_best'] > verdict['off_best']


def test_a_bfloat16_router_fails_the_tolerance_on_the_logits(
        tiny, rows_and_logits, monkeypatch):
    """(g) The router is float32 at `highest` precision because one
    expert a token makes a near-tie a whole sublayer, and because the
    chosen probability weighs the expert unnormalised: a reference whose
    router's operands are rounded to bfloat16 leaves the tolerance the
    program keeps (`ROUNDING`, in the test of the logits above) by a
    factor of 3.8 at this size through the weight's third digit alone
    (no choice of these 84 tokens flips; on the chip one token in 300 a
    layer does: PERF.md section 6, PR 47)."""
    rows, lengths, got, _ = rows_and_logits
    monkeypatch.setattr(zaya_ref, 'route', _bf16_router)
    away = np.abs(got - reference_logits(tiny, rows, lengths)).max()
    print(f'bfloat16 router: the logits move by {away:.2e}')
    assert away > 2 * ROUNDING, away


# ----- the configuration -----------------------------------------------------
def test_held_parameters_are_the_files_arithmetic_and_the_programs_tree():
    """The configuration file's total, its arithmetic worked out here, the
    family's count, the program's count and the seeded tree; the cache's
    arithmetic; every width the catalog row's."""
    config = published_config()
    family = families.load(config)
    dims = family.dims(config)
    cca = (2048 * 1024 + 2048 * 256 + 2 * 2048 * 128 + 1024 * 2048 +
           1280 * 2 + 1280 + 10 * 128 * 128 * 2 + 1280 + 2)
    router = (2048 * 256 + 256 + 256 + 256 + 2 * (256 * 256 + 256) +
              256 * 17 + 17)
    expert = 3 * 2048 * 2048
    assert (cca, router, expert) == (5575682, 661009, 12582912)
    layer = cca + router + 16 * expert + 4096 + 16384
    assert layer == 207583763 == dims.layer_params()
    total = 20 * layer + 262272 * 2048 + 2048
    assert total == config['params_total'] == dims.num_params() == \
        4688810364
    assert 40 * (cca + router + expert) == 752784120      # active a token
    model = family.serve_model(dims, config, jnp.bfloat16)
    assert model.cfg.num_params() == total
    tree = jax.eval_shape(lambda: family.make_params(
        weights.seed_key(1), dims, jnp.bfloat16))
    assert sum(leaf.size for leaf in jax.tree.leaves(tree)) == total
    serve = config['serve']
    assert dims.kv_bytes_per_position() == 20480
    assert dims.fixed_bytes_per_slot() == 107520
    assert serve['n_slots'] * serve['max_seq_len'] * 20480 == 4362076160
    assert (config['published'], config['reduced']) == (
        {'num_hidden_layers': 40}, ['num_hidden_layers'])
    assert (config['hidden_size'], config['head_dim'],
            config['num_attention_heads'], config['num_key_value_heads'],
            config['moe_intermediate_size'], config['num_experts'],
            config['num_experts_per_tok'], config['router_hidden_size'],
            config['vocab_size']) == (2048, 128, 8, 2, 2048, 16, 1, 256,
                                      262272)
