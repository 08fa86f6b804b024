"""Granite-4.0-H on the serving path, at a tiny size on the CPU, against the
plain reference (benchmarks/reference/granite_hybrid_ref.py, which runs the
recurrence a position at a time): Mamba-2 layers whose float32 state and
convolution taps live in the engine's cache beside the K and V of a NoPE
attention layer, four scalar multipliers, a tied head.

Sizes: hidden 64, 4 layers (mamba, attention, mamba, mamba), 4 Mamba heads
of 16 over a state of 16, chunks of 8, attention of 4 heads of 16 over 2 KV
heads, vocabulary 256; float32 weights from the family's seed, so that the
program and the reference differ by rounding order only.
"""
import copy
import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import families  # noqa: E402
from benchmarks.harness import check, manifest, weights  # noqa: E402
from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig  # noqa: E402
from skypilot_tpu.models import granite_hybrid as granite_lib  # noqa: E402
from skypilot_tpu.models.granite_hybrid import (GraniteHybrid,  # noqa: E402
                                                chunk_scan, ssm_step,
                                                ssm_step_groups,
                                                state_from_leaf,
                                                state_to_leaf)
from skypilot_tpu.ops.pallas import ssm_state_update as pallas_ssm  # noqa: E402
from served_utils import declaring  # noqa: E402

SEED = 2**31 + 43
DTYPE = jnp.float32
CONFIG_FILE = 'granite-4.0-h-micro'
# Float32 against float32: the program's chunked scan, its cache and its
# folded scale reorder sums of a few hundred terms of size <= 1; the widest
# difference of a logit read here is 1e-7 (logits of size 0.01: the tied
# table is drawn small, benchmarks/families/GraniteMoeHybridForCausalLM.py).
ATOL = 1e-6


def published_config():
    return manifest.load_json(manifest.BENCH_DIR, 'configs',
                              f'{CONFIG_FILE}.json')


# The model with two rows of a prefill at a time, so that a padded group of
# four goes into the cache in two groups.
TwoRows = declaring(GraniteHybrid, prefill_rows=2)


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
    model = TwoRows(family.serve_model(dims, config, DTYPE).cfg)
    params = jax.jit(lambda k: family.make_params(k, dims, DTYPE))(
        weights.seed_key(SEED))
    return model, params


def answers_of(engine, prompts, n_new=6):
    requests = [engine.submit(p, n_new) for p in prompts]
    for _ in range(300):
        if all(r.finished_at is not None for r in requests):
            break
        engine.step_pipelined()
    return [(p, r.tokens()) for p, r in zip(prompts, requests)]


# Prompts shorter than a chunk of 8, a chunk long and several chunks long
# in ONE padded group of four rows (two groups of `prefill_rows`, the
# padding row in the second); one alone; one longer than the bucket.
PATHS = {'group': [5, 8, 29], 'alone': [7], 'chunked': [37]}


@pytest.fixture(scope='module')
def served(tiny, seeded):
    """An engine over the seeded weights and what it answered."""
    _, dims, _ = tiny
    model, params = seeded
    engine = DecodeEngine(model, params, EngineConfig(
        n_slots=4, prefill_buckets=(32,), steps_per_call=3))
    rng = np.random.default_rng(SEED)
    answers = {
        name: answers_of(engine, [rng.integers(0, dims.vocab, n).tolist()
                                  for n in lengths])
        for name, lengths in PATHS.items()}
    return engine, answers


def gap_of(tiny, samples, **more):
    family, dims, _ = tiny
    return check.served_gap(family, dims, SEED, DTYPE, samples, (64, 6),
                            **more)


# ----- (a) prefill then decode through the engine's cache ---------------------
@pytest.mark.parametrize('path', list(PATHS))
def test_served_tokens_are_the_references(tiny, served, path):
    """Prefill then decode through DecodeEngine: a padded group whose rows
    end inside a chunk, at a chunk's end and chunks later, inserted two
    rows at a time; a row alone; a chunked prefill.  Every served token is
    the reference's own choice, up to float32 rounding."""
    samples = served[1][path]
    assert all(len(tokens) == 6 for _, tokens in samples)
    verdict = gap_of(tiny, samples)
    assert verdict['finite'] and verdict['positions'] == 6 * len(samples)
    assert verdict['widest_gap'] < ATOL, verdict


def test_padding_reaches_neither_state_nor_taps(tiny, seeded):
    """One padded prefill of rows of different lengths: the logits at each
    row's last valid position, and the first decode step after it, are the
    reference's for the unpadded row; with the lengths left out (padding
    folded into state and taps) they are not."""
    family, dims, _ = tiny
    model, params = seeded
    rng = np.random.default_rng(5)
    lengths = np.array([32, 5, 8, 29])
    rows = rng.integers(0, dims.vocab, (4, 33))
    ref = family.reference(dims, SEED, DTYPE)
    want = np.asarray(ref.logits_at(ref.hidden(jnp.asarray(rows))))
    padded = np.where(np.arange(32)[None, :] < lengths[:, None],
                      rows[:, :32], 0)

    def prefill_then_step(told):
        logits, cache = model.apply(
            {'params': params}, jnp.asarray(padded), decode=True,
            lengths=told, mutable=['cache'])
        step, _ = model.apply(
            {'params': params, 'cache': cache['cache']},
            jnp.asarray(rows[np.arange(4), lengths])[:, None],
            positions=jnp.asarray(lengths)[:, None], decode=True,
            mutable=['cache'])
        return np.asarray(logits), np.asarray(step[:, 0])

    last, step = prefill_then_step(jnp.asarray(lengths))
    assert last.shape == (4, 1, dims.vocab)
    for i, n in enumerate(lengths):
        np.testing.assert_allclose(last[i, 0], want[i, n - 1], atol=ATOL)
        np.testing.assert_allclose(step[i], want[i, n], atol=ATOL)
    _, wrong = prefill_then_step(None)
    assert np.abs(wrong[0] - want[0, 32]).max() < ATOL      # no padding
    assert np.abs(wrong[1] - want[1, 5]).max() > 100 * ATOL  # 27 padded


# ----- (b) the chunked scan ---------------------------------------------------
def test_the_chunked_scan_is_the_recurrence_where_a_head_forgets_fast():
    """`chunk_scan` against one `ssm_step` a position, with a head whose
    log decay is -30 a position (a quotient of cumulative products would
    be exp(240) over a chunk of 8: the decays are formed pairwise) beside
    one that hardly forgets, from a state that is not zero: finite,
    equal."""
    b, s, h, p, n = 2, 24, 3, 4, 8
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    x = jax.random.normal(keys[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, s, h)))
    a = -jnp.array([30.0, 1.0, 0.01])
    dt = dt.at[:, :, 0].set(1.0)                 # dt a = -30 a position
    bb = jax.random.normal(keys[2], (b, s, n))
    cc = jax.random.normal(keys[3], (b, s, n))
    state0 = jax.random.normal(keys[4], (b, h, p, n))
    got, got_state = chunk_scan(state0, x, dt, a, bb, cc, chunk=8)

    def step(leaf, xs):              # a head a group: [B, H, N, P]
        x_t, dt_t, b_t, c_t = xs
        y, leaf = ssm_step(
            leaf, jnp.broadcast_to(jnp.exp(dt_t * a)[..., None], x_t.shape),
            dt_t[..., None] * x_t, jnp.zeros_like(x_t), b_t, c_t)
        return leaf, y

    want_state, want = jax.lax.scan(step, state_to_leaf(state0, 1), tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, bb, cc)))
    want, want_state = jnp.moveaxis(want, 0, 1), state_from_leaf(want_state,
                                                                 1)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(got_state, want_state, atol=2e-5, rtol=1e-5)
    # The fast head keeps nothing of the state it started from.
    assert np.abs(np.asarray(got_state[:, 0])).max() < 10.0


# ----- (c) the kernel ---------------------------------------------------------
def test_the_leaf_puts_heads_side_by_side_and_the_state_on_the_sublanes():
    """`state_to_leaf`: lane i * P + p of group g is channel p of head g *
    pack + i, the state's N the axis before; `state_from_leaf` undoes
    it."""
    state = jnp.arange(2 * 6 * 4 * 8, dtype=jnp.float32).reshape(2, 6, 4, 8)
    leaf = state_to_leaf(state, 2)
    assert leaf.shape == (2, 3, 8, 8)
    assert float(leaf[1, 2, 5, 4 + 3]) == float(state[1, 2 * 2 + 1, 3, 5])
    assert (state_from_leaf(leaf, 2) == state).all()


def step_inputs(slots=3, groups=2, n=16, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    shape = (slots, groups, 128)
    dt = jax.nn.softplus(jax.random.normal(keys[2], shape))
    dt = dt.at[1].set(0.0)                       # a padded row, an empty slot
    x = jax.random.normal(keys[1], shape)
    return (jax.random.normal(keys[0], (slots, groups, n, 128)),
            jnp.exp(-dt * jnp.exp(jax.random.normal(keys[3], shape))),
            dt * x, jax.random.normal(keys[6], shape) * x,
            jax.random.normal(keys[4], (slots, n)),
            jax.random.normal(keys[5], (slots, n)))


@pytest.mark.parametrize('case', [
    dict(groups=2, n=16, block=None), dict(groups=16, n=16, block=8),
    dict(groups=3, n=128, block=None)],
    ids=['whole', 'two_blocks', 'state_of_128'])
def test_the_state_kernel_is_ssm_step(case):
    """`ssm_state_update_fwd` in interpret mode against `ssm_step`: the
    same `y` and state up to the order of a sum over N, and a row with
    `dt` 0 keeps its state bit for bit."""
    args = step_inputs(groups=case['groups'], n=case['n'])
    want_y, want_state = ssm_step(*args)
    got_y, got_state = pallas_ssm.ssm_state_update_fwd(
        *args, groups=case['block'], interpret=True)
    np.testing.assert_allclose(got_y, want_y, atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(got_state, want_state, atol=1e-6, rtol=1e-6)
    assert (np.asarray(got_state[1]) == np.asarray(args[0][1])).all()
    assert not (np.asarray(got_state[0]) == np.asarray(args[0][0])).all()


@pytest.mark.parametrize('why', ['the_cpu', 'a_two_device_mesh',
                                 'more_than_one_position', 'a_bfloat16_state',
                                 'rows_of_64_lanes'])
def test_the_rule_sends_everything_else_to_ssm_step(monkeypatch, why):
    """`ssm_step_groups` chooses the kernel where `kda_step_heads` chooses
    Solar-Open2's: one position, float32, the TPU, one device, and a leaf
    whose rows fill 128 lanes (16 of the published 32 pairs of heads a
    grid step: a megabyte of state)."""
    state = jax.ShapeDtypeStruct((2, 32, 128, 128), jnp.float32)
    if why != 'the_cpu':
        monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
        assert ssm_step_groups(state, 1) == 16
    mesh = None
    positions = 1
    if why == 'a_two_device_mesh':
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1] * 2).reshape(2),
                                 ('x',))
    elif why == 'more_than_one_position':
        positions = 2
    elif why == 'a_bfloat16_state':
        state = jax.ShapeDtypeStruct(state.shape, jnp.bfloat16)
    elif why == 'rows_of_64_lanes':
        state = jax.ShapeDtypeStruct((2, 64, 128, 64), jnp.float32)
    assert ssm_step_groups(state, positions, mesh) is None


# ----- (d) a slot that is used again ------------------------------------------
def test_a_slot_reused_after_a_longer_request_reads_nothing_of_it(tiny,
                                                                  seeded):
    """One slot: a request of 29 + 6 positions, then one of 5 + 6 in the
    same slot.  The second's tokens are the reference's: nothing of the
    first's state, taps, keys or values is left where it reads."""
    _, dims, _ = tiny
    model, params = seeded
    engine = DecodeEngine(model, params, EngineConfig(
        n_slots=1, prefill_buckets=(32,), steps_per_call=3))
    rng = np.random.default_rng(9)
    first = answers_of(engine, [rng.integers(0, dims.vocab, 29).tolist()])
    state = np.asarray(engine._cache['layer_0']['mamba']['state'])
    assert np.abs(state).max() > 0
    second = answers_of(engine, [rng.integers(0, dims.vocab, 5).tolist()])
    for samples in (first, second):
        assert gap_of(tiny, samples)['widest_gap'] < ATOL


# ----- (e) the multipliers ----------------------------------------------------
@pytest.mark.parametrize('field,conventional', [
    ('embedding_multiplier', 1.0), ('attention_multiplier', 16 ** -0.5),
    ('residual_multiplier', 1.0), ('logits_scaling', 1.0)])
def test_each_multiplier_matters(tiny, seeded, field, conventional):
    """With any of the four multipliers at its conventional value (no
    embedding or residual multiplier, scores scaled by head_dim ** -0.5
    and not by 1/64, logits not divided) the logits leave the tolerance
    that the published values keep, by two orders of magnitude or more."""
    family, dims, _ = tiny
    model, params = seeded
    tokens = jnp.asarray(np.random.default_rng(2).integers(
        0, dims.vocab, (2, 24)))
    ref = family.reference(dims, SEED, DTYPE)
    want = np.asarray(ref.logits_at(ref.hidden(tokens)))
    sound = np.asarray(model.apply({'params': params}, tokens))
    assert np.abs(sound - want).max() < ATOL
    other = GraniteHybrid(dataclasses.replace(model.cfg,
                                              **{field: conventional}))
    wrong = np.asarray(other.apply({'params': params}, tokens))
    assert np.abs(wrong - want).max() > 100 * ATOL, field


# ----- (f) the HTTP server ----------------------------------------------------
def test_the_http_server_serves_the_references_tokens(tiny, seeded):
    """`inference/server.py` over the engine's loop thread: a completion
    over HTTP is the reference's choice at every token."""
    import asyncio
    from aiohttp.test_utils import TestClient, TestServer
    from skypilot_tpu.inference.server import build_app
    family, dims, _ = tiny
    model, params = seeded
    engine = DecodeEngine(model, params, EngineConfig(
        n_slots=2, prefill_buckets=(32,), steps_per_call=3))
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
    assert verdict['widest_gap'] < ATOL


# ----- (g) a lower precision --------------------------------------------------
def test_a_lower_precision_fails_the_tolerance_the_sound_run_passes(
        tiny, seeded, monkeypatch):
    """A prefill of 24 positions and 15 decode steps through the cache lie
    within `ATOL` of the reference's logits (1.5e-8 read: float32 against
    float32).  The reference with W8A8 products in its place (the int8
    control) lies 9e-4 from it, and the program itself with its state
    rounded to bfloat16 after every update 1.1e-5 (8 bits of a state that
    sums tens of positions): a tolerance that passed either would not tell
    the stated precision from the next one down."""
    family, dims, _ = tiny
    model, params = seeded
    rows = np.random.default_rng(4).integers(0, dims.vocab, (2, 40))
    ref = family.reference(dims, SEED, DTYPE)
    want = np.asarray(ref.logits_at(ref.hidden(jnp.asarray(rows))))
    low = family.reference(dims, SEED, DTYPE, 'int8')
    control = np.asarray(low.logits_at(low.hidden(jnp.asarray(rows))))
    assert np.abs(control - want).max() > 100 * ATOL

    def through_the_cache():
        logits, cache = model.apply(
            {'params': params}, jnp.asarray(rows[:, :24]), decode=True,
            lengths=jnp.array([24, 24]), mutable=['cache'])
        out = [np.asarray(logits[:, 0])]
        for t in range(24, 39):
            logits, cache = model.apply(
                {'params': params, 'cache': cache['cache']},
                jnp.asarray(rows[:, t:t + 1]),
                positions=jnp.full((2, 1), t), decode=True,
                mutable=['cache'])
            out.append(np.asarray(logits[:, 0]))
        return np.stack(out, axis=1)

    assert np.abs(through_the_cache() - want[:, 23:39]).max() < ATOL

    def rounded(step):
        @functools.wraps(step)
        def wrapper(*args, **kwargs):
            y, state = step(*args, **kwargs)
            return y, state.astype(jnp.bfloat16).astype(jnp.float32)
        return wrapper

    monkeypatch.setattr(granite_lib, 'ssm_step', rounded(ssm_step))
    monkeypatch.setattr(granite_lib, 'chunk_scan', rounded(chunk_scan))
    assert np.abs(through_the_cache() - want[:, 23:39]).max() > 5 * ATOL


# ----- counters, cache, configuration -----------------------------------------
def test_the_updates_counter_says_who_updated(seeded, monkeypatch):
    """`publish_stats` counts the Mamba head-states a decode call updated
    (slots x Mamba layers x heads x steps) under the path its program
    took, and the yardstick's reader gives the kernel's share: nothing for
    a program without the counter, 0 where every step went through XLA."""
    from benchmarks.harness import reducers
    from skypilot_tpu.server import metrics as metrics_lib
    model, _ = seeded

    def updates():
        return {path: float(line.rpartition(' ')[2])
                for line in metrics_lib.render().splitlines()
                for path in ('kernel', 'xla')
                if line.startswith(
                    f'skytpu_ssm_state_updates_total{{path="{path}"}}')}

    before = updates()
    model.served().publish_stats(                          # the CPU
        {'rows_stepped': (np.array([4 * 3]),)})
    monkeypatch.setattr(granite_lib, 'ssm_step_groups', lambda *_: 4)
    model.served().publish_stats({'rows_stepped': (np.array([4 * 8]),)})
    after = updates()
    assert after['xla'] - before.get('xla', 0.0) == 4 * 3 * 3 * 4
    assert after['kernel'] - before.get('kernel', 0.0) == 4 * 8 * 3 * 4

    def read(text):
        monkeypatch.setattr(metrics_lib, 'render', lambda: text)
        return reducers.reduce_metric('ssm_kernel_updates_pct', {})

    assert read('skytpu_kda_state_updates_total{path="kernel"} 8\n') is None
    assert read('skytpu_ssm_state_updates_total{path="kernel"} 0\n'
                'skytpu_ssm_state_updates_total{path="xla"} 6144\n') == 0.0
    assert read('skytpu_ssm_state_updates_total{path="kernel"} 3\n'
                'skytpu_ssm_state_updates_total{path="xla"} 1\n') == 75.0


def test_a_decode_call_counts_its_steps(tiny, seeded):
    """The engine sums the model's `stats` over a call's steps: serving a
    request moves the counter by slots x steps x Mamba layers x heads a
    call."""
    from skypilot_tpu.server import metrics as metrics_lib
    _, dims, _ = tiny
    model, params = seeded

    def through_xla():
        return sum(float(line.rpartition(' ')[2])
                   for line in metrics_lib.render().splitlines()
                   if line.startswith(
                       'skytpu_ssm_state_updates_total{path="xla"}'))

    engine = DecodeEngine(model, params, EngineConfig(
        n_slots=4, prefill_buckets=(32,), steps_per_call=3))
    assert engine._stats_abs['rows_stepped'][0].shape == (1,)
    before = through_xla()
    answers_of(engine, [list(range(1, 8))])
    moved = through_xla() - before
    assert moved > 0 and moved % (4 * 3 * 3 * 4) == 0


def test_paging_speculation_and_transfer_are_refused(seeded, served):
    """State in the page manager is a later PR: refused at construction
    with the reason, never a silent fall-back."""
    model, params = seeded
    for options in (dict(kv_page_size=8),
                    dict(kv_page_size=8, speculation=2)):
        with pytest.raises(ValueError, match='keeps recurrent state beside '
                           'its keys and values.*KV transfer'):
            DecodeEngine(model, params, EngineConfig(
                n_slots=2, prefill_buckets=(8, 16), **options))
    with pytest.raises(RuntimeError, match='requires the paged KV cache'):
        served[0].submit_prefill([1, 2, 3])


def test_the_cache_holds_state_taps_and_two_heads_a_row(tiny, served):
    """The engine's cache: a float32 state (its N before the four heads'
    channels side by side) and the taps a Mamba layer with the slot
    leading, and the attention layer's K and V with two KV heads side by
    side a row; the cost model reads its bytes from those leaves."""
    _, dims, _ = tiny
    engine = served[0]
    shapes = {'/'.join(str(getattr(p, 'key', p)) for p in path): leaf.shape
              for path, leaf in jax.tree_util.tree_flatten_with_path(
                  engine._cache)[0]}
    assert shapes == {
        'layer_0/mamba/conv': (4, 3, 96), 'layer_0/mamba/state': (4, 1, 16, 64),
        'layer_1/attn/k': (4, 1, 64, 32), 'layer_1/attn/v': (4, 1, 64, 32),
        'layer_2/mamba/conv': (4, 3, 96), 'layer_2/mamba/state': (4, 1, 16, 64),
        'layer_3/mamba/conv': (4, 3, 96), 'layer_3/mamba/state': (4, 1, 16, 64)}
    cm = engine.perf_cost_model
    assert cm.n_kv_layers == 1 and cm.n_layers == 4
    assert cm.kv_bytes_per_pos() == dims.kv_bytes_per_position(4)
    assert cm.state_bytes_per_slot == 3 * (4 * 16 * 16 * 4 + 3 * 96 * 4)
    assert cm.decode_hbm_bytes_per_token(10, 2) == (
        cm.param_bytes / 2 + 11 * cm.kv_bytes_per_pos() +
        2 * cm.state_bytes_per_slot)


def test_parameters_are_the_files_arithmetic_and_the_programs_tree(tiny):
    """The configuration file's total, its arithmetic worked out here, the
    family's count, the program's count and the seeded tree; nothing cut;
    the state's arithmetic."""
    config = published_config()
    family = families.load(config)
    dims = family.dims(config)
    mixer = (2048 * 8512 + (4352 * 4 + 4352) + 3 * 64 + 4096 + 4096 * 2048)
    ffn = 2048 * 16384 + 8192 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    total = (36 * (mixer + ffn + 4096) + 4 * (attention + ffn + 4096) +
             100352 * 2048 + 2048)
    assert (mixer, ffn, attention) == (25847232, 50331648, 10485760)
    assert total == 3191396096 == config['params_total'] == dims.num_params()
    model = family.serve_model(dims, config, jnp.bfloat16)
    assert model.cfg.num_params() == total
    tree = jax.eval_shape(lambda: family.make_params(
        weights.seed_key(1), dims, jnp.bfloat16))
    assert sum(leaf.size for leaf in jax.tree.leaves(tree)) == total
    assert dims.attention_layers == (5, 15, 25, 35) and dims.layers == 40
    assert dims.state_bytes_per_slot() == 36 * (2097152 + 26112) == 76437504
    assert dims.kv_bytes_per_position() == 8192
    assert config['reduced'] == [] and config['vocab_size'] == 100352
    # Every number of the catalog's row stands in the file under its key.
    import json
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if os.path.exists(catalog):
        with open(catalog, encoding='utf-8') as f:
            row = next(r for r in map(json.loads, f)
                       if r['name'] == CONFIG_FILE)
        assert {k: config[k] for k in row['config']} == row['config']
        assert config['source'] == row['source_url']
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
