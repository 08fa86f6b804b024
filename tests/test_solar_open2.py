"""Solar-Open2 on the serving path, at a tiny size on the CPU, against the
plain reference (benchmarks/reference/solar_open2_ref.py): a layer pattern
of one softmax and three linear-attention layers whose recurrent state
lives in the engine's cache beside K and V, and a dropless expert layer
that holds a share of the experts.

Sizes: hidden 64, 4 heads of 16, 16 experts of width 32 with 4 held and 2
a token, vocabulary 256, 4 layers in the pattern; float32 weights from the
family's seed, so that the program and the reference differ by rounding
order only.
"""
import copy
import functools
import os
import sys

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import families  # noqa: E402
from benchmarks.harness import check, manifest, weights  # noqa: E402
from benchmarks.reference import solar_open2_ref  # noqa: E402
from skypilot_tpu.inference.engine import DecodeEngine, EngineConfig  # noqa: E402
from skypilot_tpu.models import moe as moe_lib  # noqa: E402
from skypilot_tpu.models import solar_open2 as solar_lib  # noqa: E402
from skypilot_tpu.models.solar_open2 import (SolarOpen2Config,  # noqa: E402
                                             delta_rule_step,
                                             kda_step_heads)
from skypilot_tpu.ops.pallas import delta_rule_step as pallas_dr  # noqa: E402

SEED = 2**31 + 30
DTYPE = jnp.float32
CONFIG_FILE = 'solar-open2-250b-ep8'


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
def wide(tiny):
    """`tiny` with the linear layers' heads at the published 128, the
    size the decode kernel's tiling takes."""
    family, _, config = tiny
    config = copy.deepcopy(config)
    config['linear_attn_config']['head_dim'] = 128
    return family, family.dims(config), config


@pytest.fixture(scope='module')
def kda_kernel_forced():
    """The KDA decode kernel wherever its shapes allow, in interpret mode,
    for the rest of the module: `jax.default_backend()` is the CPU here,
    so the tests steer the choice themselves (the rule's own cases hold
    the function they imported).  Heads of 16 still go through XLA."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(
            solar_lib, 'kda_step_heads',
            lambda state, positions, mesh=None: None if positions != 1
            else pallas_dr.block_heads(*state.shape[1:]))
        patch.setattr(
            pallas_dr, 'delta_rule_step_fwd',
            functools.partial(pallas_dr.delta_rule_step_fwd, interpret=True))
        yield


@pytest.fixture(scope='module')
def served_by_kernel(wide, kda_kernel_forced):
    """`served` at heads of 128 with the kernel in every decode step."""
    return serve(wide)


@pytest.fixture(scope='module')
def served(tiny):
    """An engine over the seeded weights, and what it answered to prompts
    of every path: alone in a bucket, three of different lengths admitted
    as one padded group, and one longer than the largest bucket."""
    return serve(tiny)


def serve(tiny):
    family, dims, config = tiny
    model = family.serve_model(dims, config, DTYPE)
    params = jax.jit(lambda k: family.make_params(k, dims, DTYPE))(
        weights.seed_key(SEED))
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
    return engine, model, params, answers


def gap_of(tiny, samples):
    """How far the served tokens lie below the reference's best."""
    family, dims, _ = tiny
    return check.served_gap(family, dims, SEED, DTYPE, samples, (64, 6))


STEP_PATHS = {'xla': ('tiny', 'served'),
              'kernel': ('wide', 'served_by_kernel')}


@pytest.mark.parametrize('step', list(STEP_PATHS))
@pytest.mark.parametrize('path', ['alone', 'group', 'chunked'])
def test_served_tokens_are_the_references(request, path, step):
    """(a) prefill then decode through DecodeEngine, (b) a padded group of
    different lengths, (c) a chunked prefill: every served token is the
    reference's own choice, up to float32 rounding; with the decode step's
    state update through XLA and through the kernel."""
    tiny, served = map(request.getfixturevalue, STEP_PATHS[step])
    samples = served[3][path]
    assert all(len(tokens) == 6 for _, tokens in samples)
    verdict = gap_of(tiny, samples)
    assert verdict['finite'] and verdict['positions'] == 6 * len(samples)
    assert verdict['widest_gap'] < 1e-3, verdict


@pytest.mark.parametrize('step', list(STEP_PATHS))
def test_padding_does_not_reach_the_state(request, step):
    """One padded prefill of rows of different lengths: the logits at each
    row's last valid position, and the first decode step after it, are the
    reference's for the unpadded row; with the lengths left out (padding
    folded into the state) they are not."""
    tiny, served = map(request.getfixturevalue, STEP_PATHS[step])
    family, dims, _ = tiny
    _, model, params, _ = served
    rng = np.random.default_rng(5)
    lengths = np.array([16, 5, 11, 2])
    rows = rng.integers(0, dims.vocab, (4, 17))
    ref = family.reference(dims, SEED, DTYPE)
    want = np.asarray(ref.logits_at(ref.hidden(jnp.asarray(rows))))
    padded = np.where(np.arange(16)[None, :] < lengths[:, None],
                      rows[:, :16], 0)

    def prefill_then_step(told):
        logits, cache = model.apply(
            {'params': params}, jnp.asarray(padded), decode=True,
            lengths=told, mutable=['cache'])
        nxt = rows[np.arange(4), lengths]
        step, _ = model.apply(
            {'params': params, 'cache': cache['cache']},
            jnp.asarray(nxt)[:, None],
            positions=jnp.asarray(lengths)[:, None], decode=True,
            mutable=['cache'])
        return np.asarray(logits), np.asarray(step[:, 0])

    last, step = prefill_then_step(jnp.asarray(lengths))
    assert last.shape == (4, 1, dims.vocab)
    for i, n in enumerate(lengths):
        np.testing.assert_allclose(last[i, 0], want[i, n - 1], atol=2e-4)
        np.testing.assert_allclose(step[i], want[i, n], atol=2e-4)
    _, wrong = prefill_then_step(None)
    assert np.abs(wrong[0] - want[0, 16]).max() < 2e-4      # no padding
    assert np.abs(wrong[1] - want[1, 5]).max() > 1e-2       # 11 padded


def test_chunked_prefill_carries_the_state_from_zero(tiny, served):
    """(c) a prompt of 37 through the engine's own chunk programs (16, 16,
    then 5 padded to 8): the logits at its last position, the token the
    insert sampled and a decode step from the slot it filled are the
    reference's full forward.  The scratch starts from zeros: a state
    that had folded in a traced dummy token would show here."""
    family, dims, _ = tiny
    engine, model, params, _ = served
    assert all(slot is None for slot in engine._slots)
    rows = np.random.default_rng(7).integers(0, dims.vocab, (1, 38))
    ref = family.reference(dims, SEED, DTYPE)
    want = np.asarray(ref.logits_at(ref.hidden(jnp.asarray(rows))))[0]
    scratch = engine._new_scratch()
    assert all(not np.asarray(leaf).any() for leaf in jax.tree.leaves(scratch))
    for offset in (0, 16):
        scratch = engine._chunk_for(16)(
            params, scratch, jnp.asarray(rows[:, offset:offset + 16]),
            jnp.asarray(offset, jnp.int32))
    last = np.zeros((1, 8), np.int32)
    last[0, :5] = rows[0, 32:37]
    logits, _ = model.apply(
        {'params': params, 'cache': scratch}, jnp.asarray(last),
        positions=32 + jnp.arange(8)[None, :], decode=True,
        lengths=jnp.asarray([5]), mutable=['cache'])
    np.testing.assert_allclose(np.asarray(logits)[0, 0], want[36], atol=2e-4)
    slot = 2
    engine._cache, engine._last_d, engine._lens_d = engine._chunk_insert_for(
        8)(params, engine._cache, engine._last_d, engine._lens_d, scratch,
           jnp.asarray(last), jnp.asarray(5, jnp.int32),
           jnp.asarray(32, jnp.int32), jnp.asarray(37, jnp.int32),
           jnp.asarray(slot, jnp.int32), engine._next_rng())
    assert int(engine._last_d[slot]) == want[36].argmax()
    assert int(engine._lens_d[slot]) == 37
    step, _ = model.apply(
        {'params': params,
         'cache': jax.tree.map(lambda a: a[slot:slot + 1], engine._cache)},
        jnp.asarray(rows[:, 37:38]), positions=jnp.asarray([[37]]),
        decode=True, mutable=['cache'])
    np.testing.assert_allclose(np.asarray(step)[0, 0], want[37], atol=2e-4)


def moe_layer(held, n_shared=1, block=16):
    return moe_lib.DroplessMoE(
        dim=64, ffn_dim=32, n_experts=16, held=tuple(held),
        router=moe_lib.LinearRouter(top_k=2),
        n_shared=n_shared, dtype=DTYPE, param_dtype=DTYPE, block=block)


@pytest.fixture(scope='module')
def moe_weights():
    """A whole layer's weights: 16 experts, a shared one."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 64), DTYPE)
    params = moe_layer(range(16)).init(jax.random.PRNGKey(2), x)['params']
    return x, params


def share_of(params, held, with_shared):
    held = list(held)
    out = {'router': params['router'],
           **{k: params[k][jnp.asarray(held)]
              for k in ('w_gate', 'w_up', 'w_down')}}
    if with_shared:
        out.update({k: v for k, v in params.items() if 'shared' in k})
    return out


def test_no_token_is_dropped_when_all_pick_one_expert(moe_weights):
    """(d) every token's first choice is expert 3: it takes all 80 tokens,
    five blocks of 16, and the layer is still the dense sum."""
    x, params = moe_weights
    x = jnp.abs(x)
    router = np.array(params['router'])
    router[:, 3] = 1.0                # scores 1 on positive inputs
    router[:, 5] = 0.5
    params = dict(params, router=jnp.asarray(router))
    held = share_of(params, (2, 3, 4, 5), True)
    out, stats = moe_layer((2, 3, 4, 5)).apply({'params': held}, x,
                                               mutable=['stats'])
    counts = np.asarray(stats['stats']['expert_tokens'][0])
    assert counts.tolist() == [0, 80, 0, 80, 0]
    assert int(stats['stats']['touched'][0]) == 2
    with jax.default_matmul_precision('highest'):
        want = solar_open2_ref.expert_layer(
            held, x, held=(2, 3, 4, 5), top_k=2, scaling=1.0,
            matmul=solar_open2_ref.plain_matmul)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)


def test_the_shares_add_up_to_the_uncut_layer(moe_weights):
    """(e) four shares of four experts, the shared expert counted once,
    give what the uncut reference gives for the whole layer; a share's
    counts say how many pairs went elsewhere."""
    x, params = moe_weights
    total, held_pairs = 0.0, 0
    for j in range(4):
        held = range(4 * j, 4 * j + 4)
        out, stats = moe_layer(held, n_shared=int(j == 0)).apply(
            {'params': share_of(params, held, j == 0)}, x,
            mutable=['stats'])
        counts = np.asarray(stats['stats']['expert_tokens'][0])
        assert counts.sum() == 80 * 2
        held_pairs += counts[:4].sum()
        total = total + out
    assert held_pairs == 80 * 2
    with jax.default_matmul_precision('highest'):
        want = solar_open2_ref.expert_layer(
            params, x, held=tuple(range(16)), top_k=2, scaling=1.0,
            matmul=solar_open2_ref.plain_matmul)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-4)
    whole = moe_layer(range(16)).apply({'params': params}, x)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                               atol=1e-4)


def test_a_decode_steps_few_tokens_go_through_the_same_loop(moe_weights):
    """A decode step's 24 tokens are one block of the expert loop: the sum
    is the reference's and the counts are the pairs of each held expert."""
    x, params = moe_weights
    few = x[:, :12]                                  # 24 tokens
    ids = (2, 3, 4, 5, 9)
    held = share_of(params, ids, True)
    out, stats = moe_lib.DroplessMoE(
        dim=64, ffn_dim=32, n_experts=16, held=ids,
        router=moe_lib.LinearRouter(top_k=2), dtype=DTYPE,
        param_dtype=DTYPE).apply({'params': held}, few, mutable=['stats'])
    with jax.default_matmul_precision('highest'):
        want = solar_open2_ref.expert_layer(
            held, few, held=ids, top_k=2, scaling=1.0,
            matmul=solar_open2_ref.plain_matmul)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)
    counts = np.asarray(stats['stats']['expert_tokens'][0])
    assert counts.sum() == 24 * 2
    assert int(stats['stats']['touched'][0]) == (counts[:5] > 0).sum()


@pytest.fixture
def kernel_forced(monkeypatch):
    """The decode kernel wherever its shapes allow, in interpret mode:
    `jax.default_backend()` is the CPU here, so the test steers the
    choice itself (the rule's own cases are below)."""
    from skypilot_tpu.ops.pallas import grouped_experts as pallas_ge
    monkeypatch.setattr(
        moe_lib, 'expert_tile',
        lambda n_tokens, block, w_gate, mesh=None: None
        if n_tokens > block else pallas_ge.tile_f(
            w_gate.shape[1], w_gate.shape[2], w_gate.dtype.itemsize))
    monkeypatch.setattr(
        pallas_ge, 'grouped_experts_fwd',
        functools.partial(pallas_ge.grouped_experts_fwd, interpret=True))


# case: (tokens, held ids, the two experts every token is made to pick
# or None, dtype, pairs of each held expert or None, atol)
KERNEL_CASES = {
    'all_pick_one_held_expert':
        (24, (2, 3, 4, 5), (3, 12), jnp.float32, [0, 24, 0, 0], 1e-4),
    'none_reaches_a_held_expert':
        (24, (2, 3, 4, 5), (10, 12), jnp.float32, [0, 0, 0, 0], 1e-4),
    'reached_rows_not_first_and_with_gaps':
        (24, (0, 1, 2, 3, 4, 5, 6, 9), (3, 9), jnp.float32,
         [0, 0, 0, 24, 0, 0, 0, 24], 1e-4),
    'tokens_not_a_multiple_of_8': (21, (2, 3, 4, 5, 9), None, jnp.float32,
                                   None, 1e-4),
    'tokens_fill_the_block': (16, (2, 3, 4, 5, 9), None, jnp.float32, None,
                              1e-4),
    'bf16_as_served': (32, (2, 3, 4, 5, 9), None, jnp.bfloat16, None, 2e-2),
}


@pytest.mark.parametrize('case', list(KERNEL_CASES))
def test_the_decode_kernel_sums_what_the_reference_sums(kernel_forced, case):
    """A decode step's tokens through the grouped kernel (interpret mode):
    the layer is the reference's at `highest`, the counts are the pairs of
    each held expert, and every reached expert was the kernel's."""
    n_tokens, ids, picks, dtype, pairs, atol = KERNEL_CASES[case]
    layer = moe_lib.DroplessMoE(
        dim=128, ffn_dim=256, n_experts=16, held=ids,
        router=moe_lib.LinearRouter(top_k=2), dtype=dtype,
        param_dtype=dtype, block=16 if case == 'tokens_fill_the_block'
        else 256)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (1, n_tokens, 128),
                                  dtype))
    params = layer.init(jax.random.PRNGKey(4), x)['params']
    if picks:
        router = np.array(params['router'])
        router[:, picks[0]] = 1.0             # scores 1 on positive inputs
        router[:, picks[1]] = 0.5
        params = dict(params, router=jnp.asarray(router))
    out, stats = layer.apply({'params': params}, x, mutable=['stats'])
    counts = np.asarray(stats['stats']['expert_tokens'][0])
    assert counts.sum() == n_tokens * 2
    if pairs is not None:
        assert counts[:-1].tolist() == pairs
    touched = int((counts[:-1] > 0).sum())
    assert int(stats['stats']['touched'][0]) == touched
    assert int(stats['stats']['kernel_trips'][0]) == touched
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision('highest'):
        want = solar_open2_ref.expert_layer(
            f32, x.astype(jnp.float32), held=ids, top_k=2, scaling=1.0,
            matmul=solar_open2_ref.plain_matmul)
        if case == 'none_reaches_a_held_expert':
            shared = solar_open2_ref.swiglu(
                x[0], f32['shared_gate']['kernel'], f32['shared_up']['kernel'],
                f32['shared_down']['kernel'], solar_open2_ref.plain_matmul)
            np.testing.assert_allclose(np.asarray(want)[0],
                                       np.asarray(shared), atol=1e-6)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want),
                               atol=atol)


@pytest.mark.parametrize('why', ['more_tokens_than_a_block', 'the_cpu',
                                 'a_two_device_mesh'])
def test_the_rule_sends_everything_else_to_the_block_loop(monkeypatch, why):
    """`expert_tile` engages the kernel on one TPU device for bf16 stacks
    and no more tokens than a block; T > block, the CPU and a mesh of two
    devices go through the loop (`kernel_trips` 0)."""
    from jax.sharding import Mesh
    w_gate = jax.ShapeDtypeStruct((4, 128, 256), jnp.bfloat16)
    if why != 'the_cpu':
        monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
        assert moe_lib.expert_tile(32, 32, w_gate) == 256
        assert moe_lib.expert_tile(32, 32, jax.ShapeDtypeStruct(
            (4, 128, 256), jnp.float32)) is None
        assert moe_lib.expert_tile(32, 32, jax.ShapeDtypeStruct(
            (4, 128, 192), jnp.bfloat16)) is None
    mesh = (Mesh(np.array(jax.devices()[:2]), ('expert',))
            if why == 'a_two_device_mesh' else None)
    n_tokens, block = (40, 16) if why == 'more_tokens_than_a_block' \
        else (16, 16)
    assert moe_lib.expert_tile(n_tokens, block, w_gate, mesh) is None
    layer = moe_lib.DroplessMoE(
        dim=128, ffn_dim=256, n_experts=16, held=(2, 3, 4, 5),
        router=moe_lib.LinearRouter(top_k=2),
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, block=block, mesh=mesh)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, n_tokens, 128),
                          jnp.bfloat16)
    params = layer.init(jax.random.PRNGKey(6), x)['params']
    _, stats = layer.apply({'params': params}, x, mutable=['stats'])
    assert int(stats['stats']['kernel_trips'][0]) == 0
    assert int(stats['stats']['touched'][0]) > 0


def test_the_stacks_reach_the_kernel_as_they_are_stored(kernel_forced):
    """A decode-shaped call with the kernel in it: the three expert stacks
    that enter the `pallas_call` are the layer's parameters themselves (no
    transpose, convert, slice or gather of a stack stands between), so the
    program keeps one copy of them in the layout they are stored in."""
    layer = moe_lib.DroplessMoE(
        dim=128, ffn_dim=256, n_experts=16, held=(2, 3, 4, 5),
        router=moe_lib.LinearRouter(top_k=2),
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    x = jnp.zeros((32, 1, 128), jnp.bfloat16)
    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0),
                                               x)['params'])
    closed = jax.make_jaxpr(
        lambda params, x: layer.apply({'params': params}, x))(params, x)
    names = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    stored = dict(zip(closed.jaxpr.invars, names))

    def calls(jaxpr, outer):
        """(pallas_call equation, its operands as variables of the
        outermost jaxpr or None) under `jaxpr`."""
        for eqn in jaxpr.eqns:
            args = [outer.get(v) if isinstance(v, jax.extend.core.Var) else None
                    for v in eqn.invars]
            if eqn.primitive.name == 'pallas_call':
                yield eqn, args
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub, dict(zip(sub.invars, args)))

    found = list(calls(closed.jaxpr, {v: v for v in closed.jaxpr.invars}))
    assert len(found) == 1
    eqn, args = found[0]
    assert [stored.get(v) for v in args[-3:]] == [
        "['w_gate']", "['w_up']", "['w_down']"]
    assert [v.aval.shape for v in eqn.invars[-3:]] == [
        (4, 128, 256), (4, 128, 256), (4, 256, 128)]


def test_the_trips_counter_says_who_multiplied(monkeypatch):
    """`publish_routing` splits the experts a fetch reached by who
    multiplied them, and the yardstick's reader gives the kernel's share:
    nothing for a program without the counter, 0 where every step fell
    back to the loop."""
    from benchmarks.harness import reducers
    from skypilot_tpu.server import metrics as metrics_lib

    def trips():
        return {path: float(line.rpartition(' ')[2])
                for line in metrics_lib.render().splitlines()
                for path in ('kernel', 'loop')
                if line.startswith(
                    f'skytpu_moe_expert_trips_total{{path="{path}"}}')}

    before = trips()
    counts = np.array([3, 0, 5, 24])
    moe_lib.publish_routing((2, 3, 4), counts, 2, 0)
    moe_lib.publish_routing((2, 3, 4), counts * 3, 6, 6)
    after = trips()
    assert after['kernel'] - before.get('kernel', 0.0) == 6
    assert after['loop'] - before.get('loop', 0.0) == 2

    def read(text):
        monkeypatch.setattr(metrics_lib, 'render', lambda: text)
        return reducers.reduce_metric('moe_kernel_trips_pct', {})

    assert read('skytpu_moe_experts_touched_total 8\n') is None
    assert read('skytpu_moe_expert_trips_total{path="kernel"} 0\n'
                'skytpu_moe_expert_trips_total{path="loop"} 716\n') == 0.0
    assert read('skytpu_moe_expert_trips_total{path="kernel"} 6\n'
                'skytpu_moe_expert_trips_total{path="loop"} 2\n') == 75.0


def kda_step_inputs(seed=0, slots=2, heads=4, size=128):
    """(state, q, k, v, a, beta) as a decode step's KDA layer meets them:
    unit q and k, decays in (0, 1), beta in (0, 2)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    shape = (slots, heads, size)
    return (0.1 * jax.random.normal(keys[0], shape + (size,), jnp.float32),
            unit(jax.random.normal(keys[1], shape, jnp.float32)),
            unit(jax.random.normal(keys[2], shape, jnp.float32)),
            jax.random.normal(keys[3], shape, jnp.float32),
            -jnp.exp(jax.random.normal(keys[4], shape, jnp.float32) - 2.0),
            2.0 * jax.nn.sigmoid(jax.random.normal(keys[5], shape[:2],
                                                   jnp.float32)))


@pytest.mark.parametrize('case', [
    'a_plain_step', 'a_padded_row_keeps_its_state_bit_for_bit',
    'beta_two_is_a_reflection', 'a_channel_that_forgets_fast',
    'three_steps_through_the_aliased_state'])
def test_the_state_kernel_is_delta_rule_step(case):
    """The decode kernel (interpret mode) against `delta_rule_step` on
    seeded float32 inputs, 2 slots x 4 heads of 128 x 128: the output and
    the new state agree to rounding, and a row with a = 0, beta = 0 (a
    padded position, an empty slot) keeps its state to the bit."""
    kernel = jax.jit(functools.partial(pallas_dr.delta_rule_step_fwd,
                                       interpret=True), donate_argnums=0)
    state, q, k, v, a, beta = kda_step_inputs()
    steps = 3 if case == 'three_steps_through_the_aliased_state' else 1
    if case == 'a_padded_row_keeps_its_state_bit_for_bit':
        a, beta = a.at[1].set(0.0), beta.at[1].set(0.0)
    elif case == 'beta_two_is_a_reflection':
        beta = jnp.full_like(beta, 2.0)
    elif case == 'a_channel_that_forgets_fast':
        a = a.at[:, :, ::5].set(-30.0)
    inputs = [(q, k, v, a, beta)] + [kda_step_inputs(seed=step)[1:]
                                     for step in range(1, steps)]
    before = np.asarray(state)
    want, got = state, jnp.array(state)
    for step in inputs:
        want_o, want = delta_rule_step(want, *step)
        got_o, got = kernel(got, *step)
        assert got_o.dtype == got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                                   atol=2e-6, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6, rtol=1e-5)
    assert np.abs(np.asarray(got) - before).max() > 1e-2
    if case == 'a_padded_row_keeps_its_state_bit_for_bit':
        np.testing.assert_array_equal(np.asarray(got)[1], before[1])
        np.testing.assert_array_equal(np.asarray(want)[1], before[1])


@pytest.mark.parametrize('why', ['the_cpu', 'a_two_device_mesh',
                                 'more_than_one_position',
                                 'a_head_size_that_is_no_multiple_of_128'])
def test_the_rule_sends_everything_else_to_delta_rule_step(monkeypatch, why):
    """`kda_step_heads` engages the kernel on one TPU device for one
    position against a float32 state whose head sizes are multiples of
    128; the CPU, a mesh of two devices, a prefill's positions and other
    head sizes keep XLA's `delta_rule_step` / `chunk_delta_rule`, and the
    layer's jaxpr then holds no `pallas_call`."""
    from jax.sharding import Mesh
    # The rule itself, whatever a module fixture has put in its place.
    monkeypatch.setattr(solar_lib, 'kda_step_heads', kda_step_heads)
    state = jax.ShapeDtypeStruct((2, 64, 128, 128), jnp.float32)
    if why != 'the_cpu':
        monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
        assert kda_step_heads(state, 1) == pallas_dr.block_heads(
            64, 128, 128) <= 32
        assert kda_step_heads(state, 1, Mesh(
            np.array(jax.devices()[:1]), ('expert',))) is not None
        assert kda_step_heads(jax.ShapeDtypeStruct(
            state.shape, jnp.bfloat16), 1) is None
    mesh = (Mesh(np.array(jax.devices()[:2]), ('expert',))
            if why == 'a_two_device_mesh' else None)
    positions = 8 if why == 'more_than_one_position' else 1
    size = 64 if why.startswith('a_head_size') else 128
    state = jax.ShapeDtypeStruct((2, 4, size, size), jnp.float32)
    assert kda_step_heads(state, positions, mesh) is None
    cfg = SolarOpen2Config(
        vocab_size=64, dim=64, n_layers=1, gqa_layers=(), kda_heads=4,
        kda_head_dim=size, kda_rank=8, n_experts=4, held_experts=(0, 1),
        experts_per_token=2, expert_dim=32, dtype=DTYPE, param_dtype=DTYPE)
    layer = solar_lib.KimiDeltaAttention(cfg, mesh)
    x = jnp.zeros((2, positions, 64), DTYPE)
    variables = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), x, True, None))
    text = str(jax.make_jaxpr(lambda v, x: layer.apply(
        v, x, True, None, mutable=['cache']))(variables, x))
    assert 'pallas_call' not in text


def test_the_updates_counter_says_who_updated(tiny, served, monkeypatch):
    """`publish_stats` counts the KDA head-states a decode call updated
    (slots x KDA layers x heads x steps) under the path its program took,
    and the yardstick's reader gives the kernel's share: nothing for a
    program without the counter, 0 where every step went through XLA."""
    from benchmarks.harness import reducers
    from skypilot_tpu.server import metrics as metrics_lib
    _, model, _, _ = served
    cfg = model.cfg

    def updates():
        return {path: float(line.rpartition(' ')[2])
                for line in metrics_lib.render().splitlines()
                for path in ('kernel', 'xla')
                if line.startswith(
                    f'skytpu_kda_state_updates_total{{path="{path}"}}')}

    def stats_of(slots, steps):
        pairs = np.zeros(len(cfg.held_experts) + 1, np.int64)
        pairs[-1] = slots * steps * cfg.experts_per_token
        return {f'layer_{i}': {'moe': {
            'expert_tokens': (pairs,), 'touched': (np.int64(0),),
            'kernel_trips': (np.int64(0),)}} for i in range(cfg.n_layers)}

    kda_layers = cfg.n_layers - len(cfg.gqa_layers)
    assert (kda_layers, cfg.kda_heads) == (3, 4)
    before = updates()
    model.served().publish_stats(stats_of(4, 3))        # the CPU: XLA
    monkeypatch.setattr(solar_lib, 'kda_step_heads', lambda *_: 4)
    model.served().publish_stats(stats_of(4, 8))
    after = updates()
    assert after['xla'] - before.get('xla', 0.0) == 4 * 3 * 3 * 4
    assert after['kernel'] - before.get('kernel', 0.0) == 4 * 3 * 4 * 8

    def read(text):
        monkeypatch.setattr(metrics_lib, 'render', lambda: text)
        return reducers.reduce_metric('kda_kernel_updates_pct', {})

    assert read('skytpu_moe_experts_touched_total 8\n') is None
    assert read('skytpu_kda_state_updates_total{path="kernel"} 0\n'
                'skytpu_kda_state_updates_total{path="xla"} 6144\n') == 0.0
    assert read('skytpu_kda_state_updates_total{path="kernel"} 6144\n'
                'skytpu_kda_state_updates_total{path="xla"} 0\n') == 100.0
    assert read('skytpu_kda_state_updates_total{path="kernel"} 3\n'
                'skytpu_kda_state_updates_total{path="xla"} 1\n') == 75.0


def test_held_parameters_are_the_files_arithmetic_and_the_programs_tree(
        tiny):
    """(f) the configuration file's total, its arithmetic worked out
    here, the family's count, the program's count and the seeded tree."""
    config = published_config()
    family = families.load(config)
    dims = family.dims(config)
    kda = (4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64 +
           3 * 8192 * 4 + 64 + 8192 + 128)
    softmax = 3 * 4096 * 8192 + 2 * 4096 * 1024
    besides = 4096 * 320 + 41 * 3 * 4096 * 1280 + 2 * 4096
    total = (softmax + besides) + 3 * (kda + besides) + \
        2 * 24576 * 4096 + 4096
    assert (kda, softmax) == (137732288, 109051904)
    assert total == config['params_total'] == dims.num_params()
    model = family.serve_model(dims, config, jnp.bfloat16)
    assert model.cfg.num_params() == total
    tree = jax.eval_shape(lambda: family.make_params(
        weights.seed_key(1), dims, jnp.bfloat16))
    assert sum(leaf.size for leaf in jax.tree.leaves(tree)) == total
    assert dims.state_bytes_per_slot() == 3 * (64 * 128 * 128 * 4 +
                                               3 * 3 * 8192 * 2)
    assert (config['published'], config['reduced']) == (
        {'num_hidden_layers': 48, 'n_routed_experts': 320,
         'vocab_size': 196608},
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
    assert isinstance(model.cfg, SolarOpen2Config)
    assert model.cfg.num_params() == dims.num_params()


def test_paging_speculation_and_transfer_are_refused(tiny, served):
    """(g) two kinds of state in the page manager is a later PR: refused
    at construction with the reason, never a silent fall-back."""
    engine, model, params, _ = served
    for options in (dict(kv_page_size=8),
                    dict(kv_page_size=8, speculation=2)):
        with pytest.raises(ValueError, match='keeps recurrent state beside '
                           'its keys and values.*KV transfer'):
            DecodeEngine(model, params, EngineConfig(
                n_slots=2, prefill_buckets=(8, 16), **options))
    with pytest.raises(RuntimeError, match='requires the paged KV cache'):
        engine.submit_prefill([1, 2, 3])


def test_cache_and_cost_model_carry_two_kinds_of_state(tiny, served):
    """The engine's cache holds K and V of the one softmax layer and the
    state and taps of three linear layers, and the cost model reads its
    bytes from those leaves."""
    _, dims, _ = tiny
    engine = served[0]
    shapes = {'/'.join(str(getattr(p, 'key', p)) for p in path): leaf.shape
              for path, leaf in jax.tree_util.tree_flatten_with_path(
                  engine._cache)[0]}
    assert shapes['layer_0/attn/k'] == (4, 2, 64, 16)
    assert shapes['layer_2/kda/state'] == (4, 4, 16, 16)
    assert shapes['layer_3/kda/conv'] == (4, 3, 3, 4, 16)
    cm = engine.perf_cost_model
    assert cm.n_kv_layers == 1 and cm.n_layers == 4
    assert cm.kv_bytes_per_pos() == dims.kv_bytes_per_position(4)
    assert cm.state_bytes_per_slot == 3 * (4 * 16 * 16 * 4 + 3 * 3 * 64 * 4)
    assert cm.decode_hbm_bytes_per_token(10, 2) == (
        cm.param_bytes / 2 + 11 * cm.kv_bytes_per_pos() +
        2 * cm.state_bytes_per_slot)
    from skypilot_tpu.server import metrics as metrics_lib
    text = metrics_lib.render()
    assert 'skytpu_engine_cache_bytes{kind="recurrent"}' in text
    assert 'skytpu_moe_pairs_total{where="held"}' in text
