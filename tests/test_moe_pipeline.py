"""MoE (expert parallelism) + GPipe pipeline tests on the virtual CPU
mesh — the §2.15 greenfield rows the reference only reaches via recipe
flags."""
import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models.llama import LLAMA_CONFIGS, Llama, init_params
from skypilot_tpu.models import moe as moe_lib
from skypilot_tpu.models.moe import MoEMLP, top_k_dispatch
from skypilot_tpu.parallel.mesh import MeshPlan, build_mesh, plan_mesh
from skypilot_tpu.parallel import pipeline as pipeline_lib


# ----- routing ---------------------------------------------------------------
def test_top_k_dispatch_selects_and_renormalizes():
    probs = jnp.array([[[0.5, 0.3, 0.2],
                        [0.1, 0.2, 0.7]]], jnp.float32)   # [1, 2, 3]
    dispatch, combine = top_k_dispatch(probs, top_k=2, capacity=2)
    # token 0 -> experts 0,1; token 1 -> experts 2,1
    assert float(dispatch[0, 0, 0].sum()) == 1.0
    assert float(dispatch[0, 0, 1].sum()) == 1.0
    assert float(dispatch[0, 0, 2].sum()) == 0.0
    assert float(dispatch[0, 1, 2].sum()) == 1.0
    # gates renormalize over the selected pair
    np.testing.assert_allclose(float(combine[0, 0].sum()), 1.0, rtol=1e-5)
    np.testing.assert_allclose(float(combine[0, 1].sum()), 1.0, rtol=1e-5)


def test_top_k_dispatch_capacity_drops():
    # Every token prefers expert 0; capacity 1 keeps only the first.
    probs = jnp.tile(jnp.array([[[0.9, 0.1]]], jnp.float32), (1, 4, 1))
    dispatch, _ = top_k_dispatch(probs, top_k=1, capacity=1)
    per_token = dispatch[0, :, 0].sum(-1)
    np.testing.assert_allclose(np.asarray(per_token), [1, 0, 0, 0])


# ----- MoE layer correctness -------------------------------------------------
def _naive_moe(layer, params, x, top_k):
    """Per-token reference: weighted sum of selected experts' SwiGLU."""
    import flax.linen as nn
    p = nn.meta.unbox(params)['params']
    logits = x.astype(jnp.float32) @ p['router']['kernel']
    probs = jax.nn.softmax(logits, axis=-1)
    wg, wu, wd = p['w_gate'], p['w_up'], p['w_down']
    out = np.zeros_like(np.asarray(x), dtype=np.float32)
    b, s, _ = x.shape
    for bi in range(b):
        for si in range(s):
            pr = np.asarray(probs[bi, si])
            top = np.argsort(-pr)[:top_k]
            gates = pr[top] / pr[top].sum()
            for g, e in zip(gates, top):
                h = (jax.nn.silu(x[bi, si] @ wg[e]) * (x[bi, si] @ wu[e]))
                out[bi, si] += g * np.asarray(h @ wd[e], np.float32)
    return out


def test_moe_layer_matches_naive_reference():
    layer = MoEMLP(dim=16, ffn_dim=32, n_experts=4, top_k=2,
                   capacity_factor=8.0,        # ample: nothing drops
                   dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 16))
    params = layer.init(jax.random.PRNGKey(1), x)
    out = layer.apply(params, x)
    ref = _naive_moe(layer, params, x, top_k=2)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)


def test_moe_aux_loss_sown():
    layer = MoEMLP(dim=8, ffn_dim=16, n_experts=2, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 8))
    params = layer.init(jax.random.PRNGKey(1), x)
    _, inter = layer.apply(params, x, mutable=['intermediates'])
    (aux,) = inter['intermediates']['moe_aux_loss']
    assert float(aux) >= 1.0 - 1e-5   # >= 1 at perfect balance


# ----- MoE llama under expert-parallel mesh ----------------------------------
def test_moe_llama_trains_expert_parallel():
    from skypilot_tpu.train.trainer import (TrainConfig,
                                            make_sharded_train_step,
                                            make_train_state)
    cfg = dataclasses.replace(
        LLAMA_CONFIGS['tiny'], n_experts=4, moe_capacity_factor=4.0)
    mesh = build_mesh(plan_mesh(8, expert=4, fsdp=1, data=2))
    model = Llama(cfg, mesh)
    rng = jax.random.PRNGKey(0)
    tokens = jax.random.randint(rng, (8, 32), 0, cfg.vocab_size)
    state, shardings = make_train_state(
        model, mesh, rng, tokens,
        TrainConfig(learning_rate=1e-2, warmup_steps=1, total_steps=50))
    # expert weights really shard over the expert axis
    moe_kernel = state.params['layer_0']['moe_mlp']['w_gate']
    spec = moe_kernel.sharding.spec
    assert spec[0] == 'expert'
    step = make_sharded_train_step(mesh, shardings)
    losses = []
    for _ in range(6):
        state, metrics = step(state, tokens)
        losses.append(float(metrics['loss']))
    assert losses[-1] < losses[0]


def test_moe_llama_decode_matches_full_forward():
    cfg = dataclasses.replace(
        LLAMA_CONFIGS['tiny'], n_experts=2, moe_capacity_factor=8.0)
    model = Llama(cfg)
    variables = init_params(model, jax.random.PRNGKey(0), batch=1, seq=8)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                                cfg.vocab_size)
    full = model.apply(variables, tokens)
    logits, cache = model.apply(variables, tokens[:, :4], decode=True,
                                mutable=['cache'])
    np.testing.assert_allclose(np.asarray(logits[0, -1]),
                               np.asarray(full[0, 3]), rtol=1e-3,
                               atol=1e-3)


# ----- pipeline --------------------------------------------------------------
def _mlp_stage(params, x):
    return jnp.tanh(x @ params['w'] + params['b'])


def _make_stage_params(n_stages, d, key):
    out = []
    for i in range(n_stages):
        k1, k2, key = jax.random.split(key, 3)
        out.append({'w': jax.random.normal(k1, (d, d)) / np.sqrt(d),
                    'b': jax.random.normal(k2, (d,)) * 0.1})
    return out


@pytest.mark.parametrize('n_micro', [4, 8])
def test_pipeline_matches_sequential(n_micro):
    mesh = build_mesh(plan_mesh(8, pipeline=4, fsdp=2))
    d = 16
    per_stage = _make_stage_params(4, d, jax.random.PRNGKey(0))
    stacked = pipeline_lib.stack_stage_params(per_stage)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, d))
    got = pipeline_lib.pipeline_apply(_mlp_stage, stacked, x, mesh=mesh,
                                      n_microbatches=n_micro)
    want = x
    for p in per_stage:
        want = _mlp_stage(p, want)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_differentiable():
    mesh = build_mesh(plan_mesh(8, pipeline=4, fsdp=2))
    d = 8
    per_stage = _make_stage_params(4, d, jax.random.PRNGKey(0))
    stacked = pipeline_lib.stack_stage_params(per_stage)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, d))

    def loss_pipe(params):
        return (pipeline_lib.pipeline_apply(
            _mlp_stage, params, x, mesh=mesh, n_microbatches=4) ** 2).sum()

    def loss_seq(params_list):
        h = x
        for p in params_list:
            h = _mlp_stage(p, h)
        return (h ** 2).sum()

    g_pipe = jax.grad(loss_pipe)(stacked)
    g_seq = jax.grad(loss_seq)(per_stage)
    g_seq_stacked = pipeline_lib.stack_stage_params(g_seq)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
        g_pipe, g_seq_stacked)


def test_pipeline_rejects_bad_microbatching():
    mesh = build_mesh(plan_mesh(8, pipeline=4, fsdp=2))
    stacked = pipeline_lib.stack_stage_params(
        _make_stage_params(4, 4, jax.random.PRNGKey(0)))
    x = jnp.zeros((6, 4))
    with pytest.raises(ValueError):
        pipeline_lib.pipeline_apply(_mlp_stage, stacked, x, mesh=mesh,
                                    n_microbatches=4)

# ----- the dropless layer is handed its routing ------------------------------
class DroplessMoEAsItWas(nn.Module):
    """`models/moe.py DroplessMoE` before its routing was handed in (the
    parent of PR 47), kept here word for word but for its docstrings: the
    router is the layer's own, one matrix, chosen by four fields."""
    dim: int
    ffn_dim: int
    n_experts: int
    held: tuple
    top_k: int = 8
    n_shared: int = 1
    routed_scaling: float = 1.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    block: int = 256
    mesh: Any = None
    scoring: str = 'sigmoid'
    router_bias: bool = False

    @nn.compact
    def __call__(self, x, valid=None):
        b, s, d = x.shape
        n_held = len(self.held)
        router = self.param('router', nn.initializers.lecun_normal(),
                            (d, self.n_experts), self.param_dtype)
        flat = x.reshape(b * s, d)
        score = {'sigmoid': jax.nn.sigmoid,
                 'softmax': lambda z: jax.nn.softmax(z, axis=-1)}[
                     self.scoring]
        scores = score(jnp.dot(
            flat.astype(jnp.float32), router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        bias = self.param('correction_bias', nn.initializers.zeros,
                          (self.n_experts,), self.param_dtype).astype(
                              jnp.float32) if self.router_bias else None
        idx, weights = moe_lib.route_top_k(scores, self.top_k,
                                           self.routed_scaling, bias)

        def stack(name, shape):
            return self.param(name, nn.initializers.lecun_normal(),
                              (n_held,) + shape,
                              self.param_dtype).astype(self.dtype)

        local_of = np.full((self.n_experts,), n_held, np.int32)
        local_of[list(self.held)] = np.arange(n_held)
        xin = flat.astype(self.dtype)
        stacks = (stack('w_gate', (d, self.ffn_dim)),
                  stack('w_up', (d, self.ffn_dim)),
                  stack('w_down', (self.ffn_dim, d)))
        out, counts, kernel_trips = moe_lib.grouped_experts(
            xin, idx, weights, jnp.asarray(local_of), *stacks,
            min(self.block, -(-(b * s) // 8) * 8), self.mesh,
            None if valid is None else valid.reshape(b * s))
        self.sow('stats', 'expert_tokens', counts)
        self.sow('stats', 'touched', jnp.sum(counts[:n_held] > 0))
        self.sow('stats', 'kernel_trips', kernel_trips)
        if self.n_shared:
            dense = lambda name, feat: nn.Dense(  # noqa: E731
                feat, use_bias=False, dtype=self.dtype,
                param_dtype=self.param_dtype, name=name)
            width = self.n_shared * self.ffn_dim
            h = nn.silu(dense('shared_gate', width)(xin)) * \
                dense('shared_up', width)(xin)
            out = out + dense('shared_down', d)(h).astype(jnp.float32)
        return out.reshape(b, s, d).astype(x.dtype)


# The four families' routers as their models hand them (models/
# solar_open2.py, openpangu_moe.py, sdar_moe.py, mimo_v2.py), each beside
# the fields that chose the same router before.
HANDED = {
    'solar': (dict(top_k=2), dict(top_k=2), 1, False),
    'openpangu': (dict(top_k=2, scaling=2.5),
                  dict(top_k=2, routed_scaling=2.5), 1, False),
    'sdar': (dict(top_k=2, scoring='softmax'),
             dict(top_k=2, scoring='softmax'), 0, False),
    'mimo': (dict(top_k=2, bias=True), dict(top_k=2, router_bias=True), 0,
             True),
}


@pytest.mark.parametrize('family', list(HANDED))
@pytest.mark.parametrize('tokens', [8, 40], ids=['a_step', 'a_prompt'])
def test_a_handed_linear_router_is_the_layer_as_it_was(family, tokens):
    """`DroplessMoE` under the `LinearRouter` its model hands it gives the
    outputs and the counts of the layer that routed for itself, bit for
    bit, from the same parameter tree, and its program lowers to the same
    text: a decode step's few tokens and a padded prompt's rows (`valid`
    where the model passes it)."""
    now, then, n_shared, padded = HANDED[family]
    common = dict(dim=64, ffn_dim=32, n_experts=16, held=(2, 3, 4, 5),
                  n_shared=n_shared, dtype=jnp.float32,
                  param_dtype=jnp.float32, block=16)
    new = moe_lib.DroplessMoE(router=moe_lib.LinearRouter(**now), **common)
    old = DroplessMoEAsItWas(**then, **common)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, tokens // 2, 64))
    valid = (jnp.arange(tokens // 2)[None, :] <
             jnp.asarray([tokens // 4, tokens // 2])[:, None]
             ) if padded and tokens > 8 else None
    params = old.init(jax.random.PRNGKey(2), x)['params']
    if 'correction_bias' in params:
        params = dict(params, correction_bias=0.1 * jax.random.normal(
            jax.random.PRNGKey(3), (16,)))
    assert jax.tree.structure(new.init(jax.random.PRNGKey(2), x)[
        'params']) == jax.tree.structure(params)

    def layer(module):
        def moe(params, x, valid):
            return module.apply({'params': params}, x, valid,
                                mutable=['stats'])
        return moe

    got, want = (layer(m)(params, x, valid) for m in (new, old))
    assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(
        jax.tree.leaves(got), jax.tree.leaves(want)))
    text = [jax.jit(layer(m)).lower(params, x, valid).as_text()
            for m in (new, old)]
    assert text[0] == text[1]


def test_top_1_shares_add_up_to_the_whole_layer():
    """With `held` a proper subset and a router of one expert a token
    weighted by its probability (handed in as `routed`, as models/zaya.py
    hands it), the parts that all the shares give add up to the whole
    layer: four shares of four experts against the layer that holds all
    sixteen; every pair is held by exactly one share."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 20, 64))
    p = jax.nn.softmax(2.0 * jax.random.normal(jax.random.PRNGKey(2),
                                               (40, 16)), axis=-1)
    idx = jnp.argmax(p, axis=-1)[:, None]
    routed = (idx, jnp.take_along_axis(p, idx, axis=-1))

    def layer(held):
        return moe_lib.DroplessMoE(
            dim=64, ffn_dim=32, n_experts=16, held=tuple(held), n_shared=0,
            dtype=jnp.float32, param_dtype=jnp.float32, block=16)

    params = layer(range(16)).init(jax.random.PRNGKey(3), x,
                                   routed=routed)['params']
    whole = layer(range(16)).apply({'params': params}, x, routed=routed)
    total, held_pairs = 0.0, 0
    for lo in range(0, 16, 4):
        share = {k: v[lo:lo + 4] for k, v in params.items()}
        out, stats = layer(range(lo, lo + 4)).apply(
            {'params': share}, x, routed=routed, mutable=['stats'])
        counts = np.asarray(stats['stats']['expert_tokens'][0])
        assert counts.sum() == 40
        held_pairs += counts[:4].sum()
        total = total + out
    assert held_pairs == 40
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=1e-5)
    assert np.abs(np.asarray(whole)).max() > 1e-2
