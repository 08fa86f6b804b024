"""Model + sharded-training tests on the virtual 8-device CPU mesh."""
import collections
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import llama as llama_lib
from skypilot_tpu.models.llama import (Llama, LlamaConfig, LLAMA_CONFIGS,
                                       init_params)
from skypilot_tpu.parallel import sharding as sharding_lib
from skypilot_tpu.parallel.mesh import MeshPlan, build_mesh, plan_mesh
from skypilot_tpu.train import loss as loss_lib
from skypilot_tpu.train import trainer as trainer_lib
from skypilot_tpu.train.trainer import (TrainConfig, Trainer, lm_loss,
                                        make_sharded_train_step,
                                        make_train_state)

CFG = LLAMA_CONFIGS['tiny']


def test_mesh_plan():
    assert plan_mesh(8) == MeshPlan(1, 8, 1)
    assert plan_mesh(8, tensor=2) == MeshPlan(1, 4, 2)
    assert plan_mesh(8, data=2, tensor=2) == MeshPlan(2, 2, 2)
    with pytest.raises(ValueError):
        plan_mesh(8, data=3)


def test_llama_forward_shapes():
    model = Llama(CFG)
    rng = jax.random.PRNGKey(0)
    variables = init_params(model, rng, batch=2, seq=32)
    tokens = jnp.zeros((2, 32), jnp.int32)
    logits = model.apply(variables, tokens)
    assert logits.shape == (2, 32, CFG.vocab_size)
    assert logits.dtype == jnp.float32


def test_llama_num_params_matches():
    model = Llama(CFG)
    variables = init_params(model, jax.random.PRNGKey(0))
    actual = sum(np.prod(p.shape) for p in jax.tree.leaves(variables))
    assert actual == CFG.num_params()


def test_init_params_are_freed_with_their_last_reference():
    """Found on four chips (PR 22): initialised under per-block remat,
    every parameter stayed alive in JAX's trace cache, so a whole
    unsharded copy sat on device 0 beside the sharded engine's."""
    import dataclasses
    import gc
    import weakref
    cfg = dataclasses.replace(CFG, remat=True)
    variables = init_params(Llama(cfg), jax.random.PRNGKey(0))
    # ...and it is the same tree that the remat'd model initialises.
    ref = Llama(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32))
    assert jax.tree.structure(ref) == jax.tree.structure(variables)
    assert all(bool((a == b).all()) for a, b in zip(
        jax.tree.leaves(ref), jax.tree.leaves(variables)))
    alive = [weakref.ref(leaf) for leaf in jax.tree.leaves(variables)]
    del variables
    gc.collect()
    assert not any(r() is not None for r in alive)


def test_llama_causality():
    """Future tokens must not affect past logits."""
    model = Llama(CFG)
    variables = init_params(model, jax.random.PRNGKey(0), batch=1, seq=16)
    t1 = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0,
                            CFG.vocab_size)
    t2 = t1.at[0, -1].set((t1[0, -1] + 1) % CFG.vocab_size)
    l1 = model.apply(variables, t1)
    l2 = model.apply(variables, t2)
    np.testing.assert_allclose(l1[0, :-1], l2[0, :-1], atol=1e-5)
    assert not np.allclose(l1[0, -1], l2[0, -1], atol=1e-5)


def test_llama_decode_cache_matches_full_forward():
    model = Llama(CFG)
    rng = jax.random.PRNGKey(0)
    seq = 8
    variables = init_params(model, rng, batch=1, seq=seq)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, seq), 0,
                                CFG.vocab_size)
    full = model.apply(variables, tokens)
    # Prefill the first half of the prompt in one decode=True apply (its
    # K/V must land in the cache), then decode the rest token-by-token.
    prefill = seq // 2
    logits, cache_vars = model.apply(variables, tokens[:, :prefill],
                                     decode=True, mutable=['cache'])
    np.testing.assert_allclose(logits[0, -1], full[0, prefill - 1],
                               rtol=1e-4, atol=1e-4)
    state = {**variables, **cache_vars}
    for i in range(prefill, seq):
        positions = jnp.array([[i]])
        logits, cache_vars = model.apply(
            state, tokens[:, i:i + 1], positions=positions, decode=True,
            mutable=['cache'])
        state = {**variables, **cache_vars}
    np.testing.assert_allclose(logits[0, 0], full[0, -1], rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize('plan', [MeshPlan(1, 8, 1), MeshPlan(2, 2, 2),
                                  MeshPlan(8, 1, 1)])
def test_sharded_training_loss_decreases(plan):
    mesh = build_mesh(plan)
    model = Llama(CFG, mesh)
    rng = jax.random.PRNGKey(0)
    tokens = jax.random.randint(rng, (8, 32), 0, CFG.vocab_size)
    state, shardings = make_train_state(
        model, mesh, rng, tokens,
        TrainConfig(learning_rate=1e-2, warmup_steps=1, total_steps=50))
    step = make_sharded_train_step(mesh, shardings)
    losses = []
    for _ in range(8):
        state, metrics = step(state, tokens)  # overfit one batch
        losses.append(float(metrics['loss']))
    assert losses[-1] < losses[0], losses


def test_fsdp_params_actually_sharded():
    mesh = build_mesh(MeshPlan(1, 8, 1))
    model = Llama(CFG, mesh)
    rng = jax.random.PRNGKey(0)
    tokens = jnp.zeros((8, 32), jnp.int32)
    state, _ = make_train_state(model, mesh, rng, tokens)
    kernel = state.params['layer_0']['mlp']['gate_proj']['kernel']
    # 'embed' axis (64) sharded over fsdp=8 -> each shard holds 1/8.
    shard_shape = kernel.sharding.shard_shape(kernel.shape)
    assert shard_shape[0] == kernel.shape[0] // 8


def test_trainer_checkpoint_roundtrip(tmp_path):
    mesh = build_mesh(MeshPlan(1, 8, 1))
    model = Llama(CFG, mesh)
    rng = jax.random.PRNGKey(0)
    tokens = jax.random.randint(rng, (8, 32), 0, CFG.vocab_size)
    trainer = Trainer(model, mesh, rng, tokens,
                      TrainConfig(warmup_steps=1, total_steps=10),
                      checkpoint_dir=str(tmp_path / 'ckpt'))
    trainer.state, _ = trainer.train_step(trainer.state, tokens)
    trainer.save_checkpoint()
    trainer._ckpt_mgr.close()  # flush async save

    trainer2 = Trainer(model, mesh, rng, tokens,
                       TrainConfig(warmup_steps=1, total_steps=10),
                       checkpoint_dir=str(tmp_path / 'ckpt'))
    resumed = trainer2.restore_if_available()
    assert resumed == 1
    p1 = jax.device_get(trainer.state.params['final_norm']['scale'])
    p2 = jax.device_get(trainer2.state.params['final_norm']['scale'])
    np.testing.assert_array_equal(p1, p2)


def test_ring_attention_model_variant():
    """Same weights, ring-attention impl == xla impl."""
    mesh = build_mesh(MeshPlan(1, 8, 1))
    import dataclasses
    cfg_ring = dataclasses.replace(CFG, attention_impl='ring')
    model_x = Llama(CFG, mesh)
    model_r = Llama(cfg_ring, mesh)
    rng = jax.random.PRNGKey(0)
    variables = init_params(model_x, rng, batch=2, seq=64)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                                CFG.vocab_size)
    lx = model_x.apply(variables, tokens)
    lr = model_r.apply(variables, tokens)
    # bf16 compute: blockwise vs global softmax round differently; bf16
    # eps is 7.8e-3 so allow a few ulps.
    np.testing.assert_allclose(lx, lr, rtol=3e-2, atol=3e-2)


def test_lm_loss_shift():
    logits = jnp.zeros((1, 4, 8))
    tokens = jnp.array([[1, 2, 3, 4]])
    loss = lm_loss(logits, tokens)
    np.testing.assert_allclose(float(loss), np.log(8), rtol=1e-5)


# ----- the head and the loss by chunks of rows (train/loss.py) ---------------
@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16],
                         ids=['float32', 'bfloat16'])
@pytest.mark.parametrize('chunks', [1, 2, 8])
@pytest.mark.parametrize('tied', [False, True], ids=['untied', 'tied'])
def test_chunked_head_and_loss_is_lm_loss(tied, chunks, dtype):
    """The hidden state and the head that `Llama` hands out, through the
    chunked head-and-loss: `lm_loss`'s value over the whole logits and
    autodiff's gradients of the hidden state and of the head's weights,
    whatever the number of chunks, with the rows summed in one group or
    two; in float32 to rounding, in bfloat16 compute to what the
    gradient tests of this file allow."""
    cfg = dataclasses.replace(CFG, tie_embeddings=tied, dtype=dtype)
    model = Llama(cfg)
    variables = init_params(model, jax.random.PRNGKey(0), batch=2, seq=32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                cfg.vocab_size)
    hidden, head, is_tied = model.apply(variables, tokens,
                                        method='hidden_and_head')
    assert is_tied == tied and hidden.dtype == dtype
    assert hidden.shape == (2, 32, cfg.dim)
    assert head.shape == ((cfg.vocab_size, cfg.dim) if tied else
                          (cfg.dim, cfg.vocab_size))

    def whole(h, w):            # as `nn.Dense(dtype=...)` / `attend` do
        w = w.astype(dtype)
        logits = jnp.dot(h, w.T if tied else w).astype(jnp.float32)
        return lm_loss(logits, tokens)

    want, (want_h, want_w) = jax.value_and_grad(whole, (0, 1))(hidden, head)
    np.testing.assert_allclose(
        want, lm_loss(model.apply(variables, tokens), tokens), rtol=1e-6)
    tolerance = 1e-6 if dtype == jnp.float32 else 2e-2
    for shards in (1, 2):
        got, (got_h, got_w) = jax.value_and_grad(
            lambda h, w: loss_lib.chunked_lm_loss(
                h, w, tokens, 32 // chunks, tied, shards), (0, 1))(
                    hidden, head)
        assert got_h.dtype == hidden.dtype and got_w.dtype == head.dtype
        np.testing.assert_allclose(got, want, rtol=max(tolerance, 2e-6)
                                   if dtype == jnp.float32 else 1e-3)
        for a, b in ((got_h, want_h), (got_w, want_w)):
            a, b = (np.asarray(x, np.float32) for x in (a, b))
            assert np.abs(a - b).max() <= tolerance * np.abs(b).max()
    # The cotangent scales what the forward pass stored.
    _, (twice_h, twice_w) = jax.value_and_grad(
        lambda h, w: 2 * loss_lib.chunked_lm_loss(
            h, w, tokens, 32 // chunks, tied, 2), (0, 1))(hidden, head)
    np.testing.assert_array_equal(np.asarray(twice_w), 2 * np.asarray(got_w))
    np.testing.assert_array_equal(np.asarray(twice_h, np.float32),
                                  2 * np.asarray(got_h, np.float32))


def test_chunks_come_from_the_shapes():
    """One function says how the step walks the rows and what the
    trainer counts for them: the largest divisor of the sequence whose
    chunk of logits stays under the constant, one device's rows and
    words."""
    # pretrain-4k: 4 rows x 4,096 positions over 64,000 words, 6 B a logit.
    assert loss_lib.loss_chunks(None, 4, 4096, 64000, 2) == (
        512, 1, 4 * 512 * 64000 * 6)
    mesh = build_mesh(MeshPlan(1, 2, 2), jax.devices()[:4])
    assert loss_lib.loss_chunks(mesh, 4, 4096, 64000, 2) == (
        2048, 2, 2 * 2048 * 32000 * 6)
    # Rows that do not divide stay whole; a sequence of no small divisor
    # goes a position at a time sooner than over the constant.
    assert loss_lib.loss_chunks(mesh, 3, 4096, 64000, 2).shards == 1
    assert loss_lib.loss_chunks(None, 64, 4099, 64000, 2).positions == 1
    # The tests' models are one chunk.
    assert loss_lib.loss_chunks(None, 8, 32, 256, 2).positions == 32
    with pytest.raises(ValueError, match='do not divide'):
        loss_lib.chunked_lm_loss(jnp.zeros((2, 32, 8)), jnp.zeros((8, 16)),
                                 jnp.zeros((2, 32), jnp.int32), 5)


def test_chunked_loss_on_a_mesh_gathers_the_head_once():
    """On the 2 x 2 host mesh, rows over fsdp and the vocabulary over
    tensor as the train step's shardings have them (plain `jnp` under
    `jit`): the one-device value and gradients; the head's kernel,
    divided over fsdp as a parameter, is gathered in front of the loop
    and never inside it, however many chunks; and the rows' sums of the
    head's gradient meet in one reduction behind the loop, not one a
    chunk."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = build_mesh(MeshPlan(1, 2, 2), jax.devices()[:4])
    b, s, d, v = 4, 64, 64, 256
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    hidden = jax.random.normal(keys[0], (b, s, d), jnp.bfloat16)
    head = jax.random.normal(keys[1], (d, v), jnp.float32) * 0.1
    tokens = jax.random.randint(keys[2], (b, s), 0, v)
    rows = sharding_lib.batch_sharding(mesh)
    shardings = (rows, NamedSharding(mesh, P('fsdp', 'tensor')), rows)

    def loss_and_grads(chunk, shards):
        return jax.value_and_grad(
            lambda h, w, t: loss_lib.chunked_lm_loss(h, w, t, chunk, False,
                                                     shards), (0, 1))

    want, (want_h, want_w) = loss_and_grads(s, 1)(hidden, head, tokens)
    gathers = {}
    for chunk in (s // 2, s // 8):
        chunks = loss_lib.loss_chunks(mesh, b, s, v, 2)
        assert chunks.shards == 2
        compiled = jax.jit(
            loss_and_grads(chunk, chunks.shards), in_shardings=shardings,
            out_shardings=(None, shardings[:2])).lower(
                hidden, head, tokens).compile()
        got, (got_h, got_w) = compiled(hidden, head, tokens)
        np.testing.assert_allclose(got, want, rtol=1e-5)
        for a, b_ in ((got_h, want_h), (got_w, want_w)):
            a, b_ = (np.asarray(x, np.float32) for x in (a, b_))
            assert np.abs(a - b_).max() <= 2e-2 * np.abs(b_).max()
        text = compiled.as_text()
        (body,) = re.findall(r'body=%?([\w.\-]+)', text)
        (body_text,) = [c for c in text.split('\n\n')
                        if c.lstrip().startswith(f'%{body} ')]
        assert 'all-gather' not in body_text
        # In the loop, what crosses devices is a row's worth: no operand
        # of the head's size on one device, [64, 128].
        crossing = [line for line in body_text.splitlines()
                    if re.search(r' (all-reduce|reduce-scatter|all-to-all)'
                                 r'(-start)?\(', line)]
        assert crossing and not any(
            f'{d},{v // 2}]' in line.split('metadata')[0]
            for line in crossing)
        gathers[chunk] = len(re.findall(r' all-gather(-start)?\(', text))
    assert gathers[s // 2] == gathers[s // 8] >= 1


@pytest.mark.parametrize('tied', [False, True], ids=['untied', 'tied'])
def test_train_step_by_chunks_is_the_whole_logits_step(tied, monkeypatch):
    """A step of `LLAMA_CONFIGS['tiny']` through the chunked head and
    loss (four chunks: the constant is set small) against the step that
    is handed the logits whole: the same loss and `grad_norm`, the same
    parameter tree, and no buffer of the whole logits' shape."""
    cfg = dataclasses.replace(CFG, tie_embeddings=tied)
    mesh = build_mesh(MeshPlan(1, 1, 1), jax.devices()[:1])
    model = Llama(cfg, mesh)
    rng = jax.random.PRNGKey(0)
    tokens = jax.random.randint(rng, (8, 32), 0, cfg.vocab_size)
    tcfg = TrainConfig(learning_rate=1e-2, warmup_steps=1, total_steps=10)
    assert trainer_lib.offers_hidden(model.apply)
    monkeypatch.setattr(loss_lib, '_CHUNK_BYTES',
                        8 * 8 * cfg.vocab_size * 6)
    assert loss_lib.loss_chunks(mesh, 8, 32, cfg.vocab_size,
                                2).positions == 8

    first, shardings = make_train_state(model, mesh, rng, tokens, tcfg)

    def one_step(loss_fn):
        state = jax.tree.map(jnp.copy, first)       # the step donates it
        lowered = make_sharded_train_step(mesh, shardings, loss_fn).lower(
            state, tokens)
        state, metrics = lowered.compile()(state, tokens)
        return ((float(metrics['loss']), float(metrics['grad_norm'])),
                state.params, lowered.as_text())

    chunked, params, text = one_step(lm_loss)
    whole, whole_params, whole_text = one_step(
        lambda logits, toks: lm_loss(logits, toks))
    # The logits whole are [8, 32, V] float32; by chunks [8, 8, V].
    assert f'tensor<8x32x{cfg.vocab_size}xf32>' in whole_text
    assert f'tensor<8x32x{cfg.vocab_size}xf32>' not in text
    assert f'tensor<8x8x{cfg.vocab_size}xf32>' in text
    np.testing.assert_allclose(chunked, whole, rtol=2e-2)
    np.testing.assert_allclose(chunked[0], whole[0], rtol=1e-3)
    assert (jax.tree_util.tree_structure(params) ==
            jax.tree_util.tree_structure(whole_params))
    paths = {'/'.join(k.key for k in path) for path, _ in
             jax.tree_util.tree_leaves_with_path(params)}
    assert 'embed/embedding' in paths
    assert ('lm_head/kernel' in paths) == (not tied)
    # A module that only returns logits is handed them whole.
    assert not trainer_lib.offers_hidden(lambda variables, toks: None)


# ----- what a block keeps for its backward pass (models/llama.py keep_plan) --
# float32 throughout, so that a kept value and one computed again differ by
# rounding only; 'flash' as the training cells run (the XLA branch here).
KEEP_CFG = dataclasses.replace(CFG, remat=True, dtype=jnp.float32,
                               attention_impl='flash')
_EVERYTHING = 10**12


def _out_lse_bytes(cfg, mesh, batch, seq):
    """The bytes that keep `out` + `lse` in every layer and nothing more."""
    tokens, tp = llama_lib.device_share(cfg, mesh, batch, seq)
    return (cfg.n_layers * tokens * cfg.n_heads // tp *
            (cfg.head_dim * jnp.dtype(cfg.dtype).itemsize + 4))


def _loss_grads(cfg, variables, tokens, mesh=None):
    model = Llama(cfg, mesh)
    return jax.grad(lambda p: lm_loss(model.apply({'params': p}, tokens),
                                      tokens))(variables['params'])


def _primitives(jaxpr, counts=None):
    """Every equation of a jaxpr and of the jaxprs inside it, by
    primitive; calls of jitted functions by the function's name as well."""
    counts = collections.Counter() if counts is None else counts
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] += 1
        if 'jaxpr' in eqn.params and 'name' in eqn.params:
            counts[eqn.params['name']] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, counts)
    return counts


@pytest.mark.parametrize('keep', ['nothing', 'out_lse', 'everything'])
def test_kept_activations_leave_the_gradients_as_they_are(keep):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                CFG.vocab_size)
    budget = {'nothing': 0, 'everything': _EVERYTHING,
              'out_lse': _out_lse_bytes(KEEP_CFG, None, 2, 32)}[keep]
    cfg = dataclasses.replace(KEEP_CFG, remat_keep_bytes=budget)
    plan = llama_lib.keep_plan(cfg, None, 2, 32)
    assert plan.layers == ({'nothing': (), 'out_lse': ('attn_out',),
                            'everything': tuple(llama_lib.KEEP_GROUPS)}[keep],
                           ) * 2
    variables = init_params(Llama(cfg), jax.random.PRNGKey(0), batch=2,
                            seq=32)
    plain = _loss_grads(dataclasses.replace(cfg, remat=False), variables,
                        tokens)
    kept = _loss_grads(cfg, variables, tokens)
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(kept)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-7)


def test_backward_pass_runs_again_only_what_was_not_kept():
    """One block's gradient program, by its equations.  With nothing to
    keep it is the program of 'none' (and of a model nobody gave a
    budget); with everything kept no matmul runs a second time: the
    count is that of a model without checkpoints, where 'none' runs q, k,
    v, gate, up, `o_proj` and the attention's two products again (the
    last to feed `o_proj`: this branch's backward rule makes them once
    more for itself either way)."""
    cfg = dataclasses.replace(KEEP_CFG, n_layers=1)
    tokens = jnp.zeros((2, 32), jnp.int32)
    variables = init_params(Llama(cfg), jax.random.PRNGKey(0), batch=2,
                            seq=32)

    def counts(**kw):
        c = dataclasses.replace(cfg, **kw)
        found = _primitives(jax.make_jaxpr(
            lambda p: _loss_grads(c, {'params': p}, tokens))(
                variables['params']).jaxpr)
        del found['name']           # a name is the identity
        return found

    none = counts(remat_policy='none')
    assert counts(remat_keep_bytes=0) == none == counts()
    everything = counts(remat_keep_bytes=_EVERYTHING)
    plain = counts(remat=False)
    assert everything['dot_general'] == plain['dot_general']
    assert none['dot_general'] == plain['dot_general'] + 8
    # q/k/v alone (no flash call, so they come first): the three
    # projections are spared and nothing else.
    qkv_bytes = llama_lib.keep_plan(
        dataclasses.replace(cfg, remat_keep_bytes=_EVERYTHING), None, 2,
        32).kept_bytes['qkv']
    assert counts(remat_keep_bytes=qkv_bytes, attention_impl='xla')[
        'dot_general'] == counts(remat_policy='none', attention_impl='xla')[
            'dot_general'] - 3


def test_a_remat_policy_that_is_neither_fit_nor_none_is_refused():
    """'fit' and 'none' are what there is ('dots' went: no run set it, and
    the compiler refused what it kept); anything else is refused where the
    config is read, not taken for 'none'."""
    cfg = dataclasses.replace(KEEP_CFG, remat_policy='dots')
    with pytest.raises(ValueError, match="'fit' or 'none', got 'dots'"):
        llama_lib.keep_plan(cfg, None, 2, 32)
    with pytest.raises(ValueError, match="'fit' or 'none', got 'dots'"):
        jax.eval_shape(lambda: Llama(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((2, 32), jnp.int32)))


@pytest.mark.parametrize('mesh_shape', [None, (2, 2)],
                         ids=['one-device', 'fsdp2-tensor2'])
def test_keep_plan_spends_the_bytes_dearest_first(mesh_shape):
    """Given bytes, the groups come out in `KEEP_GROUPS`' order, a layer
    at a time, never past the bytes; on a mesh the bytes are one
    device's (tokens over fsdp, heads and FFN columns over tensor)."""
    mesh = None
    if mesh_shape:
        mesh = build_mesh(MeshPlan(1, *mesh_shape), jax.devices()[:4])
    cfg = LlamaConfig(vocab_size=64000, dim=2048, n_layers=8, n_heads=16,
                      n_kv_heads=16, ffn_dim=5504, max_seq_len=4096)
    share = 4 if mesh_shape else 1      # 2 ways the tokens x 2 the heads
    whole = llama_lib.keep_plan(
        dataclasses.replace(cfg, remat_keep_bytes=_EVERYTHING), mesh, 4,
        4096)
    assert whole.kept_bytes == {
        'attn_out': 545259520 // share, 'qkv': 1610612736 // share,
        'gate_up': 2885681152 // share,
        'stream': 536870912 // (2 if mesh_shape else 1)}
    assert whole.recomputed_flops == 0
    assert whole.forward_flops == pytest.approx(15.46e12, rel=1e-3)
    order = list(llama_lib.KEEP_GROUPS)
    per_layer = {g: b // 8 for g, b in whole.kept_bytes.items()}
    previous = None
    for budget in [0, per_layer['attn_out'] * 5, whole.kept_bytes['attn_out'],
                   10**9 // share, 3 * 10**9 // share, _EVERYTHING]:
        plan = llama_lib.keep_plan(
            dataclasses.replace(cfg, remat_keep_bytes=budget), mesh, 4, 4096)
        assert sum(plan.kept_bytes.values()) <= budget
        assert all(plan.kept_bytes[g] == per_layer[g] * sum(
            g in kept for kept in plan.layers) for g in order)
        counts = [sum(g in kept for kept in plan.layers) for g in order]
        # Dearest first: a group is begun only once no layer of a dearer
        # one still fits in what is left.
        left = budget - sum(plan.kept_bytes.values())
        for g, n in zip(order, counts):
            assert n == 8 or per_layer[g] > left
        for kept in plan.layers:
            assert list(kept) == [g for g in order if g in kept]
        if previous is not None:
            assert plan.recomputed_flops <= previous
        previous = plan.recomputed_flops
    nothing = llama_lib.keep_plan(
        dataclasses.replace(cfg, remat_keep_bytes=0), mesh, 4, 4096)
    assert 100 * nothing.recomputed_flops / nothing.forward_flops == \
        pytest.approx(80.89, abs=0.01)
    assert nothing == llama_lib.keep_plan(cfg, mesh, 4, 4096)  # nobody said


def test_kept_names_survive_the_shard_map(monkeypatch):
    """On a 2 x 2 mesh the flash call goes through `shard_map`: with
    `out` + `lse` kept the gradient program holds the forward kernel once
    a layer, with nothing kept twice.  Traced only (the kernel's branch is
    chosen by the backend's name: steered here), never run."""
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    mesh = build_mesh(MeshPlan(1, 2, 2), jax.devices()[:4])
    cfg = dataclasses.replace(KEEP_CFG, dtype=jnp.bfloat16)
    tokens = jnp.zeros((4, 128), jnp.int32)
    variables = jax.eval_shape(
        lambda: Llama(dataclasses.replace(cfg, remat=False), mesh).init(
            jax.random.PRNGKey(0), tokens))
    import flax.linen as nn
    params = nn.meta.unbox(variables)['params']

    def kernels(budget):
        c = dataclasses.replace(cfg, remat_keep_bytes=budget)
        found = _primitives(jax.make_jaxpr(
            lambda p: _loss_grads(c, {'params': p}, tokens, mesh))(
                params).jaxpr)
        assert found['shard_map'] >= cfg.n_layers
        return found['flash_attention_fwd']

    assert kernels(0) == 2 * cfg.n_layers
    assert kernels(_out_lse_bytes(cfg, mesh, 4, 128)) == cfg.n_layers


@pytest.mark.parametrize('plan', [MeshPlan(1, 1, 1), MeshPlan(1, 2, 2)],
                         ids=['one-device', 'fsdp2-tensor2'])
def test_trainer_hands_the_model_what_the_device_has_left(plan, monkeypatch):
    """Where the device reports a limit (none does here: one is given),
    the trainer counts the state's bytes on ONE device, the step's
    temporaries and a margin, and the model it steps with has the rest
    as `remat_keep_bytes`; the gauges say what that bought, the counters
    what the steps still run twice.  With no limit the model is left as
    it was and keeps nothing.  The losses are the same either way."""
    from skypilot_tpu.server import metrics as metrics_lib
    from skypilot_tpu.train import trainer as trainer_lib
    n = plan.num_devices
    mesh = build_mesh(plan, jax.devices()[:n])
    cfg = dataclasses.replace(KEEP_CFG, dtype=jnp.bfloat16)
    rng = jax.random.PRNGKey(0)
    tokens = jax.random.randint(rng, (8, 32), 0, cfg.vocab_size)
    tcfg = TrainConfig(warmup_steps=1, total_steps=10)

    def losses(trainer):
        got = []
        trainer.run(iter([tokens] * 3), num_steps=3, log_every=1,
                    log_fn=lambda m: got.append(float(m['loss'])))
        return got

    metrics_lib.reset_for_tests()
    plain = Trainer(Llama(cfg, mesh), mesh, rng, tokens, tcfg)
    assert plain.model.cfg.remat_keep_bytes is None
    assert 'skytpu_train_kept_activation_bytes{what="attn_out"} 0' in \
        metrics_lib.render()
    base = losses(plain)
    text = metrics_lib.render()
    forward = float(re.search(
        r'^skytpu_train_forward_flops_total (\S+)$', text, re.M).group(1))
    again = float(re.search(
        r'^skytpu_train_recomputed_flops_total (\S+)$', text, re.M).group(1))
    # Two logged steps (the first, the compile, is outside every window).
    assert forward == pytest.approx(2 * plain._plan.forward_flops)
    assert again / forward == pytest.approx(
        plain._plan.recomputed_flops / plain._plan.forward_flops)
    assert 0.7 < again / forward < 0.85

    # One device's state: all of it alone, about a quarter on the mesh.
    state_bytes = sum(s.data.nbytes for leaf in jax.tree.leaves(plain.state)
                      for s in leaf.addressable_shards
                      if s.device == mesh.local_devices[0])
    whole = sum(leaf.nbytes for leaf in jax.tree.leaves(plain.state))
    assert state_bytes == whole if n == 1 else state_bytes < 0.4 * whole
    params_bytes = sum(
        s.data.nbytes for leaf in jax.tree.leaves(plain.state.params)
        for s in leaf.addressable_shards
        if s.device == mesh.local_devices[0])

    def plan_of(budget):
        return llama_lib.keep_plan(
            dataclasses.replace(cfg, remat_keep_bytes=budget), mesh, 8, 32)

    # Room for `out` + `lse` in both layers and q/k/v in one: the limit
    # is what the count of that plan comes to, and 16 B.
    per_layer = plan_of(0).layer_bytes
    assert list(per_layer) == list(llama_lib.KEEP_GROUPS)
    want = 2 * per_layer['attn_out'] + per_layer['qkv']
    temporaries = trainer_lib.step_temporary_bytes(
        cfg, mesh, 8, 32, params_bytes, plan_of(want))
    assert temporaries >= trainer_lib.step_temporary_bytes(
        cfg, mesh, 8, 32, params_bytes, plan_of(0))
    limit = (state_bytes + temporaries + 16) * 32 // 31
    monkeypatch.setattr(trainer_lib, '_bytes_limit', lambda device: limit)
    metrics_lib.reset_for_tests()
    fitted = Trainer(Llama(cfg, mesh), mesh, rng, tokens, tcfg)
    # The pass took that and whatever more costs the fullest moment
    # nothing (a later block's, freed before the first block's backward
    # pass, where this small model is fullest); everything does not fit.
    budget = trainer_lib.activation_budget(
        cfg, mesh, 8, 32, limit, state_bytes, params_bytes)
    assert fitted.model.cfg.remat_keep_bytes == budget >= want
    assert fitted._plan == plan_of(budget)
    assert sum(fitted._plan.kept_bytes.values()) == budget
    assert fitted._plan.layers[0][:2] == ('attn_out', 'qkv')
    room = int(limit * 31 / 32) - state_bytes
    assert trainer_lib.step_temporary_bytes(
        cfg, mesh, 8, 32, params_bytes, fitted._plan) <= room
    assert trainer_lib.step_temporary_bytes(
        cfg, mesh, 8, 32, params_bytes, plan_of(_EVERYTHING)) > room
    text = metrics_lib.render()
    assert (f'skytpu_train_kept_activation_bytes{{what="qkv"}} '
            f'{fitted._plan.kept_bytes["qkv"]}\n') in text
    # One chunk of logits here, every row's: 6 B a logit on one device.
    tokens_here, tp = llama_lib.device_share(cfg, mesh, 8, 32)
    assert (f'skytpu_train_loss_logit_bytes '
            f'{tokens_here * cfg.vocab_size // tp * 6}\n') in text
    np.testing.assert_allclose(losses(fitted), base, rtol=2e-2)


def test_trainer_keeps_five_gigabytes_at_the_cells_shape():
    """`pretrain-4k` by arithmetic alone (Yi-Coder's widths, 8 layers, 4 x
    4,096 tokens, float32 parameters and Adam on a v5e's 16,909,336,064
    B): with the loss by chunks the blocks are handed more than the
    3.2 GB that counting the backward pass as one moment would leave,
    and with the logits whole what PR 40 measured fits, no more."""
    cfg = LlamaConfig(vocab_size=64000, dim=2048, n_layers=8, n_heads=16,
                      n_kv_heads=16, ffn_dim=5504, max_seq_len=4096)
    params = 4 * cfg.num_params()
    state = 3 * params + 64         # the parameters, Adam's moments, counts

    def handed(chunked):
        budget = trainer_lib.activation_budget(
            cfg, None, 4, 4096, 16909336064, state, params, chunked)
        return budget, llama_lib.keep_plan(
            dataclasses.replace(cfg, remat_keep_bytes=budget), None, 4, 4096)

    budget, plan = handed(chunked=True)
    assert budget == sum(plan.kept_bytes.values()) >= 3.2e9
    assert [sum(g in kept for kept in plan.layers)
            for g in llama_lib.KEEP_GROUPS] == [8, 8, 8, 5]
    assert 100 * plan.recomputed_flops / plan.forward_flops < 3
    assert trainer_lib.loss_logit_bytes(None, 4, 4096, 64000, 2,
                                        True) == 786432000
    budget, plan = handed(chunked=False)
    assert trainer_lib.loss_logit_bytes(None, 4, 4096, 64000, 2,
                                        False) == 6291456000
    assert [sum(g in kept for kept in plan.layers)
            for g in llama_lib.KEEP_GROUPS] == [8, 3, 0, 1]
