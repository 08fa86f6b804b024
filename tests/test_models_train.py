"""Model + sharded-training tests on the virtual 8-device CPU mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models.llama import (Llama, LlamaConfig, LLAMA_CONFIGS,
                                       init_params)
from skypilot_tpu.parallel.mesh import MeshPlan, build_mesh, plan_mesh
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
