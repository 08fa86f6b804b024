"""GPipe-style pipeline parallelism over the mesh's `pipeline` axis.

The reference reaches pipeline parallelism only through NeMo recipe
flags (model.pipeline_model_parallel_size,
examples/nemo/nemo_gpt_distributed.yaml:100 — SURVEY.md §2.15); here it
is a first-party SPMD transform, built the TPU way:

- stage weights are STACKED with a leading [n_stages] dim sharded over
  the `pipeline` mesh axis — every device holds exactly its stage's
  slice, there is no per-stage program;
- one shard_map runs the classic pipelined loop: at step t each stage
  applies its layer to its current microbatch and `ppermute`s the
  activation to the next stage (point-to-point neighbor hops — the one
  collective pattern that tolerates slow inter-slice links, which is why
  `pipeline` is the outermost mesh axis);
- the bubble is the standard GPipe (n_stages - 1) / (n_micro + n_stages
  - 1) fraction: pick n_microbatches >> n_stages.

Differentiable end-to-end (ppermute transposes to the reverse
permutation, so the backward pass pipelines in the opposite direction
for free).
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def stack_stage_params(per_stage_params: list) -> Any:
    """[stage0_tree, stage1_tree, ...] -> one tree with leading stage
    dim (shard it over 'pipeline' with stage_param_sharding)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                  *per_stage_params)


def stage_param_sharding(mesh: Mesh, tree: Any) -> Any:
    """NamedShardings putting every leaf's leading dim on 'pipeline'."""
    def spec(x):
        return NamedSharding(
            mesh, P('pipeline', *([None] * (x.ndim - 1))))
    return jax.tree_util.tree_map(spec, tree)


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   stacked_params: Any,
                   x: jax.Array,
                   *,
                   mesh: Mesh,
                   n_microbatches: int) -> jax.Array:
    """Run `n_stages` chained applications of stage_fn over x, pipelined.

    stage_fn(params_i, activation) -> activation (shape-preserving
    between stages); stacked_params leaves have leading dim n_stages
    (= mesh.shape['pipeline']); x [B, ...] with B % n_microbatches == 0.
    Equivalent (numerically) to sequentially folding stage_fn over the
    stages.
    """
    n_stages = mesh.shape['pipeline']
    b = x.shape[0]
    if b % n_microbatches:
        raise ValueError(f'batch {b} not divisible by '
                         f'{n_microbatches} microbatches')
    mb = b // n_microbatches
    micro = x.reshape((n_microbatches, mb) + x.shape[1:])
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P('pipeline'), P()),
        out_specs=P(),
        check_vma=False)
    def run(params_local, micro_all):
        # params_local leaves: [1, ...] — this stage's slice.
        params_i = jax.tree_util.tree_map(lambda p: p[0], params_local)
        stage = jax.lax.axis_index('pipeline')
        last = n_stages - 1
        state = jnp.zeros_like(micro_all[0])
        outputs = jnp.zeros_like(micro_all)

        def step(t, carry):
            state, outputs = carry
            recv = jax.lax.ppermute(state, 'pipeline', perm)
            feed_idx = jnp.clip(t, 0, n_microbatches - 1)
            feed = jax.lax.dynamic_index_in_dim(micro_all, feed_idx, 0,
                                                keepdims=False)
            my_in = jnp.where(stage == 0, feed, recv)
            out = stage_fn(params_i, my_in)
            out_idx = jnp.clip(t - last, 0, n_microbatches - 1)
            write = jnp.logical_and(stage == last, t >= last)
            cur = jax.lax.dynamic_index_in_dim(outputs, out_idx, 0,
                                               keepdims=False)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(write, out, cur), out_idx, 0)
            return out, outputs

        _, outputs = jax.lax.fori_loop(
            0, n_microbatches + last, step, (state, outputs))
        # Only the last stage holds real outputs; psum broadcasts them
        # (every other stage contributes zeros).
        outputs = jnp.where(stage == last, outputs,
                            jnp.zeros_like(outputs))
        return jax.lax.psum(outputs, 'pipeline')

    out = run(stacked_params, micro)
    return out.reshape((b,) + out.shape[2:])
