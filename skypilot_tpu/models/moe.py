"""Mixture-of-Experts MLP with expert parallelism.

The reference covers MoE only through serving recipes (llm/mixtral/,
llm/dbrx/ — vLLM handles expert parallel internally, SURVEY.md §2.15);
here it is a first-party layer, built the TPU way:

- GShard-style top-k routing with a fixed per-expert capacity, expressed
  as dense one-hot dispatch/combine einsums — static shapes, no sorting,
  no dynamic gathers, so XLA tiles everything onto the MXU;
- expert weights carry the logical 'expert' axis; with the default
  sharding rules that maps to the `expert` mesh axis, and since tokens
  are batch-sharded over the same axis, pjit lowers the dispatch/combine
  contractions into all_to_alls over ICI — expert parallelism is a
  sharding-rule change, not a model change;
- the load-balancing auxiliary loss (mean router prob x mean token
  fraction per expert, scaled by E) is sown under
  `intermediates/moe_aux_loss` for the train loss to pick up.

Tokens overflowing an expert's capacity are dropped for that expert (the
residual connection around the block carries them unchanged) — standard
Switch/GShard semantics.

Recommended mesh: EP x DP (x TP), i.e. `plan_mesh(n, expert=E, data=...)`
with fsdp=1.  Pairing expert parallelism with ZeRO-sharded dense params
(fsdp > 1) currently makes XLA bounce the residual's backward through a
full repartition (replicate-then-shard) — correct but slow; keep the
dense params expert-axis-replicated instead.

Beside it stands the serving path's layer, `DroplessMoE`: one chip's share
of an expert-parallel layer.  It is told which experts it holds and is
HANDED its routing, a token's expert ids and weights over all the experts:
by the `router` the model gives it (`LinearRouter`, the router of one
matrix: sigmoid scores, or a softmax over them; the k largest, normalised;
with a correction bias the k largest of score + bias, weighted by the
scores alone: `route_top_k`), or as `routed` arrays where the model routes
itself because its router reads more than the layer's own tokens
(models/zaya.py: an MLP router with a state carried from layer to layer,
one expert a token weighted by its probability, and an output that is no
expert).  It returns the held experts' part of the sum plus the shared
expert; no capacity, no drop, and what the absent experts would add is
another chip's.  Its sum, `grouped_experts`, has one meaning and two ways
to be computed, chosen by what the code can see (`expert_tile`: the
backend, the mesh, the dtype and the shapes; nothing a configuration
sets):

- a decode step's few tokens (no more than one block) on one TPU device
  are bound by the bytes of the experts they reach, so one Pallas call
  (`ops/pallas/grouped_experts.py`) streams those experts' weight tiles
  through a scalar-prefetched table of their rows, each expert once,
  against all the tokens, weighted by a [n_held, T] table of routing
  weights that is zero where a token did not choose the expert;
- anything else (a prefill's thousands of tokens, the CPU, a mesh, other
  dtypes or widths) sorts the token-expert pairs by expert and loops over
  the non-empty blocks of pairs, a block against its expert's weights.

Both count what they routed (`expert_tokens`, `touched`), and
`kernel_trips` says who multiplied.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def top_k_dispatch(probs: jax.Array, top_k: int, capacity: int):
    """GShard top-k routing.

    probs [B, S, E] (f32) -> (dispatch [B,S,E,C] 0/1, combine [B,S,E,C]).
    Selection is greedy per token (k rounds of argmax); capacity slots
    fill in (round, token) order; selected gates renormalize to sum 1.
    """
    b, s, e = probs.shape
    masks = []
    p = probs
    for _ in range(top_k):
        idx = jnp.argmax(p, axis=-1)                       # [B, S]
        mask = jax.nn.one_hot(idx, e, dtype=probs.dtype)   # [B, S, E]
        masks.append(mask)
        p = p * (1.0 - mask)
    gate_sum = sum((probs * m).sum(-1) for m in masks)     # [B, S]
    gate_sum = jnp.maximum(gate_sum, 1e-9)

    dispatch = jnp.zeros((b, s, e, capacity), probs.dtype)
    combine = jnp.zeros((b, s, e, capacity), probs.dtype)
    counts = jnp.zeros((b, 1, e), probs.dtype)             # slots used
    for mask in masks:
        pos = jnp.cumsum(mask, axis=1) - mask + counts     # [B, S, E]
        counts = counts + jnp.sum(mask, axis=1, keepdims=True)
        keep = mask * (pos < capacity)
        pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                                dtype=probs.dtype) * keep[..., None]
        gate = (probs * mask).sum(-1) / gate_sum           # [B, S]
        dispatch = dispatch + pos_oh
        combine = combine + pos_oh * gate[..., None, None]
    return dispatch, combine


def load_balancing_loss(probs: jax.Array, dispatch: jax.Array) -> jax.Array:
    """Switch-style aux loss: E * mean_prob_e . mean_assigned_frac_e."""
    e = probs.shape[-1]
    mean_prob = probs.mean(axis=(0, 1))                    # [E]
    assigned = dispatch.sum(-1).mean(axis=(0, 1))          # [E] (0/1 sums)
    return e * jnp.sum(mean_prob * assigned)


class MoEMLP(nn.Module):
    """Drop-in replacement for a dense (SwiGLU) MLP block."""
    dim: int
    ffn_dim: int
    n_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    mesh: Optional[Mesh] = None

    def _constrain(self, t: jax.Array, *axes) -> jax.Array:
        """Pin the expert-parallel layout of internal activations so XLA
        inserts all_to_alls instead of bouncing through a full
        replicate-then-repartition."""
        if self.mesh is None:
            return t
        sizes = {
            'expert': self.mesh.shape.get('expert', 1),
            ('dcn', 'data', 'fsdp'): (self.mesh.shape.get('dcn', 1) *
                                      self.mesh.shape.get('data', 1) *
                                      self.mesh.shape.get('fsdp', 1)),
        }
        for dim_idx, axis in enumerate(axes):
            need = sizes.get(axis)
            if need and t.shape[dim_idx] % need:
                return t    # tiny-shape fallback: skip the constraint
        return jax.lax.with_sharding_constraint(
            t, NamedSharding(self.mesh, P(*axes)))

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:          # [B, S, D]
        b, s, d = x.shape
        e = self.n_experts
        capacity = max(1, int(self.capacity_factor * s * self.top_k / e))

        # Router in f32: tiny compute, and routing decisions are the one
        # place bf16 noise visibly changes the computation graph.
        logits = nn.Dense(
            e, use_bias=False, dtype=jnp.float32,
            param_dtype=self.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ('embed', None)),
            name='router')(x.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)             # [B, S, E]
        dispatch, combine = top_k_dispatch(probs, self.top_k, capacity)
        self.sow('intermediates', 'moe_aux_loss',
                 load_balancing_loss(probs, dispatch))

        def expert_param(name, shape, logical):
            return self.param(
                name, nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), logical),
                shape, self.param_dtype).astype(self.dtype)

        # Expert weights shard over 'expert' (+'mlp'->tensor); the embed
        # dim stays unsharded — the E-way expert split already distributes
        # the params, and fsdp-sharding the contraction dim would make the
        # dispatch einsum's backward bounce through a full repartition.
        w_gate = expert_param('w_gate', (e, d, self.ffn_dim),
                              ('expert', None, 'mlp'))
        w_up = expert_param('w_up', (e, d, self.ffn_dim),
                            ('expert', None, 'mlp'))
        w_down = expert_param('w_down', (e, self.ffn_dim, d),
                              ('expert', 'mlp', None))

        xin = x.astype(self.dtype)
        disp = dispatch.astype(self.dtype)
        # dispatch: tokens -> per-expert capacity slots (all_to_all when
        # 'expert' is a real mesh axis)
        expert_in = jnp.einsum('bsec,bsd->ebcd', disp, xin)
        expert_in = self._constrain(expert_in, 'expert',
                                    ('dcn', 'data', 'fsdp'), None, None)
        h = (nn.silu(jnp.einsum('ebcd,edf->ebcf', expert_in, w_gate)) *
             jnp.einsum('ebcd,edf->ebcf', expert_in, w_up))
        h = self._constrain(h, 'expert', ('dcn', 'data', 'fsdp'), None,
                            'tensor')
        expert_out = jnp.einsum('ebcf,efd->ebcd', h, w_down)
        expert_out = self._constrain(expert_out, 'expert',
                                     ('dcn', 'data', 'fsdp'), None, None)
        # combine: slots -> tokens, weighted by renormalized gates
        out = jnp.einsum('ebcd,bsec->bsd', expert_out,
                         combine.astype(self.dtype))
        out = self._constrain(out, ('dcn', 'data', 'fsdp', 'expert'),
                              None, None)
        return out.astype(x.dtype)


# ----- dropless routing over a held share of the experts --------------------
def route_top_k(scores: jax.Array, top_k: int, scaling: float = 1.0,
                bias: Optional[jax.Array] = None):
    """scores [T, E] (f32) -> (expert ids [T, k], weights [T, k]): the k
    largest scores of each token, normalised to sum to 1, times
    `scaling`.  With a correction `bias` [E] (`topk_method` `noaux_tc`:
    a vector learned beside the router to even out the experts' load) the
    k are those with the largest `scores + bias`, and their weights are
    still their `scores`, normalised: the bias decides who is chosen and
    never how much a chosen expert counts.  Without one the program is
    the one it was."""
    if bias is None:
        top, idx = jax.lax.top_k(scores, top_k)
    else:
        _, idx = jax.lax.top_k(scores + bias, top_k)
        top = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, top / jnp.sum(top, axis=-1, keepdims=True) * scaling


def expert_tile(n_tokens: int, block: int, w_gate: jax.Array,
                mesh: Optional[Mesh] = None) -> Optional[int]:
    """The F columns one grid step of the decode kernel covers
    (`ops/pallas/grouped_experts.py`) for stacks like `w_gate`
    [n_held, D, F], or None where `grouped_experts` goes through the block
    loop: more tokens than one block, off the TPU, under a mesh of several
    devices (XLA cannot partition a Mosaic call), stacks that are not
    bf16, or widths the kernel's tiling cannot take."""
    if (n_tokens > block or w_gate.dtype != jnp.bfloat16 or
            jax.default_backend() != 'tpu' or
            (mesh is not None and mesh.size > 1)):
        return None
    from skypilot_tpu.ops.pallas import grouped_experts as pallas_ge
    return pallas_ge.tile_f(w_gate.shape[1], w_gate.shape[2],
                            w_gate.dtype.itemsize)


def grouped_experts(x: jax.Array, idx: jax.Array, weights: jax.Array,
                    local_of: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                    w_down: jax.Array, block: int,
                    mesh: Optional[Mesh] = None,
                    valid: Optional[jax.Array] = None):
    """sum over the HELD experts e of weight_e * SwiGLU_e(x), for tokens x
    [T, D] routed to `idx` [T, k] with `weights` [T, k].

    `local_of` [E] maps an expert's id to its row of the held stacks
    w_gate / w_up [n_held, D, F] and w_down [n_held, F, D], and to n_held
    where the expert lives elsewhere.  No capacity and no drop: every
    held expert that some token chose is multiplied, with every token
    that chose it.  An expert nobody chose costs nothing.  Who multiplies
    follows the shape (`expert_tile`): the few tokens of a decode step go
    through one kernel call that streams the reached experts' weights,
    anything else through the block loop.  `valid` [T] bool, where given,
    says which tokens are real: the pairs of the others (a padded
    prompt's rows past its length, which nothing reads) are counted as
    routed elsewhere and multiplied by nobody.

    Returns (out [T, D] float32, counts [n_held + 1] int32: the pairs of
    each held expert, and last the pairs routed elsewhere, and the
    experts the kernel multiplied: 0 where the loop did).
    """
    n_held = w_gate.shape[0]
    keys = local_of[idx]                                     # [T, k]
    if valid is not None:
        keys = jnp.where(valid[:, None], keys, n_held)
    one_hot = jax.nn.one_hot(keys.reshape(-1), n_held + 1, dtype=jnp.int32)
    counts = jnp.sum(one_hot, axis=0)
    tile = expert_tile(x.shape[0], block, w_gate, mesh)
    if tile is None:
        out = _block_loop(x, keys, weights, one_hot, counts, w_gate, w_up,
                          w_down, block)
        return out, counts, jnp.zeros((), jnp.int32)
    # One block an expert, all tokens in it: c[e, t] is token t's weight
    # for the expert of row e, zero where it did not choose it, and the
    # reached experts' rows stand first in `rows`.
    held_rows = jnp.arange(n_held)
    c = jnp.sum(jnp.where(keys[None] == held_rows[:, None, None],
                          weights[None], 0.0), axis=-1)      # [n_held, T]
    reached = counts[:n_held] > 0
    place = jnp.cumsum(reached) - 1
    rows = jnp.sum(jnp.where(reached[None, :] &
                             (place[None, :] == held_rows[:, None]),
                             held_rows[None, :], 0), axis=1)
    n_reached = jnp.sum(reached)
    from skypilot_tpu.ops.pallas import grouped_experts as pallas_ge
    out = pallas_ge.grouped_experts_fwd(x, c, rows, n_reached, w_gate, w_up,
                                        w_down, tile=tile)
    return out, counts, n_reached


def _block_loop(x, keys, weights, one_hot, counts, w_gate, w_up, w_down,
                block: int):
    """`grouped_experts` for any number of tokens: the token-expert pairs
    are sorted by held expert, and a loop whose trip count is the number
    of non-empty blocks of `block` pairs multiplies each block by its own
    expert's weights.  An expert that every token chose takes T / block
    blocks.  `one_hot` [T * k, n_held + 1] and `counts` are `keys`'."""
    t, k = keys.shape
    n_held = w_gate.shape[0]
    m = t * k
    keys = keys.reshape(m)
    # The pairs by held expert: a counting sort (a pair's place is its
    # expert's start plus its rank among that expert's pairs).  A
    # comparison sort of 262,144 keys takes the TPU compiler 16 s a
    # program.
    rank = jnp.cumsum(one_hot, axis=0) - one_hot
    ends = jnp.cumsum(counts)
    starts = ends - counts
    place = starts[keys] + jnp.take_along_axis(rank, keys[:, None],
                                               axis=1)[:, 0]
    order = jnp.zeros((m,), jnp.int32).at[place].set(
        jnp.arange(m, dtype=jnp.int32), unique_indices=True)
    n_blocks = (counts[:n_held] + block - 1) // block
    block_ends = jnp.cumsum(n_blocks)
    flat_w = weights.reshape(m)

    def body(b, acc):
        e = jnp.sum(block_ends <= b)                 # this block's expert
        first = starts[e] + (b - (block_ends[e] - n_blocks[e])) * block
        rows = first + jnp.arange(block)
        live = rows < ends[e]
        pair = order[jnp.minimum(rows, m - 1)]
        tok = pair // k
        xb = x[tok]                                  # [block, D]
        gate = xb @ jax.lax.dynamic_index_in_dim(w_gate, e, 0, False)
        up = xb @ jax.lax.dynamic_index_in_dim(w_up, e, 0, False)
        y = (nn.silu(gate) * up) @ jax.lax.dynamic_index_in_dim(
            w_down, e, 0, False)
        y = jnp.where(live[:, None],
                      y.astype(jnp.float32) * flat_w[pair][:, None], 0.0)
        return acc.at[tok].add(y)

    return jax.lax.fori_loop(0, block_ends[-1], body,
                             jnp.zeros(x.shape, jnp.float32))


@dataclasses.dataclass(frozen=True)
class LinearRouter:
    """The router of one matrix, as a step `DroplessMoE` is handed: scores
    of ALL the layer's experts (float32; `scoring` 'sigmoid', each expert
    on its own, or 'softmax' over all of them), each token's `top_k`
    largest, weighted by score / sum of the k times `scaling` (`bias`: the
    largest of score + the parameter `correction_bias` [n_experts]; the
    weights stay the scores').  Its parameters, `router` [dim, n_experts]
    and `correction_bias`, are the layer's own."""
    top_k: int = 8
    scoring: str = 'sigmoid'    # or 'softmax' over all the experts
    bias: bool = False          # choose by score + a learned bias
    scaling: float = 1.0

    def __call__(self, layer: 'DroplessMoE', flat: jax.Array):
        """flat [T, dim] -> (expert ids [T, k], weights [T, k])."""
        # Router in float32 at full precision: a choice flipped by
        # rounding changes which weights a token meets.
        router = layer.param('router', nn.initializers.lecun_normal(),
                             (layer.dim, layer.n_experts), layer.param_dtype)
        score = {'sigmoid': jax.nn.sigmoid,
                 'softmax': lambda z: jax.nn.softmax(z, axis=-1)}[
                     self.scoring]
        scores = score(jnp.dot(
            flat.astype(jnp.float32), router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        bias = layer.param('correction_bias', nn.initializers.zeros,
                           (layer.n_experts,), layer.param_dtype).astype(
                               jnp.float32) if self.bias else None
        return route_top_k(scores, self.top_k, self.scaling, bias)


class DroplessMoE(nn.Module):
    """An expert layer that is told which experts it holds and is handed
    its routing.

    Who routes is the model's: `router` is a callable `(this layer, flat
    tokens [T, dim]) -> (expert ids [T, k], weights [T, k])` over ALL
    `n_experts` (`LinearRouter`, whose parameters are this layer's), or
    the model routes before the call and hands the two arrays as `routed`
    (a router with inputs of its own).  Both ways stay: `LinearRouter`'s
    matrix and bias are parameters of THIS layer (`<layer>/moe/router`),
    where the four families' checkpoints and seeded trees have them, so a
    model that routed before the call would move them in the tree; and a
    router that reads another layer's state cannot be a step of this one.
    This module holds the experts
    `held` (ids into the n_experts) and returns their part of the result,
    `sum over held e of w_e E_e(x)`, plus the shared expert: what one chip
    of an expert-parallel group computes before the exchange.  What the
    absent experts would add is left out; nothing here stands in for the
    other chips.  With `held` = all experts it is the whole layer.  No
    token is ever dropped (`grouped_experts`).

    An id from `n_experts` up (`n_skip` of them) is no expert: a router
    with such outputs sends a token past the layer's experts.  Its pairs
    are multiplied by nobody and counted as neither held nor elsewhere;
    what the token gets in the experts' place is the model's to add.

    Under `mutable=['stats']` it sows, a call: `expert_tokens` [n_held +
    1] (pairs of each held expert, then pairs routed elsewhere), `touched`
    (held experts with at least one token), `kernel_trips` (those of
    them that the decode kernel multiplied: `touched` where it runs, 0
    where the block loop does) and, with `n_skip`, `skipped` (pairs sent
    past the experts).
    """
    dim: int
    ffn_dim: int
    n_experts: int
    held: tuple
    router: Optional[Callable] = None   # None: `__call__` takes `routed`
    n_shared: int = 1
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    block: int = 256            # pairs a trip of the expert loop
    mesh: Optional[Mesh] = None
    n_skip: int = 0             # router outputs past n_experts: no expert

    @nn.compact
    def __call__(self, x: jax.Array, valid: Optional[jax.Array] = None,
                 routed: Optional[Tuple[jax.Array, jax.Array]] = None
                 ) -> jax.Array:
        """x [B, S, D]; `valid` [B, S] bool (a padded prefill): the rows
        that are real, None for all of them (`grouped_experts`); `routed`
        (ids [B * S, k], weights [B * S, k]) where the model has routed."""
        b, s, d = x.shape
        n_held = len(self.held)
        flat = x.reshape(b * s, d)
        if (routed is None) == (self.router is None):
            raise ValueError('DroplessMoE is handed its routing once: by '
                             'its `router`, or as `routed`')
        idx, weights = routed if routed is not None else \
            self.router(self, flat)

        def stack(name, shape):
            return self.param(name, nn.initializers.lecun_normal(),
                              (n_held,) + shape,
                              self.param_dtype).astype(self.dtype)

        # An id held elsewhere, and one that is no expert, go to the row
        # past the held stacks: multiplied by nobody.
        local_of = np.full((self.n_experts + self.n_skip,), n_held, np.int32)
        local_of[list(self.held)] = np.arange(n_held)
        xin = flat.astype(self.dtype)
        stacks = (stack('w_gate', (d, self.ffn_dim)),
                  stack('w_up', (d, self.ffn_dim)),
                  stack('w_down', (self.ffn_dim, d)))
        out, counts, kernel_trips = grouped_experts(
            xin, idx, weights, jnp.asarray(local_of), *stacks,
            min(self.block, -(-(b * s) // 8) * 8), self.mesh,
            None if valid is None else valid.reshape(b * s))
        if self.n_skip:
            past = idx >= self.n_experts
            if valid is not None:
                past = past & valid.reshape(b * s, 1)
            skipped = jnp.sum(past.astype(jnp.int32))
            counts = counts.at[n_held].add(-skipped)
            self.sow('stats', 'skipped', skipped)
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


def publish_routing(held: tuple, expert_tokens, touched, kernel_trips,
                    skipped=0) -> None:
    """One fetch's routing counts, to the /metrics registry (host side;
    `expert_tokens` [n_held + 1], `touched`, `kernel_trips` and, of a
    router with an output that is no expert, `skipped` as sown, summed
    over the layers and steps of the fetch)."""
    from skypilot_tpu.server import metrics as metrics_lib
    n_held = len(held)
    if skipped:
        metrics_lib.inc_counter('skytpu_moe_skipped_pairs_total',
                                float(skipped))
    metrics_lib.inc_counter('skytpu_moe_pairs_total',
                            float(expert_tokens[:n_held].sum()), where='held')
    metrics_lib.inc_counter('skytpu_moe_pairs_total',
                            float(expert_tokens[n_held]), where='elsewhere')
    metrics_lib.inc_counter('skytpu_moe_experts_touched_total',
                            float(touched))
    metrics_lib.inc_counter('skytpu_moe_expert_trips_total',
                            float(kernel_trips), path='kernel')
    metrics_lib.inc_counter('skytpu_moe_expert_trips_total',
                            float(touched - kernel_trips), path='loop')
    for e, n in zip(held, expert_tokens[:n_held]):
        if n:
            metrics_lib.inc_counter('skytpu_moe_expert_tokens_total',
                                    float(n), expert=str(e))


def publish_stats(held: tuple, stats):
    """A decode call's summed `stats` collection (host arrays; every entry
    a layer whose `moe` holds what `DroplessMoE` sows), to the /metrics
    registry: the expert layers' counts added up, one update, and the
    pairs a router with a skip output sent past the experts under a
    counter of their own.  Returns the summed `expert_tokens`."""
    layers = [layer['moe'] for layer in stats.values()]
    expert_tokens = sum(moe['expert_tokens'][0] for moe in layers)
    publish_routing(held, expert_tokens,
                    sum(moe['touched'][0] for moe in layers),
                    sum(moe['kernel_trips'][0] for moe in layers),
                    sum(moe['skipped'][0] for moe in layers
                        if 'skipped' in moe))
    return expert_tokens
