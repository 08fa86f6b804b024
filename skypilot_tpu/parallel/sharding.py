"""Logical-axis sharding rules (scaling-book style).

Models annotate parameters/activations with *logical* axis names; these rules
map them onto mesh axes.  Changing the parallelism strategy = changing the
rules, not the model.  This is the design the reference cannot express (its
strategies are frozen into per-recipe torchrun flags, SURVEY.md §2.15).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axis (or None = replicated).
# 'embed' shards over fsdp (ZeRO-3-style param sharding); 'mlp'/'heads'
# shard over tensor; 'batch' over (data, fsdp); 'seq' over fsdp for
# context parallelism (ring attention).
DEFAULT_RULES: Tuple[Tuple[str, Optional[object]], ...] = (
    # 'dcn' leads the batch group: on multislice clusters the batch is
    # split across slices first (pure DP over DCN — gradient all-reduce
    # is the only collective that crosses the inter-slice network).
    ('batch', ('dcn', 'data', 'fsdp', 'expert')),
    ('seq', None),
    ('embed', 'fsdp'),
    ('mlp', 'tensor'),
    ('heads', 'tensor'),
    ('kv', None),
    ('vocab', 'tensor'),
    # MoE experts shard over their own mesh axis; tokens are sharded over
    # it too (batch rule above), so the dispatch/combine einsums become
    # all_to_alls under pjit.  Non-MoE params ignore the axis (replicated
    # over it) and their grads all-reduce across it automatically.
    ('expert', 'expert'),
    ('conv_in', None),
    ('conv_out', 'tensor'),
)


def rules_to_dict(rules: Sequence[Tuple[str, Optional[object]]]) -> dict:
    return dict(rules)


def logical_to_spec(logical_axes: Sequence[Optional[str]],
                    rules: Optional[Sequence] = None) -> P:
    """('embed', 'mlp') -> PartitionSpec('fsdp', 'tensor')."""
    table = rules_to_dict(rules or DEFAULT_RULES)
    return P(*[table.get(a) if a is not None else None
               for a in logical_axes])


def named_sharding(mesh: Mesh, *logical_axes: Optional[str],
                   rules: Optional[Sequence] = None) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(logical_axes, rules))


def tree_shardings(mesh: Mesh, logical_tree,
                   rules: Optional[Sequence] = None):
    """Map a pytree of logical-axis tuples to NamedShardings."""
    return jax.tree.map(
        lambda axes: NamedSharding(mesh, logical_to_spec(axes, rules)),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(a, (str, type(None))) for a in x))


# The mesh axes a batch's rows divide over (dcn = inter-slice DP on
# multislice clusters; the expert axis doubles as data parallelism in
# non-MoE layers).
BATCH_AXES = ('dcn', 'data', 'fsdp', 'expert')


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for (batch, ...) input arrays: batch over `BATCH_AXES`."""
    return NamedSharding(mesh, P(BATCH_AXES))
