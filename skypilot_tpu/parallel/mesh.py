"""Device-mesh construction for TPU slices.

Axes (scaling-book recipe: pick a mesh, annotate shardings, let XLA insert
collectives):

- ``dcn``     — inter-slice data parallelism for MULTISLICE clusters
                (outermost: crosses the DCN network between ICI slices, so
                only bandwidth-light gradient all-reduces ride it; size =
                number of slices, 1 on single-slice clusters)
- ``pipeline`` — GPipe-style stage parallelism (stage hops are
                 point-to-point, the other pattern that tolerates DCN)
- ``data``    — pure data parallelism (gradient all-reduce over ICI/DCN)
- ``fsdp``    — data parallelism with fully-sharded params (ZeRO-3 style);
                also the context-parallel axis for ring attention (sequence
                shards travel around this axis's ring)
- ``expert``  — MoE expert parallelism (experts sharded, tokens all_to_all
                dispatched); doubles as a data axis for non-MoE layers
- ``tensor``  — megatron-style tensor parallelism inside a layer
                (innermost: needs the fastest ICI links)

The TPU ICI torus favors meshes whose fastest-varying axis maps to
physically adjacent chips; `jax.sharding.Mesh` over `jax.devices()` already
uses the slice's physical order, so we only choose axis *sizes* here.
Reference parity: this replaces the reference's env-var plumbing into
torchrun/NCCL (SURVEY.md §2.15) with an actual mesh object the model and
train step consume.
"""
from __future__ import annotations

import dataclasses
import math
import os
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh

MESH_AXES = ('dcn', 'pipeline', 'data', 'fsdp', 'expert', 'tensor')


def mesh_axes() -> Tuple[str, ...]:
    return MESH_AXES


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """Chosen parallelism degrees; product must equal device count.
    (Field order keeps the historical positional form
    MeshPlan(data, fsdp, tensor); expert/pipeline are keyword-new.)"""
    data: int = 1
    fsdp: int = 1
    tensor: int = 1
    expert: int = 1
    pipeline: int = 1
    dcn: int = 1

    @property
    def num_devices(self) -> int:
        return (self.data * self.fsdp * self.tensor * self.expert *
                self.pipeline * self.dcn)

    def validate(self, n_devices: int) -> None:
        if self.num_devices != n_devices:
            raise ValueError(
                f'Mesh plan {self} uses {self.num_devices} devices, but '
                f'{n_devices} are available.')


def plan_mesh(n_devices: int,
              data: Optional[int] = None,
              fsdp: Optional[int] = None,
              tensor: Optional[int] = None,
              expert: Optional[int] = None,
              pipeline: Optional[int] = None,
              dcn: Optional[int] = None) -> MeshPlan:
    """Fill in unset axis sizes.

    Policy (matches common TPU practice): tensor/expert/pipeline
    parallelism only when asked; remaining devices default to ``fsdp``,
    which composes with context parallelism and keeps HBM headroom for
    large models.  `data` absorbs what the caller pins.  ``dcn`` defaults
    to SKYTPU_NUM_SLICES (injected per host by the gang executor on
    multislice clusters) so inter-slice data parallelism is automatic;
    the per-slice axes then divide the per-slice devices.
    """
    if dcn is None:
        # On a multislice cluster the gang executor injects
        # SKYTPU_NUM_SLICES (parallel/distributed.py); default the dcn
        # axis to it so plan_mesh(jax.device_count()) does the right
        # thing without the user threading the slice count through.
        env_slices = int(os.environ.get('SKYTPU_NUM_SLICES', '1'))
        if env_slices > 1:
            if n_devices % env_slices != 0:
                raise ValueError(
                    f'SKYTPU_NUM_SLICES={env_slices} does not divide the '
                    f'device count {n_devices}; pass dcn= explicitly.')
            dcn = env_slices
    known = {'data': data, 'fsdp': fsdp, 'tensor': tensor,
             'expert': expert, 'pipeline': pipeline, 'dcn': dcn}
    fixed = {k: v for k, v in known.items() if v is not None}
    prod = math.prod(fixed.values()) if fixed else 1
    if n_devices % max(prod, 1) != 0:
        raise ValueError(
            f'Pinned axes {fixed} do not divide device count {n_devices}.')
    free = n_devices // max(prod, 1)
    if 'fsdp' not in fixed:
        fixed['fsdp'] = fixed.get('fsdp', 1) * free
        free = 1
    elif 'data' not in fixed:
        fixed['data'] = fixed.get('data', 1) * free
        free = 1
    if free != 1:
        # All axes pinned but don't multiply out — validate() catches.
        pass
    plan = MeshPlan(data=fixed.get('data', 1),
                    fsdp=fixed.get('fsdp', 1),
                    tensor=fixed.get('tensor', 1),
                    expert=fixed.get('expert', 1),
                    pipeline=fixed.get('pipeline', 1),
                    dcn=fixed.get('dcn', 1))
    plan.validate(n_devices)
    return plan


def validate_tensor_parallel(tensor: int,
                             n_heads: Optional[int] = None,
                             n_kv_heads: Optional[int] = None) -> None:
    """Reject a tensor degree the model's head layout cannot shard.

    Tensor parallelism splits attention over heads: `tensor` must divide
    `n_heads` (query heads) and, under GQA, `n_kv_heads` as well — the
    per-layer KV cache shards over kv heads, and a non-dividing degree
    would leave some chip with a fractional head.  (Replicating KV under
    an over-wide degree is possible but silently wastes the HBM the user
    went multi-chip to get; make them pick a degree that fits.)
    """
    if n_heads is not None and n_heads % tensor != 0:
        raise ValueError(
            f'tensor={tensor} does not divide n_heads={n_heads}; '
            f'attention shards over query heads')
    if n_kv_heads is not None and n_kv_heads % tensor != 0:
        raise ValueError(
            f'tensor={tensor} does not divide n_kv_heads={n_kv_heads} '
            f'(GQA): the KV cache shards over kv heads — pick a tensor '
            f'degree that divides both head counts')


def plan_serve_mesh(n_devices: int,
                    tensor: Optional[int] = None,
                    n_heads: Optional[int] = None,
                    n_kv_heads: Optional[int] = None) -> MeshPlan:
    """Mesh plan for a SERVE replica: tensor parallelism only.

    Unlike `plan_mesh` (training), leftover devices go to `data` (pure
    replication for the decode batch) rather than fsdp, `tensor`
    defaults to the whole device set (decode is bandwidth-bound — every
    chip's HBM should hold a weight shard), and the `dcn` axis is NEVER
    inherited from SKYTPU_NUM_SLICES: a serve replica is per-slice by
    construction (the service load balancer, not DCN collectives,
    spreads traffic across slices).
    """
    tensor = int(n_devices if tensor is None else tensor)
    if tensor < 1 or n_devices % tensor != 0:
        raise ValueError(
            f'tensor={tensor} must be >= 1 and divide the serve '
            f'replica\'s device count {n_devices}')
    validate_tensor_parallel(tensor, n_heads=n_heads, n_kv_heads=n_kv_heads)
    return MeshPlan(data=n_devices // tensor, tensor=tensor)


def build_serve_mesh(tensor: int,
                     n_heads: Optional[int] = None,
                     n_kv_heads: Optional[int] = None,
                     devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Mesh for a tensor-parallel serve engine over the first `tensor`
    devices (jax.devices() order follows the ICI torus, so adjacent
    chips land on the tensor axis — the axis that rides every matmul)."""
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < tensor:
        raise ValueError(
            f'tensor={tensor} needs {tensor} devices, have {len(devices)}')
    plan = plan_serve_mesh(tensor, tensor=tensor, n_heads=n_heads,
                           n_kv_heads=n_kv_heads)
    return build_mesh(plan, devices[:tensor])


def build_mesh(plan: Optional[MeshPlan] = None,
               devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Construct the Mesh.  Device order is `jax.devices()` order, which on a
    TPU slice follows the physical ICI torus — the last mesh axis varies
    fastest, so put the most communication-hungry axis (`tensor`) last and
    the point-to-point-only axis (`pipeline`) first."""
    devices = list(devices if devices is not None else jax.devices())
    if plan is None:
        plan = plan_mesh(len(devices))
    plan.validate(len(devices))
    import numpy as np
    # dcn outermost: jax.devices() enumerates slice 0's devices first, so
    # splitting on the leading axis puts each slice's devices into one dcn
    # coordinate — per-slice axes stay on ICI, only dcn crosses slices.
    dev_array = np.array(devices).reshape(plan.dcn, plan.pipeline,
                                          plan.data, plan.fsdp,
                                          plan.expert, plan.tensor)
    return Mesh(dev_array, MESH_AXES)
