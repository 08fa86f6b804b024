"""Compile-only placement validation against abstract TPU topologies.

TPU-first, greenfield (no reference analog): before any quota is spent,
AOT-lower the full sharded train step against a PJRT *topology
description* of the target slice — e.g. a v5p-256 you do not have — and
report the per-device HBM footprint and any involuntary-rematerialization
warnings.  ``jax.experimental.topologies.get_topology_desc`` gives
abstract devices for any TPU shape; the real TPU compiler then compiles
for that target without hardware, and ``compiled.memory_analysis()``
yields per-device byte counts.

Two tiers:
- analytic (instant): exact sharded parameter + optimizer-state + gradient
  bytes from eval_shape'd shapes, plus a transformer activation estimate —
  catches clearly-OOM plans (a 70B on v5e-8) without invoking a compiler;
- compiled (seconds..minutes): the XLA answer, exact temps included.

The multichip dryrun (__graft_entry__.py) proves plans *execute* on a
virtual CPU mesh; this proves they *fit* on the real target's HBM.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Dict, List, Optional

from skypilot_tpu import accelerators as acc_lib
from skypilot_tpu import exceptions

# Canonical generation name -> PJRT topology platform prefix.
_TOPO_PREFIX = {
    'v2': 'v2', 'v3': 'v3', 'v4': 'v4', 'v5p': 'v5p',
    'v5litepod': 'v5e', 'v6e': 'v6e',
}

# Fraction of a chip's HBM usable by the program (the rest is runtime
# reserve — libtpu, collectives scratch; matches what we observe on v5e:
# 15.75 of 16 GB visible, minus framework overhead).
_USABLE_HBM_FRACTION = 0.92


@dataclasses.dataclass
class PlacementReport:
    accelerator: str
    mesh_plan: 'object'                    # parallel.mesh.MeshPlan
    per_device_bytes: int                  # peak per-device HBM estimate
    hbm_bytes_per_device: int
    fits: bool
    mode: str                              # 'analytic' | 'compiled'
    breakdown: Dict[str, int]
    warnings: List[str]

    @property
    def utilization(self) -> float:
        usable = self.hbm_bytes_per_device * _USABLE_HBM_FRACTION
        return self.per_device_bytes / max(usable, 1)

    def summary(self) -> str:
        gb = 1024 ** 3
        lines = [
            f'placement: {self.accelerator}  plan={self.mesh_plan}',
            f'per-device HBM: {self.per_device_bytes / gb:.2f} GiB of '
            f'{self.hbm_bytes_per_device / gb:.2f} GiB '
            f'({self.utilization:.0%} of usable)  [{self.mode}]',
        ]
        for k, v in sorted(self.breakdown.items()):
            lines.append(f'  {k}: {v / gb:.2f} GiB')
        for w in self.warnings:
            lines.append(f'  WARNING: {w}')
        lines.append('FITS' if self.fits else 'DOES NOT FIT')
        return '\n'.join(lines)


def topology_for(accelerator: str):
    """Abstract PJRT topology for a TPU accelerator string (no hardware
    needed; requires libtpu, which ships with jax[tpu])."""
    from jax.experimental import topologies
    tpu = acc_lib.parse_tpu(accelerator)
    prefix = _TOPO_PREFIX.get(tpu.generation)
    if prefix is None:
        raise exceptions.InvalidAcceleratorError(
            f'No topology mapping for generation {tpu.generation!r}')
    dims = 'x'.join(str(d) for d in tpu.default_topology())
    return topologies.get_topology_desc(platform='tpu',
                                        topology_name=f'{prefix}:{dims}')


def _abstract_state(model, mesh, rng_shape_tokens, rules=None):
    """(abstract TrainState shapes, shardings) without materializing."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from skypilot_tpu.parallel import sharding as sharding_lib
    from skypilot_tpu.train.trainer import (TrainConfig, TrainState,
                                            make_optimizer)
    rules = list(rules or sharding_lib.DEFAULT_RULES)
    tx = make_optimizer(TrainConfig())

    def create(rng) -> TrainState:
        variables = model.init(rng, rng_shape_tokens)
        return TrainState.create(apply_fn=model.apply,
                                 params=variables['params'], tx=tx)

    # The rng rides eval_shape as an ABSTRACT value: analytic validation
    # must never materialize anything (no backend may even exist).
    abstract = jax.eval_shape(
        create, jax.ShapeDtypeStruct((2,), jnp.uint32))
    logical_specs = nn.get_partition_spec(abstract)
    shardings = nn.logical_to_mesh_sharding(logical_specs, mesh, rules)
    return (nn.meta.unbox(abstract), nn.meta.unbox(shardings))


def _sharded_bytes(abstract, shardings, mesh) -> int:
    """Total bytes of the LARGEST per-device shard across the pytree."""
    import jax
    import numpy as np

    def shard_bytes(sds, sharding):
        shape = sds.shape
        spec = sharding.spec if hasattr(sharding, 'spec') else None
        per = np.prod(shape, dtype=np.int64) if shape else 1
        if spec is not None:
            for dim, axes in enumerate(spec):
                if axes is None or dim >= len(shape):
                    continue
                axes = axes if isinstance(axes, tuple) else (axes,)
                factor = int(np.prod([mesh.shape[a] for a in axes]))
                per //= max(factor, 1)
        return int(per) * sds.dtype.itemsize

    total = 0
    for sds, sh in zip(jax.tree.leaves(abstract),
                       jax.tree.leaves(shardings, is_leaf=lambda x:
                                       hasattr(x, 'spec'))):
        total += shard_bytes(sds, sh)
    return total


def validate_placement(accelerator: str,
                       model_name: str = 'llama3-8b',
                       batch: int = 8,
                       seq: int = 2048,
                       data: Optional[int] = None,
                       fsdp: Optional[int] = None,
                       tensor: Optional[int] = None,
                       compile: bool = False,  # pylint: disable=redefined-builtin
                       remat: bool = True) -> PlacementReport:
    """Validate that a train-step placement fits the target slice's HBM.

    analytic mode (default): exact sharded param/optimizer/gradient bytes
    + a transformer activation estimate.  ``compile=True`` additionally
    runs the real TPU compiler against the abstract topology and uses
    XLA's own memory analysis (and surfaces rematerialization warnings).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from skypilot_tpu.models.llama import Llama, LLAMA_CONFIGS
    from skypilot_tpu.parallel.mesh import build_mesh, plan_mesh

    tpu = acc_lib.parse_tpu(accelerator)
    n_devices = tpu.num_chips * tpu.num_slices
    hbm = int(tpu.gen.hbm_gb_per_chip * 1024 ** 3)
    if model_name not in LLAMA_CONFIGS:
        raise exceptions.InvalidRequestError(
            f'unknown model {model_name!r}; known: '
            f'{sorted(LLAMA_CONFIGS)}')
    cfg = LLAMA_CONFIGS[model_name]
    plan = plan_mesh(n_devices, data=data, fsdp=fsdp, tensor=tensor,
                     dcn=tpu.num_slices if tpu.num_slices > 1 else None)

    warnings: List[str] = []
    breakdown: Dict[str, int] = {}

    if compile:
        topo = topology_for(accelerator)
        mesh = build_mesh(plan, np.array(topo.devices))
    else:
        # Analytic mode needs only axis SIZES; an AbstractMesh avoids
        # touching any backend.
        from jax.sharding import AbstractMesh
        from skypilot_tpu.parallel.mesh import MESH_AXES
        mesh = AbstractMesh(
            tuple(getattr(plan, a) for a in MESH_AXES), MESH_AXES)

    model = Llama(cfg, mesh if compile else None)
    tokens_sds = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    abstract, shardings = _abstract_state(model, mesh, tokens_sds)

    state_bytes = _sharded_bytes(abstract, shardings, mesh)
    breakdown['params+optimizer_state'] = state_bytes

    # The step's temporaries at its fullest moment (a chunk of logits at
    # the loss, or the gradients that exist by a block's backward pass
    # beside its working set) with the named activations the blocks
    # keep: the trainer's own count (train/trainer.py activation_budget
    # spends what this leaves).
    params_bytes = _sharded_bytes(abstract.params, shardings.params, mesh)
    from skypilot_tpu.models.llama import keep_plan
    from skypilot_tpu.train.trainer import step_temporary_bytes
    act_bytes = step_temporary_bytes(
        cfg, mesh, batch, seq, params_bytes,
        keep_plan(dataclasses.replace(cfg, remat=remat), mesh, batch, seq))
    breakdown['step_temporaries_est'] = act_bytes

    if compile:
        from skypilot_tpu.train.trainer import make_sharded_train_step
        step = make_sharded_train_step(mesh, shardings)
        records: List[logging.LogRecord] = []

        class _Capture(logging.Handler):
            def emit(self, record):
                records.append(record)

        handler = _Capture()
        logging.getLogger('jax').addHandler(handler)
        try:
            compiled = step.lower(abstract, tokens_sds).compile()
        finally:
            logging.getLogger('jax').removeHandler(handler)
        for rec in records:
            msg = rec.getMessage()
            if 'rematerialization' in msg.lower():
                warnings.append(msg[:300])
        ma = compiled.memory_analysis()
        breakdown['xla_arguments'] = int(ma.argument_size_in_bytes)
        breakdown['xla_temps'] = int(ma.temp_size_in_bytes)
        # Donated outputs alias arguments; peak = args + temps.
        per_device = int(ma.argument_size_in_bytes +
                         ma.temp_size_in_bytes)
        mode = 'compiled'
    else:
        per_device = state_bytes + act_bytes
        mode = 'analytic'

    fits = per_device <= hbm * _USABLE_HBM_FRACTION
    return PlacementReport(accelerator=accelerator, mesh_plan=plan,
                           per_device_bytes=per_device,
                           hbm_bytes_per_device=hbm, fits=fits,
                           mode=mode, breakdown=breakdown,
                           warnings=warnings)
