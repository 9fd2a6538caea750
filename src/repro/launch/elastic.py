"""Elastic re-scaling: rebuild the mesh from surviving devices and re-shard.

On-disk checkpoints are sharding-agnostic (checkpoint/manager.py stores
plain arrays), so scaling from N to M chips is:

  1. pick the largest (data', model') grid that divides the survivors
     (TP degree is kept if possible — model-parallel degree changes need
     the same weight layout, only FSDP/data degree is truly elastic),
  2. rebuild mesh + shardings from the same rules (parallel/sharding.py),
  3. restore the checkpoint with the *new* shardings (device_put does the
     re-shard on load),
  4. re-scale microbatching so the global batch is preserved.

At 1000+-node scale the same flow runs per-host against the sharded
checkpoint index; only step 1 differs (scheduler reports the survivor set).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from repro.launch.mesh import make_mesh
from repro.parallel.context import ParallelContext


@dataclasses.dataclass
class ElasticPlan:
    mesh_shape: Tuple[int, ...]
    axis_names: Tuple[str, ...]
    n_devices: int
    data_degree: int
    model_degree: int
    n_microbatches: int


def plan_mesh(n_devices: int, model_degree: int, global_batch: int,
              per_shard_batch: int = 1,
              prefer_model: Optional[int] = None) -> ElasticPlan:
    """Choose (data, model) for a (possibly reduced) device count."""
    model = prefer_model or model_degree
    while model > 1 and n_devices % model != 0:
        model //= 2
    data = n_devices // model
    # keep global batch fixed: microbatches absorb the lost data degree
    mb = max(1, global_batch // max(data * per_shard_batch, 1))
    return ElasticPlan(mesh_shape=(data, model), axis_names=("data", "model"),
                       n_devices=n_devices, data_degree=data,
                       model_degree=model, n_microbatches=mb)


def build(plan: ElasticPlan):
    mesh = make_mesh(plan.mesh_shape, plan.axis_names)
    ctx = ParallelContext(mesh=mesh, batch_axes=("data",))
    return mesh, ctx


def reshard_restore(manager, step: int, like_tree, shardings):
    """Restore a checkpoint under *new* shardings (elastic reload)."""
    return manager.restore(step, like_tree, shardings)
