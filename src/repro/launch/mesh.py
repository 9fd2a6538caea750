"""Production meshes.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the "pod" axis composes
with "data" for batch/FSDP sharding (DCN-friendly: only gradient/FSDP
traffic crosses pods, TP stays inside a pod's ICI domain).

Functions, not module constants — importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax initialization).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.parallel.context import ParallelContext


def make_mesh(shape, axes, *, devices=None):
    """Every mesh of the repo: ``Auto`` axes, so sharding constraints and
    jit place arrays by the mesh without each call site naming it
    (``jax.make_mesh`` defaults to ``Explicit`` axes)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_context(mesh) -> ParallelContext:
    axes = mesh.axis_names
    batch_axes = tuple(a for a in axes if a in ("pod", "data"))
    return ParallelContext(mesh=mesh, batch_axes=batch_axes,
                           model_axis="model")


def make_test_mesh(data: int = 2, model: int = 4):
    """Small host-device mesh for sharding tests (needs
    --xla_force_host_platform_device_count >= data*model)."""
    return make_mesh((data, model), ("data", "model"))
