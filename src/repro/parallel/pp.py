"""Optional GPipe pipeline parallelism over the ``pod`` axis (DESIGN.md §5).

With 2 pods the default layout (FSDP over pod×data) wins — DCN crossings
carry only gradient/FSDP traffic once per step.  PP becomes interesting at
4+ pods or when per-pod HBM can't hold the FSDP shard; it is provided as a
composable alternative, off by default.

Schedule: classic GPipe fill-drain over ``n_stages`` stages.  Each mesh
shard along the PP axis holds one stage's layer slice (stacked params
sharded on their leading layer dim); activations hop stages with
``ppermute``; microbatches stream through; every stage runs its layers with
the usual scan.  Bubble fraction = (S-1)/(M+S-1).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(block_fn: Callable, stage_params, x_microbatches,
                   *, mesh, axis: str = "pod"):
    """Run a stack of identical blocks as a pipeline.

    block_fn(params_slice, x) -> x        one stage's computation
    stage_params: pytree with leading dim n_stages, sharded P(axis, ...)
    x_microbatches: (n_mb, mb, ...) activations (replicated over `axis`)

    Returns (n_mb, mb, ...) outputs (replicated over `axis`).
    """
    n_stages = mesh.shape[axis]
    n_mb = x_microbatches.shape[0]
    perm_fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def shard_body(params_local, xs):
        params_local = jax.tree.map(lambda t: t[0], params_local)
        stage = jax.lax.axis_index(axis)
        t_total = n_mb + n_stages - 1
        buf = jnp.zeros_like(xs[0])                      # inter-stage buffer
        outs = jnp.zeros_like(xs)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 injects microbatch t; others consume the permuted buf
            mb_idx = jnp.clip(t, 0, n_mb - 1)
            inject = jax.lax.dynamic_index_in_dim(xs, mb_idx, 0,
                                                  keepdims=False)
            x_in = jnp.where(stage == 0, inject, buf)
            active = (t - stage >= 0) & (t - stage < n_mb)
            y = block_fn(params_local, x_in)
            y = jnp.where(active, y, buf)
            # last stage writes its finished microbatch t - (S-1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_mb - 1)
            write = active & (stage == n_stages - 1)
            upd = jnp.where(write, y, jax.lax.dynamic_index_in_dim(
                outs, out_idx, 0, keepdims=False))
            outs = jax.lax.dynamic_update_index_in_dim(outs, upd, out_idx, 0)
            buf = jax.lax.ppermute(y, axis, perm_fwd)
            return buf, outs

        buf, outs = jax.lax.fori_loop(0, t_total, tick, (buf, outs))
        # replicate results: only the last stage holds them — psum-broadcast.
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs

    pspec = jax.tree.map(lambda _: P(axis), stage_params)
    return jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(pspec, P()), out_specs=P(),
        check_vma=False,
    )(stage_params, x_microbatches)
