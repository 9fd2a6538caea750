"""Int8 gradient all-reduce with error feedback (beyond-paper, DESIGN.md §6).

Quantized ring-reduce analogue, expressible in shard_map:
  1. split the local gradient into n_shards chunks,
  2. quantize chunks to int8 (per-chunk scale), all_to_all the codes,
  3. dequantize + sum locally (fp32 accumulate)  -> each shard owns one
     fully-reduced chunk (reduce-scatter, int8 wire),
  4. re-quantize the reduced chunk, all_gather the codes, dequantize.

Wire bytes ≈ 2×(bytes/4) vs 2×bytes for a bf16 ring all-reduce -> ~4×
compression.  Quantization residue is fed back into the next step's
gradient (error feedback), which keeps SGD unbiased in practice.

Applies to the pure-DP regime (params replicated over the batch axes);
FSDP-sharded params use the standard bf16 reduce-scatter instead.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _quant_chunks(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: (n, chunk) -> int8 codes + (n, 1) scales."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def int8_psum(g_flat: jax.Array, axis: str, n_shards: int) -> jax.Array:
    """Inside shard_map: all-reduce a flat f32 vector over `axis` in int8."""
    n = g_flat.shape[0]
    pad = (-n) % n_shards
    gp = jnp.pad(g_flat, (0, pad)).reshape(n_shards, -1)
    q, s = _quant_chunks(gp)
    q_x = jax.lax.all_to_all(q, axis, 0, 0)               # (n_shards, chunk)
    s_x = jax.lax.all_to_all(s, axis, 0, 0)
    partial = jnp.sum(q_x.astype(jnp.float32) * s_x, axis=0)      # (chunk,)
    q2, s2 = _quant_chunks(partial[None, :])
    q_all = jax.lax.all_gather(q2[0], axis)               # (n_shards, chunk)
    s_all = jax.lax.all_gather(s2[0], axis)
    out = (q_all.astype(jnp.float32) * s_all).reshape(-1)
    return out[:n]


def compressed_allreduce(grads, mesh, batch_axes: Tuple[str, ...],
                         errors=None):
    """All-reduce a gradient pytree over the batch axes in int8 with error
    feedback. grads must be replicated w.r.t. all mesh axes on entry (the
    per-shard local gradients). Returns (mean_grads, new_errors)."""
    if errors is None:
        errors = jax.tree.map(lambda g: jnp.zeros_like(g, jnp.float32), grads)

    n_shards = 1
    for a in batch_axes:
        n_shards *= mesh.shape[a]
    axis = batch_axes if len(batch_axes) > 1 else batch_axes[0]

    def body(g, e):
        acc = jax.tree.map(
            lambda gl, el: gl.astype(jnp.float32) + el, g, e)
        red = jax.tree.map(
            lambda a: (int8_psum(a.reshape(-1), axis, n_shards)
                       / n_shards).reshape(a.shape), acc)
        new_e = jax.tree.map(lambda a, r: a - r, acc, red)
        red = jax.tree.map(lambda r, gl: r.astype(gl.dtype), red, g)
        return red, new_e

    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(), grads),
                  jax.tree.map(lambda _: P(), errors)),
        out_specs=(jax.tree.map(lambda _: P(), grads),
                   jax.tree.map(lambda _: P(), errors)),
        check_vma=False,
    )(grads, errors)
    return out
