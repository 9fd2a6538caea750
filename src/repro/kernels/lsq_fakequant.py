"""Pallas kernel: fused LSQ fake-quantization (quantize-dequantize).

QAT's inner loop applies ``clip(round(x/s), qmin, qmax) * s`` to every weight
and activation tensor every step.  Unfused, XLA materializes x/s, round, two
compares and a rescale; the kernel does one VMEM pass.  Step size and
bit-width ride along as (1, 1) scalars so one compiled kernel serves every
layer and every knapsack outcome.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128


def _lsq_kernel(x_ref, step_ref, bits_ref, o_ref):
    s = jnp.maximum(jnp.abs(step_ref[0, 0]), 1e-9)
    b = bits_ref[0, 0]
    qmax = jnp.exp2(b - 1.0) - 1.0
    qmin = -jnp.exp2(b - 1.0)
    x = x_ref[...].astype(jnp.float32)
    q = jnp.clip(jnp.round(x / s), qmin, qmax)
    o_ref[...] = (q * s).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def lsq_fakequant(x: jax.Array, step: jax.Array, bits: jax.Array,
                  block_rows: int = 256, interpret: bool = False) -> jax.Array:
    """Fake-quantize a tensor of any shape; returns same shape/dtype."""
    orig_shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    tile = block_rows * LANE
    n_pad = (-n) % tile
    mat = jnp.concatenate([flat, jnp.zeros((n_pad,), x.dtype)]).reshape(-1, LANE)
    grid = (mat.shape[0] // block_rows,)
    step2 = jnp.reshape(step.astype(jnp.float32), (1, 1))
    bits2 = jnp.reshape(jnp.asarray(bits, jnp.float32), (1, 1))
    out = pl.pallas_call(
        _lsq_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(mat.shape, x.dtype),
        interpret=interpret,
    )(mat, step2, bits2)
    return out.reshape(-1)[:n].reshape(orig_shape)
