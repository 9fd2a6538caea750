"""Pallas kernel: histogram of integer codes (EAGL's hot loop).

EAGL needs, for every quant-unit, the bin counts of the quantized weight
codes (paper Eq. 1).  On-device this is a reduction over the full weight
tensor; the kernel tiles the (rows, 128)-shaped code matrix through VMEM and
accumulates one (1, n_bins) histogram across sequential grid steps.

Out-of-range codes (used as padding sentinels by the wrapper) fall into no
bin and are therefore ignored — the wrapper pads inputs to tile boundaries
with ``n_bins``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128


def _hist_kernel(codes_ref, out_ref, *, n_bins: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    c = codes_ref[...]                                   # (br, LANE) int32
    bins = jax.lax.broadcasted_iota(jnp.int32, (1, 1, n_bins), 2)
    onehot = (c[:, :, None] == bins).astype(jnp.float32)  # (br, LANE, n_bins)
    out_ref[...] += jnp.sum(onehot, axis=(0, 1))[None, :]


@functools.partial(jax.jit, static_argnames=("n_bins", "block_rows", "interpret"))
def histogram(codes: jax.Array, n_bins: int, block_rows: int = 64,
              interpret: bool = False) -> jax.Array:
    """Counts of int codes in [0, n_bins). codes: int32 (n,) -> (n_bins,) f32."""
    n = codes.shape[0]
    tile = block_rows * LANE
    n_pad = (-n) % tile
    padded = jnp.concatenate(
        [codes.astype(jnp.int32),
         jnp.full((n_pad,), n_bins, jnp.int32)])         # sentinel: no bin
    mat = padded.reshape(-1, LANE)
    grid = (mat.shape[0] // block_rows,)
    out = pl.pallas_call(
        functools.partial(_hist_kernel, n_bins=n_bins),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, n_bins), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, n_bins), jnp.float32),
        interpret=interpret,
    )(mat)
    return out[0]
