"""Pallas kernel: packed low-bit weight × bf16 activation matmul.

This is the TPU realization of the paper's "faster, energy-efficient
inference" claim.  NorthPole executes 2/4-bit MACs natively; TPU v5e does
not, so the win is re-derived for the memory hierarchy: decode is HBM-bound,
and streaming weights at 4 (or 2) bits instead of 16 cuts the dominant
roofline term by 4× (8×).

Layout: weights are packed K-major — 2 int4 (or 4 int2) K-rows per uint8 —
so the N dimension stays a full 128-lane dimension and the unpacked tile
feeds the MXU directly as bf16.  Per-output-channel scales are applied once
on the final K step.

Grid (nm, nn, nk), K innermost; fp32 accumulation in a VMEM scratch tile.
Block defaults (bm=128, bn=128, bk=512): x tile 128·512·2B = 128 KiB, packed
w tile 512/pack·128 B ≤ 32 KiB, acc 64 KiB — comfortably inside the ~16 MiB
v5e VMEM budget with double-buffering, and every matmul dim is a multiple of
the 128×128 MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _unpack_part(wp, bits: int, i: int):
    """Codes of packed slot ``i`` of a (rows, bn) uint8 tile as bf16.

    Slot ``i`` holds K-row ``pack*r + i`` of packed row ``r`` in bits
    ``[bits*i, bits*(i+1))``.  The byte widens to int32 first: v5e's vector
    unit has no 8-bit shift or compare.  Shifting the slot to the top of the
    word and arithmetic-shifting it back down sign-extends it."""
    w = wp.astype(jnp.int32)
    code = (w << (32 - bits * (i + 1))) >> (32 - bits)
    return code.astype(jnp.float32).astype(jnp.bfloat16)


def _qmm_kernel(x_ref, wp_ref, scale_ref, o_ref, acc_ref, *, nk: int,
                bits: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # The x tile arrives slot-major (see quant_matmul): its columns
    # [i*rows, (i+1)*rows) are the K-rows that packed slot i holds, so each
    # slot is one lane-aligned matmul and no row interleave is needed.
    wp = wp_ref[...]
    rows = wp.shape[0]
    x = x_ref[...].astype(jnp.bfloat16)
    acc = acc_ref[...]
    for i in range(8 // bits):
        acc += jnp.dot(x[:, i * rows:(i + 1) * rows],
                       _unpack_part(wp, bits, i),
                       preferred_element_type=jnp.float32)
    acc_ref[...] = acc

    @pl.when(k == nk - 1)
    def _finish():
        scale = scale_ref[...].astype(jnp.float32)        # (1, bn)
        o_ref[...] = (acc_ref[...] * scale).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "bm", "bn", "bk",
                                             "interpret", "out_dtype"))
def quant_matmul(x: jax.Array, w_packed: jax.Array, scale: jax.Array,
                 bits: int = 4, bm: int = 128, bn: int = 128, bk: int = 512,
                 interpret: bool = False, out_dtype=jnp.float32) -> jax.Array:
    """x (M, K) @ packed-weights (K//pack, N) -> (M, N).

    bits in {4, 2}; pack = 8 // bits. scale: (N,) per-output-channel fp32
    (pass a broadcasted scalar for per-tensor LSQ steps).
    """
    pack = 8 // bits
    m, kdim = x.shape
    kp, n = w_packed.shape
    assert kp * pack == kdim, (x.shape, w_packed.shape, bits)
    bm = min(bm, m)
    bn = min(bn, n)
    bk = min(bk, kdim)
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0 and bk % pack == 0
    grid = (m // bm, n // bn, kdim // bk)
    # Reorder each bk-block of x's columns slot-major: column pack*r + i
    # moves to i*(bk//pack) + r, matching the kernel's per-slot matmuls.
    x = x.reshape(m, grid[2], bk // pack, pack).swapaxes(2, 3).reshape(m, kdim)
    out = pl.pallas_call(
        functools.partial(_qmm_kernel, nk=grid[2], bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // pack, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w_packed, scale.reshape(1, n))
    return out
