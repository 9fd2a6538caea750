"""Quantization primitives: LSQ fake-quant, integer quantization, packing.

The paper fine-tunes mixed-precision networks with LSQ (Esser et al., 2020):
weights and activations are quantized with a *learned* step size ``s``::

    q      = clamp(round(x / s), qmin, qmax)
    x_hat  = q * s

Gradients flow through a straight-through estimator for ``x`` and through the
LSQ step-size gradient for ``s`` (scaled by ``g = 1/sqrt(n * qmax)``).

Bit-widths are **traced values** (float32 scalars/arrays), not Python ints, so
one compiled train step serves every mixed-precision policy the knapsack can
produce — changing a layer from 4-bit to 2-bit does not recompile anything.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp


def qrange(bits: jax.Array, signed: bool = True) -> Tuple[jax.Array, jax.Array]:
    """(qmin, qmax) for a traced bit-width. bits may be any float/int array."""
    b = jnp.asarray(bits, jnp.float32)
    if signed:
        qmax = jnp.exp2(b - 1.0) - 1.0
        qmin = -jnp.exp2(b - 1.0)
    else:
        qmax = jnp.exp2(b) - 1.0
        qmin = jnp.zeros_like(qmax)
    return qmin, qmax


def quantize_int(x: jax.Array, step: jax.Array, bits: jax.Array,
                 signed: bool = True) -> jax.Array:
    """Integer codes q = clamp(round(x/s)) — the paper's Q_b(W) before rescale."""
    qmin, qmax = qrange(bits, signed)
    return jnp.clip(jnp.round(x / step), qmin, qmax)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def lsq_fake_quant(x: jax.Array, step: jax.Array, bits: jax.Array,
                   signed: bool = True) -> jax.Array:
    """LSQ quantize-dequantize with learned step size.

    x:    tensor to fake-quantize (weights or activations)
    step: positive scalar (or broadcastable) learned step size
    bits: traced bit-width (scalar or broadcastable), e.g. 2.0 / 4.0 / 8.0
    """
    qmin, qmax = qrange(bits, signed)
    s = jnp.maximum(jnp.abs(step), 1e-9).astype(jnp.float32)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), qmin, qmax)
    return (q * s).astype(x.dtype)


def _lsq_fwd(x, step, bits, signed):
    # quantization arithmetic in f32 regardless of storage dtype (bf16's 8
    # mantissa bits would mis-round codes near bin boundaries).
    #
    # RESIDUALS ARE THE RAW INPUTS ONLY. Saving xs/q (two f32 tensors the
    # size of the weights, per quant-unit, per layer, per microbatch) was
    # the dominant HBM/collective cost of QAT at scale — the backward
    # recomputes them elementwise instead (EXPERIMENTS.md §Perf A1).
    # (primal inlined — calling the decorated fn would break jvp-of-vjp,
    # e.g. HAWQ's Hutchinson HVPs)
    qmin, qmax = qrange(bits, signed)
    s = jnp.maximum(jnp.abs(step), 1e-9).astype(jnp.float32)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), qmin, qmax)
    return (q * s).astype(x.dtype), (x, step, bits)


def _lsq_bwd(signed, res, g):
    x, step, bits = res
    qmin, qmax = qrange(bits, signed)
    s = jnp.maximum(jnp.abs(step), 1e-9).astype(jnp.float32)
    xs = x.astype(jnp.float32) / s
    in_range = (xs >= qmin) & (xs <= qmax)
    # STE for x: pass-through inside the clip range, zero outside.
    # Cotangent dtype follows the PRIMAL (bf16 params/activations keep the
    # whole backward chain — and its psums/reduce-scatters — in bf16;
    # returning g.dtype here silently upcast every QAT backward to f32 and
    # doubled the collective wire: EXPERIMENTS.md §Perf A2).
    gx = jnp.where(in_range, g, 0).astype(x.dtype)
    # LSQ grad for s:  d(q*s)/ds = (round(xs) - xs) inside range; qmin/qmax
    # outside.
    ds_elem = jnp.where(in_range, jnp.round(xs) - xs,
                        jnp.clip(xs, qmin, qmax))
    # float, not int: element counts of full-scale layers exceed int32
    n = float(max(1, x.size // _size(step)))
    # LSQ grad scale g = 1/sqrt(n*qmax) for stability (Esser et al., 2020).
    gscale = jax.lax.rsqrt(jnp.maximum(
        n * jnp.mean(qmax).astype(jnp.float32), 1.0))
    gs_full = (g.astype(jnp.float32) * ds_elem) * gscale
    # Reduce to the step's shape (step is usually a scalar per quant-unit).
    gs = _reduce_to_shape(gs_full, jnp.shape(step)).astype(
        step.dtype if hasattr(step, "dtype") else jnp.float32)
    gbits = jnp.zeros_like(bits)       # bits come from the policy, not SGD
    return gx, gs, gbits


def _size(a) -> int:
    n = 1
    for d in jnp.shape(a):
        n *= d
    return max(n, 1)


def _reduce_to_shape(x: jax.Array, shape) -> jax.Array:
    """Sum-reduce x down to `shape` (supporting scalar or broadcast shapes)."""
    if shape == ():
        return jnp.sum(x)
    # Sum over leading axes until ranks match, then over broadcasted dims.
    while x.ndim > len(shape):
        x = jnp.sum(x, axis=0)
    for i, (xd, sd) in enumerate(zip(x.shape, shape)):
        if sd == 1 and xd != 1:
            x = jnp.sum(x, axis=i, keepdims=True)
    return x


lsq_fake_quant.defvjp(_lsq_fwd, _lsq_bwd)


def init_step_from_tensor(w: jax.Array, bits: float) -> jax.Array:
    """LSQ step-size init: 2*mean(|w|)/sqrt(qmax) (Esser et al., 2020)."""
    qmax = 2.0 ** (bits - 1) - 1.0
    return 2.0 * jnp.mean(jnp.abs(w)).astype(jnp.float32) / jnp.sqrt(qmax)


def rescale_step_for_bits(step: jax.Array, old_bits: float, new_bits: float) -> jax.Array:
    """Paper §3.4.3: when dropping 4-bit -> 2-bit, init new step = 4 * old step.

    Generalized: step scales by 2**(old_bits - new_bits) so the representable
    range (step * 2^(b-1)) is preserved.
    """
    return step * (2.0 ** (old_bits - new_bits))


# ---------------------------------------------------------------------------
# Real integer quantization + packing for the serving path.
# ---------------------------------------------------------------------------

def quantize_weights_int(w: jax.Array, step: jax.Array, bits: int):
    """Quantize to integer codes for storage. Returns (codes_int8, step)."""
    q = quantize_int(w, step, jnp.float32(bits))
    return q.astype(jnp.int8), step


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("wp", "scale", "sa"),
                   meta_fields=("bits", "k_dim"))
@dataclasses.dataclass
class PackedLinear:
    """One dense projection in the packed serving layout (DESIGN.md §3).

    ``wp`` holds the integer codes in their streaming container:
      bits=4 -> uint8 (Kp//2, N), two K-rows per byte (low nibble first)
      bits=2 -> uint8 (Kp//4, N), four K-rows per byte (LSB pair first)
      bits=8 -> int8  (K, N), one code per byte (pinned edges)
    where Kp = k_dim rounded up to the pack factor; padding K-rows are
    zero codes, so they contribute exactly 0 to any matmul.

    ``scale`` is per-output-channel (N,) f32 — a per-tensor LSQ step is
    stored broadcast, so the layout is ready for per-channel calibration
    without a format change.  ``sa`` is the activation LSQ step (scalar
    f32) carried over from the checkpoint.  ``bits``/``k_dim`` are static
    (pytree metadata): the unpack path of kernels/quant_matmul.py is
    compile-time specialized per bit-width.
    """
    wp: jax.Array
    scale: jax.Array
    sa: jax.Array
    bits: int
    k_dim: int

    @property
    def pack(self) -> int:
        return 8 // self.bits

    @property
    def n_dim(self) -> int:
        return self.wp.shape[-1]

    @property
    def k_padded(self) -> int:
        # -2 (not 0): a bucketed serve layout stacks same-signature layers
        # on a leading axis (models/layout.py), so wp may be (m, Kp/pack, N).
        return self.wp.shape[-2] * self.pack


def pack_codes_kmajor(codes: jax.Array, bits: int) -> jax.Array:
    """(K, N) integer codes -> K-major packed uint8 (ceil(K/pack), N).

    K-major (pack adjacent *K*-rows into one byte) keeps N a full lane
    dimension, so the unpacked tile feeds the MXU directly
    (kernels/quant_matmul.py shares this layout).  K is zero-padded up to
    the pack factor; zero codes dequantize to exactly 0.
    """
    assert bits in (2, 4), bits
    pack = 8 // bits
    c = jnp.asarray(codes).astype(jnp.int32)
    k, n = c.shape
    kp = -(-k // pack) * pack
    if kp != k:
        c = jnp.pad(c, ((0, kp - k), (0, 0)))
    u = (c & ((1 << bits) - 1)).reshape(kp // pack, pack, n)
    out = u[:, 0, :]
    for i in range(1, pack):
        out = out | (u[:, i, :] << (bits * i))
    return out.astype(jnp.uint8)


def unpack_codes_kmajor(wp: jax.Array, bits: int,
                        dtype=jnp.float32) -> jax.Array:
    """Inverse of pack_codes_kmajor: (..., Kp//pack, N) uint8 ->
    (..., Kp, N) codes.  Leading axes (a bucketed layer stack) pass
    through untouched — the byte layout is per-(K, N) slab."""
    assert bits in (2, 4), bits
    pack = 8 // bits
    parts = []
    for i in range(pack):
        c = ((wp >> (bits * i)) & ((1 << bits) - 1)).astype(jnp.int8)
        c = jnp.where(c >= (1 << (bits - 1)), c - (1 << bits), c)
        parts.append(c)
    w = jnp.stack(parts, axis=-2)                 # (..., Kp//pack, pack, N)
    out_shape = wp.shape[:-2] + (wp.shape[-2] * pack, wp.shape[-1])
    return w.reshape(out_shape).astype(dtype)


def pack_linear(w: jax.Array, step: jax.Array, sa, bits: int) -> PackedLinear:
    """Quantize + pack one (K, N) weight into the serving layout.

    The codes are computed with the SAME arithmetic as the fake-quant path
    (clip(round(w/s)) in f32), so dequantizing the packed buffer reproduces
    ``lsq_fake_quant(w, step, bits)`` bit-exactly — the packed serving path
    stays greedy-argmax-parity with the fake-quant reference.
    """
    assert w.ndim == 2, w.shape
    assert bits in (2, 4, 8), bits
    k, n = w.shape
    stepf = jnp.maximum(jnp.abs(jnp.asarray(step, jnp.float32)), 1e-9)
    codes = quantize_int(w.astype(jnp.float32), stepf, jnp.float32(bits))
    scale = jnp.broadcast_to(jnp.reshape(stepf, (-1,)), (n,)).astype(
        jnp.float32)
    if bits == 8:
        wp = jnp.asarray(codes, jnp.int8)
    else:
        wp = pack_codes_kmajor(codes, bits)
    return PackedLinear(wp=wp, scale=scale,
                        sa=jnp.asarray(sa, jnp.float32), bits=int(bits),
                        k_dim=int(k))


def packed_weight_dense(p: PackedLinear, dtype=jnp.float32) -> jax.Array:
    """Dequantize a PackedLinear back to its (k_dim, N) weight matrix
    (a bucketed (m, ...) layer stack dequantizes to (m, k_dim, N)).

    Dequant order matches the fake-quant path (codes * scale elementwise,
    THEN any downstream matmul) so the two layouts agree bit-for-bit.

    Both branches truncate to ``k_dim`` rows: pack padding beyond it is
    zero-rows for 2/4-bit, and a row-parallel shard (serve/packing.py
    ``_shard_row_packed``) stores a LOCAL k_dim against a buffer whose
    global view holds every shard's rows — a caller outside the shard_map
    body gets the first shard's slab for every bit-width alike, not a
    silently different shape per container.
    """
    if p.bits == 8:
        codes = p.wp.astype(jnp.float32)[..., :p.k_dim, :]
    else:
        codes = unpack_codes_kmajor(p.wp, p.bits,
                                    jnp.float32)[..., :p.k_dim, :]
    return (codes * p.scale[..., None, :].astype(jnp.float32)).astype(dtype)


def pack_int4(codes: jax.Array) -> jax.Array:
    """Store int8 codes in native jnp.int4 (XLA packs 2 per byte)."""
    return codes.astype(jnp.int4)


def unpack_int4(packed: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    return packed.astype(dtype)


def pack_int2(codes: jax.Array) -> jax.Array:
    """Pack 2-bit codes (values in [-2,1]) 4-per-uint8 along the last axis.

    Last axis length must be a multiple of 4.
    """
    assert codes.shape[-1] % 4 == 0, codes.shape
    u = (codes.astype(jnp.int32) & 0x3).astype(jnp.uint8)
    u = u.reshape(*codes.shape[:-1], codes.shape[-1] // 4, 4)
    shifts = jnp.array([0, 2, 4, 6], jnp.uint8)
    return jnp.sum(u << shifts, axis=-1).astype(jnp.uint8)


def unpack_int2(packed: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    """Inverse of pack_int2: uint8 -> 4x signed 2-bit values in [-2, 1]."""
    shifts = jnp.array([0, 2, 4, 6], jnp.uint8)
    u = (packed[..., None] >> shifts) & 0x3          # (..., n//4, 4) in [0,3]
    s = u.astype(jnp.int8)
    s = jnp.where(s >= 2, s - 4, s)                   # sign-extend 2-bit
    out = s.reshape(*packed.shape[:-1], packed.shape[-1] * 4)
    return out.astype(dtype)


def dequantize(codes: jax.Array, step: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    return codes.astype(dtype) * step.astype(dtype)
