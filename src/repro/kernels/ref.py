"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

These are also the production CPU/dry-run implementations: they lower to
plain XLA HLO, so the dry-run roofline sees the true byte traffic (packed
integer weights stay packed in HBM until the unpack op).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.kernels import kv_quant


# ------------------------------------------------------------- entropy_hist
def histogram(codes: jax.Array, n_bins: int) -> jax.Array:
    """Counts of integer codes in [0, n_bins). codes: int32 (n,)."""
    one_hot = (codes[:, None] == jnp.arange(n_bins, dtype=codes.dtype)[None, :])
    return jnp.sum(one_hot.astype(jnp.float32), axis=0)


def entropy_from_counts(counts: jax.Array) -> jax.Array:
    """H(p̂) in bits (paper Eq. 3) with masked p·log2(p) — empty bins
    contribute exactly 0, so p stays normalized and H is independent of how
    many unused bins the histogram carries.  Single definition: the kernel
    dispatch path (kernels/ops.py) shares this post-processing, so the ref
    and Pallas paths cannot drift."""
    p = counts / jnp.maximum(jnp.sum(counts), 1.0)
    plogp = jnp.where(p > 0, p * jnp.log2(jnp.maximum(p, 1e-30)), 0.0)
    return -jnp.sum(plogp)


def entropy_bits(codes: jax.Array, n_bins: int) -> jax.Array:
    return entropy_from_counts(histogram(codes, n_bins))


# ------------------------------------------------------------ lsq_fakequant
def lsq_fakequant(x: jax.Array, step: jax.Array, bits: jax.Array) -> jax.Array:
    """Quantize-dequantize forward (no VJP here — oracle only).
    Arithmetic in f32 (matches core/quant.py and the Pallas kernel)."""
    qmin, qmax = quant.qrange(bits)
    s = jnp.maximum(jnp.abs(step), 1e-9).astype(jnp.float32)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), qmin, qmax)
    return (q * s).astype(x.dtype)


# ------------------------------------------------------------- quant_matmul
def dequant_matmul(x: jax.Array, w_packed: jax.Array, scale: jax.Array,
                   bits: int) -> jax.Array:
    """The CPU/dry-run serving path: dequantize-then-matmul in x's dtype.

    Unlike the bf16 Pallas oracles below (scale applied after the fp32
    accumulator), this dequantizes codes * scale elementwise FIRST and runs
    the matmul in ``x.dtype`` — the exact op order of the fake-quant
    reference (models/common.qproj), so packed serving is greedy-argmax
    bit-parity with the fake-quant path on CPU.  x: (..., Kp*?); the last
    dim must equal w_packed's unpacked K (callers pad x with zeros when the
    logical K is not a pack multiple — padding codes are 0, contributing
    exactly 0).
    """
    unpack = unpack_w4 if bits == 4 else unpack_w2
    w = unpack(w_packed, jnp.float32) * scale[None, :].astype(jnp.float32)
    return x @ w.astype(x.dtype)


def quant_matmul_w4(x: jax.Array, w_packed: jax.Array, scale: jax.Array,
                    ) -> jax.Array:
    """x (M,K) bf16 @ int4-weights packed 2-per-uint8 along K.

    w_packed: (K//2, N) uint8; row r holds K-rows 2r (low nibble) and 2r+1
    (high nibble), sign-extended 4-bit codes. scale: (N,) f32 per-channel.
    """
    w = unpack_w4(w_packed)                       # (K, N) bf16 codes
    acc = jnp.dot(x.astype(jnp.bfloat16), w,
                  preferred_element_type=jnp.float32)
    return acc * scale[None, :].astype(jnp.float32)


def quant_matmul_w2(x: jax.Array, w_packed: jax.Array, scale: jax.Array,
                    ) -> jax.Array:
    """x (M,K) bf16 @ 2-bit weights packed 4-per-uint8 along K.

    w_packed: (K//4, N) uint8; row r holds K-rows 4r..4r+3 in bit-pairs
    (LSB first). scale: (N,) f32.
    """
    w = unpack_w2(w_packed)                       # (K, N) bf16 codes
    acc = jnp.dot(x.astype(jnp.bfloat16), w,
                  preferred_element_type=jnp.float32)
    return acc * scale[None, :].astype(jnp.float32)


def unpack_w4(w_packed: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    """(K//2, N) uint8 -> (K, N) sign-extended codes."""
    lo = (w_packed & 0xF).astype(jnp.int8)
    hi = ((w_packed >> 4) & 0xF).astype(jnp.int8)
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    w = jnp.stack([lo, hi], axis=1)               # (K//2, 2, N)
    return w.reshape(w_packed.shape[0] * 2, w_packed.shape[1]).astype(dtype)


def unpack_w2(w_packed: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    """(K//4, N) uint8 -> (K, N) sign-extended 2-bit codes in [-2, 1]."""
    parts = []
    for i in range(4):
        c = ((w_packed >> (2 * i)) & 0x3).astype(jnp.int8)
        c = jnp.where(c >= 2, c - 4, c)
        parts.append(c)
    w = jnp.stack(parts, axis=1)                  # (K//4, 4, N)
    return w.reshape(w_packed.shape[0] * 4, w_packed.shape[1]).astype(dtype)


def pack_w4(codes: jax.Array) -> jax.Array:
    """(K, N) int codes in [-8,7] -> (K//2, N) uint8 (K-major nibbles)."""
    assert codes.shape[0] % 2 == 0
    c = (codes.astype(jnp.int32) & 0xF).astype(jnp.uint8)
    c = c.reshape(codes.shape[0] // 2, 2, codes.shape[1])
    return (c[:, 0, :] | (c[:, 1, :] << 4)).astype(jnp.uint8)


def pack_w2(codes: jax.Array) -> jax.Array:
    """(K, N) int codes in [-2,1] -> (K//4, N) uint8 (K-major bit-pairs)."""
    assert codes.shape[0] % 4 == 0
    c = (codes.astype(jnp.int32) & 0x3).astype(jnp.uint8)
    c = c.reshape(codes.shape[0] // 4, 4, codes.shape[1])
    out = c[:, 0, :]
    for i in range(1, 4):
        out = out | (c[:, i, :] << (2 * i))
    return out.astype(jnp.uint8)


# ------------------------------------------------------- kv-cache attention
def kv_cache_attention(q: jax.Array, kq: jax.Array, k_scale: jax.Array,
                       vq: jax.Array, v_scale: jax.Array,
                       positions: jax.Array, bits: int,
                       layer: jax.Array | None = None) -> jax.Array:
    """Decode attention over a QUANTIZED KV cache — the pure-jnp oracle of
    kernels/flash_attention.kv_decode_attention, and the production CPU
    serving path (kernels/ops dispatch, impl='auto' off-TPU).

    Op order is the quantized-cache serving contract (DESIGN.md §3):
    dequantize codes·scale to f32 FIRST, then exactly the full-dtype
    decode math of models/attention.gqa_apply (f32 score einsum, dh^-0.5
    scale, ``s_pos <= position`` mask, f32 softmax, f32 value einsum) —
    so a quantized-cache decode differs from the full-cache decode by the
    K/V quantization error and nothing else.

    q: (B, H, D); kq/vq: (B, S, Hkv, D or D//2) int8/uint8 codes;
    k_scale: (B, Hkv, D); v_scale: (B, S, Hkv); positions: (B,) int32.
    With ``layer``, kq/vq/v_scale are (L, B, ...) layer stacks read at
    that layer, as the kernel reads them.  Returns (B, H, D) f32.
    """
    if layer is not None:
        kq, vq, v_scale = (jax.lax.dynamic_index_in_dim(a, layer, 0, False)
                           for a in (kq, vq, v_scale))
    k = kv_quant.dequant_k(kq, k_scale, bits)            # (B,S,Hkv,D) f32
    v = kv_quant.dequant_v(vq, v_scale, bits)
    h, d = q.shape[1], q.shape[2]
    group = h // k.shape[2]
    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    logits = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32), k) \
        * (d ** -0.5)
    s_pos = jnp.arange(kq.shape[1])
    mask = s_pos[None, None, :] <= positions[:, None, None]
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhs,bshd->bhd", p, v)


def paged_kv_cache_attention(q: jax.Array, kq_pool: jax.Array,
                             k_scale: jax.Array, vq_pool: jax.Array,
                             v_scale_pool: jax.Array, tbl: jax.Array,
                             positions: jax.Array, bits: int) -> jax.Array:
    """Decode attention over a PAGED quantized KV cache — the pure-jnp
    oracle of kernels/flash_attention.paged_kv_decode_attention, and the
    production CPU serving path (kernels/ops dispatch, impl='auto'
    off-TPU).

    The pools hold fixed-size pages; each slot's virtual (B, n*page)
    sequence is assembled through its block-table row
    (kv_quant.gather_pages) and then runs EXACTLY the contiguous
    quantized-cache decode math (``kv_cache_attention`` above) — so the
    paged read differs from the contiguous read by the page indirection
    and NOTHING else; masked softmax rows contribute exactly 0 either
    way, which is what makes paged==contiguous decode bit-exact
    (tests/test_serve.py) and unmapped-page contents (even NaN — the
    poisoned-free-page test) unobservable.

    q: (B, H, D); kq_pool/vq_pool: (P, page, Hkv, D or D//2) codes;
    k_scale: (B, Hkv, D) per-slot per-channel; v_scale_pool:
    (P, page, Hkv) per-token rows riding their pages; tbl: (B, n) int32;
    positions: (B,) int32.  Returns (B, H, D) f32.
    """
    kq = kv_quant.gather_pages(kq_pool, tbl)             # (B, S_virt, ...)
    vq = kv_quant.gather_pages(vq_pool, tbl)
    vs = kv_quant.gather_pages(v_scale_pool, tbl)
    s_virt = kq.shape[1]
    # Zero the V rows past each slot's position BEFORE the value einsum:
    # their softmax weight is exactly 0, but 0 * NaN (a poisoned free
    # page) would still smear — the contiguous path never holds NaN, so
    # the zeroing keeps bit-parity AND NaN-safety.
    mask = jnp.arange(s_virt)[None, :] <= positions[:, None]
    vq = jnp.where(mask[..., None, None], vq, 0).astype(vq.dtype)
    vs = jnp.where(mask[..., None], vs, 0.0)
    return kv_cache_attention(q, kq, k_scale, vq, vs, positions, bits)


# ---------------------------------------------------------- flash_attention
def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              causal: bool = True, scale: float | None = None) -> jax.Array:
    """Naive softmax attention oracle. q,k,v: (B, H, S, D) (H = q heads;
    k/v may have fewer heads — pre-broadcast before calling)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        s_q, s_k = q.shape[2], k.shape[2]
        mask = jnp.tril(jnp.ones((s_q, s_k), bool), k=s_k - s_q)
        logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
