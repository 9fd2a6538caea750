"""Dispatch wrappers: Pallas kernels on TPU, pure-jnp refs elsewhere.

``impl`` semantics:
  - "auto":      Pallas (compiled) on TPU; ref (plain XLA) on CPU/GPU.
                 This is what models/serving call — the dry-run therefore
                 lowers the ref path, whose HLO carries the true packed-byte
                 traffic for the roofline.
  - "pallas":    force-compile the Pallas kernel (TPU only).
  - "interpret": Pallas kernel body interpreted on CPU — used by the test
                 suite to validate kernels against the refs.
  - "ref":       force the pure-jnp oracle.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from repro.core import quant as _quant
from repro.core.quant import PackedLinear
from repro.kernels import entropy_hist as _hist
from repro.kernels import flash_attention as _flash
from repro.kernels import lsq_fakequant as _lsq
from repro.kernels import quant_matmul as _qmm
from repro.kernels import ref


# Forced-backend stack for deployed_backend(); empty -> real backend.
_DEPLOYED: list = []


@contextlib.contextmanager
def deployed_backend(backend: str):
    """Resolve ``impl='auto'`` as if running on ``backend`` ("tpu"/"cpu").

    For ABSTRACT work only — tracing (``jax.make_jaxpr``) and lowering.
    The static analyzer (repro.analysis) uses this to trace the serving
    dispatches down the Pallas path on a CPU host, so contracts like
    "quantized decode never materializes a full-dtype cache" are checked
    against the program that actually deploys, not the CPU ref oracle
    (which legitimately dequantizes in full).  Actually EXECUTING a
    Pallas kernel under a forced "tpu" on a CPU host will fail at
    compile time, loudly.
    """
    _DEPLOYED.append(backend)
    try:
        yield
    finally:
        _DEPLOYED.pop()


def on_tpu() -> bool:
    if _DEPLOYED:
        return _DEPLOYED[-1] == "tpu"
    return jax.default_backend() == "tpu"


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if on_tpu() else "ref"
    return impl


def histogram(codes: jax.Array, n_bins: int, impl: str = "auto") -> jax.Array:
    impl = _resolve(impl)
    if impl == "ref":
        return ref.histogram(codes, n_bins)
    return _hist.histogram(codes, n_bins, interpret=(impl == "interpret"))


def entropy_bits(codes: jax.Array, n_bins: int, impl: str = "auto") -> jax.Array:
    """H(p̂) in bits with masked p·log2(p): empty bins contribute exactly 0.

    (A flat +eps on every bin would un-normalize p and leak -eps·log2(eps)
    per empty bin into H, which biases wide histograms — n_bins enters H.)
    Only the histogram dispatches per-impl; the counts->H formula is shared
    with the ref path (ref.entropy_from_counts).
    """
    return ref.entropy_from_counts(histogram(codes, n_bins, impl=impl))


def lsq_fakequant(x: jax.Array, step: jax.Array, bits, impl: str = "auto",
                  ) -> jax.Array:
    """Forward-only fake-quant (inference/eval). QAT uses
    repro.core.quant.lsq_fake_quant, which carries the LSQ custom VJP."""
    impl = _resolve(impl)
    if impl == "ref":
        return ref.lsq_fakequant(x, step, jnp.asarray(bits, jnp.float32))
    return _lsq.lsq_fakequant(x, step, jnp.asarray(bits, jnp.float32),
                              interpret=(impl == "interpret"))


def quant_matmul(x: jax.Array, w_packed: jax.Array, scale: jax.Array,
                 bits: int, impl: str = "auto", **kw) -> jax.Array:
    impl = _resolve(impl)
    if impl == "ref":
        f = ref.quant_matmul_w4 if bits == 4 else ref.quant_matmul_w2
        return f(x, w_packed, scale)
    return _qmm.quant_matmul(x, w_packed, scale, bits=bits,
                             interpret=(impl == "interpret"), **kw)


def packed_weight(p: PackedLinear, dtype=jnp.float32) -> jax.Array:
    """Dequantized (k_dim, N) weight of a packed projection.

    For sites that consume the weight tensor directly (MLA's absorbed
    decode einsums) rather than as one (M,K)@(K,N) matmul — the codes
    still *stream* packed; the unpack happens at use.
    """
    return _quant.packed_weight_dense(p, dtype)


def packed_matmul(x: jax.Array, p: PackedLinear, impl: str = "auto",
                  ) -> jax.Array:
    """x (..., K) @ PackedLinear -> (..., N): the serving-side dense path.

    Dispatch (DESIGN.md §3):
      - bits 4/2 on TPU: the Pallas quant_matmul streams the packed uint8
        codes from HBM (4×/8× fewer weight bytes than bf16) and unpacks
        in VMEM.
      - bits 4/2 on CPU (or impl="ref"): ref.dequant_matmul — exact
        dequantize-then-matmul in x.dtype, bit-parity with the fake-quant
        reference.
      - bits 8 (pinned edges): plain dequant matmul everywhere (the kernel
        packs 4/2-bit only; int8 already streams at 1 byte/code).

    K not divisible by the pack factor is handled by zero-padding x up to
    the packed buffer's K — padding rows hold zero codes and contribute
    exactly 0.

    Under a serving shard_map body (ServeEngine(mesh=...)) this sees the
    LOCAL PackedLinear: column shards carry an N slice at the global
    k_dim; row shards carry an independently repacked K-slab whose static
    k_dim IS the local contraction length (packing._shard_row_packed —
    nibble bytes never straddle shards), so the same dispatch works
    unchanged per shard.  (The hot CPU decode path instead dequantizes
    once per dispatch via packing.decode_weight_view and skips this
    per-step call entirely.)
    """
    k = x.shape[-1]
    assert k == p.k_dim, (x.shape, p.k_dim)
    if p.bits == 8:
        w = p.wp.astype(jnp.float32) * p.scale[None, :].astype(jnp.float32)
        return x @ w.astype(x.dtype)
    kp = p.k_padded
    if kp != k:
        pad = [(0, 0)] * (x.ndim - 1) + [(0, kp - k)]
        x = jnp.pad(x, pad)
    impl = _resolve(impl)
    if impl == "ref":
        return ref.dequant_matmul(x, p.wp, p.scale, p.bits)
    lead, n = x.shape[:-1], p.n_dim
    x2 = x.reshape(-1, kp)
    m = x2.shape[0]
    mp = m if m <= 128 else -(-m // 128) * 128
    if mp != m:
        x2 = jnp.pad(x2, ((0, mp - m), (0, 0)))
    # Block sizes must DIVIDE the problem dims (quant_matmul asserts) —
    # real model dims are not always multiples of the 128/512 defaults
    # (e.g. d_ff=11008 % 512 == 256), so shrink to the largest divisor.
    # Non-MXU-aligned blocks cost perf, never correctness.
    pack = 8 // p.bits
    bn = _largest_divisor(n, 128)
    bk = _largest_divisor(kp // pack, 512 // pack) * pack
    out = _qmm.quant_matmul(x2, p.wp, p.scale, bits=p.bits, bn=bn, bk=bk,
                            interpret=(impl == "interpret"))
    return out[:m].astype(x.dtype).reshape(lead + (n,))


def _largest_divisor(dim: int, cap: int) -> int:
    """Largest divisor of ``dim`` that is <= ``cap``."""
    for d in range(min(cap, dim), 0, -1):
        if dim % d == 0:
            return d
    return 1


def kv_cache_attention(q: jax.Array, kq: jax.Array, k_scale: jax.Array,
                       vq: jax.Array, v_scale: jax.Array,
                       positions: jax.Array, bits: int,
                       impl: str = "auto", layer: jax.Array | None = None,
                       **kw) -> jax.Array:
    """Decode attention over a quantized KV cache (serving read path).

    Dispatch (DESIGN.md §3): the Pallas kernel on TPU dequantizes K/V code
    tiles in-register (HBM streams 1 or 0.5 bytes/elem); the ref oracle —
    also the production CPU path — dequantizes then runs the exact
    full-dtype decode math, so quantized-cache serving differs from the
    full cache only by the quantization error.

    ``layer``: with it, kq/vq/v_scale are a carried (L, B, ...) layer
    stack and layer ``layer`` is read by index (k_scale is that layer's);
    without it they are one layer's (B, ...) buffers.

    S_max need not be tile-aligned: the Pallas path shrinks the S block to
    the largest divisor <= 128 (same rule as ``packed_matmul``), and D is
    never blocked.
    """
    impl = _resolve(impl)
    if impl == "ref":
        return ref.kv_cache_attention(q, kq, k_scale, vq, v_scale,
                                      positions, bits, layer)
    if "bs" not in kw:
        kw["bs"] = _largest_divisor(kq.shape[-3], 128)
    return _flash.kv_decode_attention(q, kq, k_scale, vq, v_scale, positions,
                                      layer, bits=bits,
                                      interpret=(impl == "interpret"), **kw)


def paged_kv_cache_attention(q: jax.Array, kq_pool: jax.Array,
                             k_scale: jax.Array, vq_pool: jax.Array,
                             v_scale_pool: jax.Array, tbl: jax.Array,
                             positions: jax.Array, bits: int,
                             impl: str = "auto") -> jax.Array:
    """Decode attention over a PAGED quantized KV cache (serving read path
    for ``ServeEngine(cache_layout='paged')``, DESIGN.md §3).

    Dispatch mirrors ``kv_cache_attention``: the Pallas kernel on TPU
    streams physical pages through a scalar-prefetched block table and
    dequantizes in-register; the ref oracle — also the production CPU
    path — gathers the pages then runs the EXACT contiguous
    quantized-cache decode math, so a paged decode differs from the
    contiguous decode by the page indirection and nothing else.
    """
    impl = _resolve(impl)
    if impl == "ref":
        return ref.paged_kv_cache_attention(q, kq_pool, k_scale, vq_pool,
                                            v_scale_pool, tbl, positions,
                                            bits)
    return _flash.paged_kv_decode_attention(
        q, kq_pool, k_scale, vq_pool, v_scale_pool, tbl, positions,
        bits=bits, interpret=(impl == "interpret"))


def flash_attention(q, k, v, causal: bool = True, impl: str = "auto", **kw):
    impl = _resolve(impl)
    if impl == "ref":
        group = q.shape[1] // k.shape[1]
        if group > 1:
            k = jnp.repeat(k, group, axis=1)
            v = jnp.repeat(v, group, axis=1)
        return ref.attention(q, k, v, causal=causal)
    return _flash.flash_attention(q, k, v, causal=causal,
                                  interpret=(impl == "interpret"), **kw)
