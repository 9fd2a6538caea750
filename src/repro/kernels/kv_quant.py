"""KV-cache quantization primitives (KVQuant/Atom-style, DESIGN.md §3).

The serving KV cache is what actually grows with batch × context; holding
it in the compute dtype makes the decode roofline weights-only in name
but cache-bound in practice.  This module quantizes attention K/V cache
state to int8 (1 byte/elem) or packed int4 (2 elems/byte) with the
scale placement the KV-quantization literature converged on:

  * K — **per-channel** scales, shape (..., B, Hkv, D): RoPE'd keys carry
    outlier *channels* (a few frequency dims dominate), so the grid must
    resolve per channel.  The scale is calibrated once per request from
    its own prefill rows (masked to the valid prompt length — right-pad
    garbage must not inflate it) with a small headroom margin, then held
    fixed for decode writes; a shared-across-tokens scale is what lets
    the fused kernel dequantize a K tile with one broadcast multiply.
  * V — **per-token** scales, shape (..., B, S, Hkv): values have no
    stable channel structure, but each row is fully known at write time,
    so its scale is exact (no clipping ever) and rides the same
    ``cache_write`` row scatter as the codes.

Codes use the symmetric range [-qmax, qmax] (int8: ±127, int4: ±7) so a
packed int4 nibble sign-extends cleanly.  All quantization arithmetic is
f32, matching core/quant.py.

Every function is leading-dim agnostic over the canonical cache axes
(..., B, S, Hkv, D) so the same code serves per-layer dicts and the
(n_repeats,)-stacked scan layout.
"""
from __future__ import annotations

import jax.numpy as jnp

QMAX = {8: 127.0, 4: 7.0}
# Decode K rows quantize against the prefill-calibrated grid; the margin
# widens the step slightly (int8: +50% of a 0.8%-of-max step — noise) so
# decode keys that overshoot the prompt's per-channel max are rarely
# clipped hard.
K_SCALE_MARGIN = 1.5
_EPS = 1e-8


def cache_bits(cache: dict) -> int:
    """Static bit-width of a quantized cache dict, derived from the code
    container (int8 -> 8, packed uint8 nibbles -> 4) — no metadata has to
    ride through scan/jit.  Works on both the contiguous ('kq') and the
    paged ('pkq' pool) layouts."""
    codes = cache["kq"] if "kq" in cache else cache["pkq"]
    return 8 if codes.dtype == jnp.int8 else 4


def code_dtype(bits: int):
    return jnp.int8 if bits == 8 else jnp.uint8


def packed_dim(d: int, bits: int) -> int:
    """Last-axis length of the code container for a head_dim of ``d``."""
    if bits == 8:
        return d
    assert d % 2 == 0, f"packed-int4 cache needs an even head_dim, got {d}"
    return d // 2


# ------------------------------------------------------------- int4 packing
def pack4(codes: jnp.ndarray) -> jnp.ndarray:
    """Signed int4 codes in [-8, 7] -> uint8, 2 codes/byte along the LAST
    axis (even index -> low nibble).  Cache packing is D-major (the last,
    contiguous axis) — unlike weight packing (K-major), because the cache
    write path appends whole (Hkv, D) rows."""
    assert codes.shape[-1] % 2 == 0, codes.shape
    c = (codes.astype(jnp.int32) & 0xF).astype(jnp.uint8)
    c = c.reshape(*codes.shape[:-1], codes.shape[-1] // 2, 2)
    return (c[..., 0] | (c[..., 1] << 4)).astype(jnp.uint8)


def code_parts(codes: jnp.ndarray, bits: int) -> list:
    """Code container (..., X) -> sign-extended int32 codes, one array per
    packed slot: ``[codes]`` for int8, ``[low nibbles, high nibbles]`` for
    packed int4 (channels 0::2 and 1::2).  Everything is widened to int32
    before any shift, so it also lowers inside a TPU kernel, whose vector
    unit has no 8-bit shift or compare."""
    w = codes.astype(jnp.int32)
    if bits == 8:
        return [w]
    # shift the nibble to the top of the word, arithmetic-shift it back
    return [(w << 28) >> 28, (w << 24) >> 28]


def unpack4(packed: jnp.ndarray, dtype=jnp.float32) -> jnp.ndarray:
    """Inverse of pack4: uint8 (..., D//2) -> sign-extended codes (..., D)."""
    w = jnp.stack(code_parts(packed, 4), axis=-1)
    return w.reshape(*packed.shape[:-1], packed.shape[-1] * 2).astype(dtype)


# ------------------------------------------------------------------- scales
def k_channel_scale(k: jnp.ndarray, lengths, bits: int) -> jnp.ndarray:
    """Per-channel K scale from a request's own prefill rows.

    k: (..., B, S, Hkv, D); lengths: (B,) valid prompt rows per request —
    rows >= lengths[i] are right-pad garbage and MUST NOT reach the max
    (they would both corrupt the grid and break batched-vs-solo parity).
    Returns (..., B, Hkv, D) f32.
    """
    s = k.shape[-3]
    lengths = jnp.asarray(lengths, jnp.int32)
    valid = jnp.arange(s, dtype=jnp.int32)[None, :] < lengths[:, None]
    mag = jnp.where(valid[..., None, None], jnp.abs(k.astype(jnp.float32)),
                    0.0)
    amax = jnp.max(mag, axis=-3)
    return jnp.maximum(amax * K_SCALE_MARGIN, _EPS) / QMAX[bits]


def v_token_scale(v: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Per-token (per-head) V scale, exact at write time.

    v: (..., S, Hkv, D) -> (..., S, Hkv) f32."""
    amax = jnp.max(jnp.abs(v.astype(jnp.float32)), axis=-1)
    return jnp.maximum(amax, _EPS) / QMAX[bits]


# -------------------------------------------------------- quantize/dequant
def _encode(x: jnp.ndarray, scale: jnp.ndarray, bits: int) -> jnp.ndarray:
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                 -QMAX[bits], QMAX[bits])
    if bits == 8:
        return q.astype(jnp.int8)
    return pack4(q.astype(jnp.int8))


def quantize_k(k: jnp.ndarray, k_scale: jnp.ndarray, bits: int) -> jnp.ndarray:
    """k (..., S, Hkv, D) with k_scale (..., Hkv, D) -> codes
    (..., S, Hkv, D or D//2).  Decode rows written after calibration clip
    into the fixed per-channel grid."""
    return _encode(k, k_scale[..., None, :, :], bits)


def quantize_v(v: jnp.ndarray, v_scale: jnp.ndarray, bits: int) -> jnp.ndarray:
    """v (..., S, Hkv, D) with v_scale (..., S, Hkv) -> codes."""
    return _encode(v, v_scale[..., None], bits)


def dequant_k(kq: jnp.ndarray, k_scale: jnp.ndarray, bits: int,
              dtype=jnp.float32) -> jnp.ndarray:
    codes = kq.astype(jnp.float32) if bits == 8 else unpack4(kq)
    return (codes * k_scale[..., None, :, :].astype(jnp.float32)).astype(dtype)


def dequant_v(vq: jnp.ndarray, v_scale: jnp.ndarray, bits: int,
              dtype=jnp.float32) -> jnp.ndarray:
    codes = vq.astype(jnp.float32) if bits == 8 else unpack4(vq)
    return (codes * v_scale[..., None].astype(jnp.float32)).astype(dtype)


# --------------------------------------------------------- page primitives
# The paged serving cache (serve/paging.py, DESIGN.md §3) stores K/V in
# fixed-size PAGES: pool buffers shaped (..., P, page, Hkv, X) indexed
# through a per-slot (B, max_pages) int32 block table.  These are the ONE
# definition of the page read/write layout — models/attention.py (decode
# writes + full-dtype gather reads), kernels/ref.py (the paged-attention
# oracle) and serve/paging.py (admission writes) all go through them, so
# the layouts cannot drift.

def page_count(n_tokens: int, page_size: int) -> int:
    """Pages needed to hold ``n_tokens`` rows (host-side sizing)."""
    return -(-int(n_tokens) // int(page_size))


def gather_pages(pool: jnp.ndarray, tbl: jnp.ndarray) -> jnp.ndarray:
    """Assemble each slot's virtual sequence from its mapped pages.

    pool: (P, page, ...) physical pages; tbl: (B, n) int32 page ids.
    Returns (B, n*page, ...) — logical row ``s`` of slot ``b`` is
    ``pool[tbl[b, s // page], s % page]``.  Rows mapped through stale /
    zero table entries are garbage-until-overwritten exactly like the
    contiguous cache's tail rows: the decode position mask keeps them
    unread.
    """
    b, n = tbl.shape
    page = pool.shape[1]
    # clip, don't wrap: unmapped entries (-1 sentinel / stale ids) must
    # resolve to SOME in-pool page — its rows sit at masked positions
    got = jnp.take(pool, jnp.clip(tbl, 0, pool.shape[0] - 1), axis=0)
    return got.reshape((b, n * page) + pool.shape[2:])


def paged_write_row(pool: jnp.ndarray, new: jnp.ndarray,
                    positions: jnp.ndarray, tbl: jnp.ndarray) -> jnp.ndarray:
    """Write decode rows per slot through the block table.

    pool: (P, page, ...); new: (B, S, ...) — S consecutive rows per slot
    (S == 1 for plain decode, S == k+1 for a speculative verify dispatch);
    positions: (B, S) absolute LOGICAL positions; tbl: (B, n) int32.
    The paged counterpart of models/attention.cache_write: logical
    position ``pos`` lands in page ``tbl[b, pos // page]`` at row
    ``pos % page``.  Distinct logical positions of live slots never
    collide physically (each slot owns its writable pages), so the S-row
    scatter is order-independent and bit-identical to S sequential
    single-row writes.

    Writes through UNMAPPED table entries are dropped, never redirected:
    entries < 0 (the ``set_table_rows`` sentinel beyond a slot's mapped
    range) and positions >= n*page (an evicted slot run past its window)
    push the ROW offset out of range so the ``mode='drop'`` scatter
    drops them.  This is load-bearing for page isolation — a slot whose
    budget ends mid-chunk keeps scanning (and "writing") to advancing
    positions, and in the contiguous layout those overrun writes land in
    its own (B, S_max) rows; here they would land wherever a stale table
    entry points, i.e. in ANOTHER request's page.  The same sentinel
    drop guards speculative verify rows that overrun a slot's claimed
    pages (admission claims worst-case pages, so in-budget rows always
    have a home; rows past the budget drop exactly like decode overrun).
    """
    b, n = tbl.shape
    page = pool.shape[1]
    s = positions.shape[1]
    pos = positions.reshape(b * s)
    rows = jnp.repeat(jnp.arange(b), s)
    page_idx = jnp.clip(pos // page, 0, n - 1)
    phys_raw = tbl[rows, page_idx]
    valid = (pos < n * page) & (phys_raw >= 0)
    phys = jnp.clip(phys_raw, 0, pool.shape[0] - 1)
    off = jnp.where(valid, pos % page, page)     # page -> dropped
    flat = new.reshape((b * s,) + new.shape[2:])
    return pool.at[phys, off].set(flat.astype(pool.dtype), mode="drop")


# -------------------------------------------------------- prefill handoff
def quantize_prefill(got: dict, lengths, bits: int) -> dict:
    """Full-precision prefill cache {'k','v'} (..., B, S_pad, Hkv, D) ->
    quantized cache leaves sized to the prefill.  K scales calibrate on
    the valid rows only; garbage rows still produce (garbage) codes, which
    stay provably unread under the decode mask — the same
    garbage-until-overwritten contract as the full-dtype cache."""
    k, v = got["k"], got["v"]
    ks = k_channel_scale(k, lengths, bits)
    vs = v_token_scale(v, bits)
    return {"kq": quantize_k(k, ks, bits), "k_scale": ks,
            "vq": quantize_v(v, vs, bits), "v_scale": vs}
