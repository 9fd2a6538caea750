"""Pallas kernel: blocked online-softmax (flash) attention.

Prefill at 32k context is the compute hot-spot of the serving path; naive
attention materializes the (S, S) score matrix (32k² × heads — TBs of HBM
traffic).  The kernel streams K/V blocks through VMEM with the online-softmax
recurrence, so HBM traffic is O(S·D) per head and the score tile lives only
in VMEM.

GQA is handled in the index maps: query head h reads K/V head h // group, so
K/V are never materialized at the query-head count.

Grid (B, H, nq, nk), K innermost; running (m, l, acc) in VMEM scratch.
Causal blocks strictly above the diagonal are skipped (no FLOPs, no loads
wasted on masked tiles — ~2× prefill FLOP reduction).

``kv_decode_attention`` is the DECODE counterpart over a QUANTIZED KV cache
(kernels/kv_quant.py layout): one query token per request streams int8 /
packed-int4 K/V code tiles from HBM and dequantizes them IN-REGISTER inside
the score and value sums — a full-precision cache is never materialized
in HBM, so the decode roofline reads 1 (or 0.5) bytes per cache element
instead of 2–4.

``paged_kv_decode_attention`` is the same fused decode over the PAGED
cache layout (serve/paging.py): K/V code pages stream through a
scalar-prefetched (B, max_pages) block table — the physical page id is
dereferenced in the BlockSpec index maps, so the gather never
materializes and unmapped pages are never touched.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import kv_quant

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, nk: int, causal: bool, scale: float):
    i = pl.program_id(2)          # query block
    j = pl.program_id(3)          # kv block

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Skip fully-masked blocks (strictly above the causal diagonal).
    run = (not causal) or (j * bk <= i * bq + bq - 1)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)               # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qi = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kj = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qi >= kj, s, NEG_INF)
        m_prev = m_ref[...]                               # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                            # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                   # (bq, 1)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)               # (bk, d)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


# ------------------------------------------------- quantized-cache decode
# Both decode kernels take every head of one request per grid step: a K/V
# code tile is (rows, Hkv, X), whose last two dims are the cache's own, as
# the TPU block rule asks.  Scores and the value sum are elementwise
# products reduced over lanes (D) and over rows, so no head ever has to be
# sliced out of the middle dim.  Packed int4 splits D into its two nibble
# slots ("parts"): q, the K scale and the output travel part-major,
# (B, parts, group, Hkv, D // parts), so no lane interleave happens
# in-kernel; the wrappers reorder them outside.

def _to_parts(x: jax.Array, parts: int) -> jax.Array:
    """(..., D) -> (parts, ..., D // parts); part i holds channels i::parts
    (pack4's nibble order)."""
    y = x.reshape(*x.shape[:-1], x.shape[-1] // parts, parts)
    return jnp.moveaxis(y, -1, 0)


def _q_parts(q: jax.Array, hkv: int, parts: int) -> jax.Array:
    """q (B, H, D) -> (B, parts, group, Hkv, D // parts); head h is KV head
    h // group, member h % group (the jnp.repeat GQA layout)."""
    b, h, d = q.shape
    y = _to_parts(q.astype(jnp.float32).reshape(b, hkv, h // hkv, d), parts)
    return y.transpose(1, 0, 3, 2, 4)


def _out_from_parts(o: jax.Array) -> jax.Array:
    """Inverse of _q_parts: (B, parts, group, Hkv, dp) -> (B, H, D)."""
    b, parts, group, hkv, dp = o.shape
    return o.transpose(0, 3, 2, 4, 1).reshape(b, hkv * group, dp * parts)


def _decode_block(q_ref, kq_ref, ks_ref, vq_ref, vs, live, m_ref, l_ref,
                  acc_ref, *, bits: int, scale: float):
    """One online-softmax step of every head over one K/V row block.

    kq_ref/vq_ref: (1, rows, Hkv, dp) codes; vs: (rows, Hkv) per-token V
    scales; live: (rows, 1, 1) bool — rows past the position contribute
    exactly 0 (their V is zeroed too, so a poisoned row's NaN cannot smear
    through 0 * NaN).  K dequantizes as codes * per-channel scale, as in
    kv_quant.dequant_k; V's per-token scale folds into the probabilities.
    """
    k = [c.astype(jnp.float32) * ks_ref[0, i][None]
         for i, c in enumerate(kv_quant.code_parts(kq_ref[0], bits))]
    v = [c.astype(jnp.float32)
         for c in kv_quant.code_parts(vq_ref[0], bits)]
    vs = vs.astype(jnp.float32)[:, :, None]             # (rows, Hkv, 1)
    for g in range(m_ref.shape[0]):
        s = sum(jnp.sum(k[i] * q_ref[0, i, g][None], axis=-1, keepdims=True)
                for i in range(len(k))) * scale          # (rows, Hkv, 1)
        s = jnp.where(live, s, NEG_INF)
        m_prev = m_ref[g]                                # (Hkv, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
        p = jnp.exp(s - m_new[None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[g] = l_ref[g] * alpha + jnp.sum(p, axis=0)
        pv = jnp.where(live, p * vs, 0.0)
        for i in range(len(v)):
            acc_ref[i, g] = acc_ref[i, g] * alpha + jnp.sum(pv * v[i],
                                                            axis=0)
        m_ref[g] = m_new


def _decode_finish(o_ref, l_ref, acc_ref):
    for i in range(acc_ref.shape[0]):
        o_ref[0, i] = (acc_ref[i] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


def _decode_init(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _decode_scratch(parts: int, group: int, hkv: int, dp: int) -> list:
    return [pltpu.VMEM((group, hkv, 1), jnp.float32),
            pltpu.VMEM((group, hkv, 1), jnp.float32),
            pltpu.VMEM((parts, group, hkv, dp), jnp.float32)]


def _kv_decode_kernel(layer_ref, pos_ref, q_ref, kq_ref, ks_ref, vq_ref,
                      vs_ref, o_ref, m_ref, l_ref, acc_ref, *, bs: int,
                      ns: int, bits: int, scale: float):
    j = pl.program_id(1)          # kv block (innermost)

    @pl.when(j == 0)
    def _init():
        _decode_init(m_ref, l_ref, acc_ref)

    pos = pos_ref[pl.program_id(0)]

    # Blocks entirely past this request's position are fully masked — skip
    # them (an evicted slot's out-of-range position keeps every block live;
    # its output is discarded upstream, matching the full-dtype path).
    @pl.when(j * bs <= pos)
    def _step():
        kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, 1, 1), 0)
        _decode_block(q_ref, kq_ref, ks_ref, vq_ref, vs_ref[0].T,
                      kpos <= pos, m_ref, l_ref, acc_ref, bits=bits,
                      scale=scale)

    @pl.when(j == ns - 1)
    def _finish():
        _decode_finish(o_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnames=("bits", "bs", "interpret"))
def kv_decode_attention(q: jax.Array, kq: jax.Array, k_scale: jax.Array,
                        vq: jax.Array, v_scale: jax.Array,
                        positions: jax.Array, layer: jax.Array | None = None,
                        bits: int = 8, bs: int = 128,
                        interpret: bool = False) -> jax.Array:
    """Fused dequant decode attention over a quantized KV cache.

    q: (B, H, D) — one query token per request.
    kq/vq: (L, B, S, Hkv, D) int8 or (L, B, S, Hkv, D//2) packed-int4
    uint8 — a stack of layers, of which layer ``layer`` (an int32 scalar)
    is read; v_scale: (L, B, S, Hkv) f32 per-token, stacked the same way.
    An unstacked cache ((B, S, Hkv, ...) codes, (B, S, Hkv) scales, no
    ``layer``) is read as a stack of one.  k_scale: (B, Hkv, D) f32
    per-channel, this layer's; positions: (B,) int32 — rows with s_pos <=
    positions[b] are attended (the serving validity mask).  Returns
    (B, H, D) f32.

    Grid (B, ns), S innermost, all heads per step; K/V code tiles
    dequantize in-register right before use, so HBM only ever streams the
    1-byte (or half-byte) codes.  The layer rides in as a scalar-prefetch
    operand and the code and scale index maps select it, so a decode step
    inside the layer scan reads its layer straight out of the carried
    stack, with no slab copied out to feed the call.  D is deliberately
    NOT blocked (head_dim is small), so only S must divide ``bs`` — the
    dispatch layer (kernels/ops) picks a divisor for non-tile-multiple S.
    V scales travel S-minor, as (Hkv, bs) tiles whose lanes the rows fill
    (XLA keeps the scale stack in that layout), so on the chip ``bs`` is
    a multiple of 128 or all of S.
    """
    if layer is None:
        kq, vq, v_scale, layer = kq[None], vq[None], v_scale[None], 0
    b, h, d = q.shape
    _, _, s, hkv, dp = kq.shape
    assert h % hkv == 0, (h, hkv)
    group = h // hkv
    parts = 1 if bits == 8 else 2
    assert dp * parts == d, (kq.shape, d, bits)
    assert vq.shape == kq.shape, (vq.shape, kq.shape)
    assert v_scale.shape == kq.shape[:4], (v_scale.shape, kq.shape)
    bs = min(bs, s)
    assert s % bs == 0, (s, bs)
    ns = s // bs

    # index maps receive the grid indices, then the scalar-prefetch refs
    # (layer, positions)
    def rows(b, j, lyr, p):
        return (lyr[0], b, j, 0, 0)

    def slot(b, j, lyr, p):
        return (b, 0, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,           # layer, positions
        grid=(b, ns),
        in_specs=[
            pl.BlockSpec((1, parts, group, hkv, dp), slot),
            pl.BlockSpec((pl.squeezed, 1, bs, hkv, dp), rows),
            pl.BlockSpec((1, parts, hkv, dp),
                         lambda b, j, lyr, p: (b, 0, 0, 0)),
            pl.BlockSpec((pl.squeezed, 1, bs, hkv, dp), rows),
            pl.BlockSpec((pl.squeezed, 1, hkv, bs),
                         lambda b, j, lyr, p: (lyr[0], b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, parts, group, hkv, dp), slot),
        scratch_shapes=_decode_scratch(parts, group, hkv, dp),
    )
    out = pl.pallas_call(
        functools.partial(_kv_decode_kernel, bs=bs, ns=ns, bits=bits,
                          scale=d ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, parts, group, hkv, dp),
                                       jnp.float32),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      positions.astype(jnp.int32), _q_parts(q, hkv, parts), kq,
      jnp.moveaxis(_to_parts(k_scale, parts), 0, 1), vq,
      jnp.swapaxes(v_scale, -1, -2))
    return _out_from_parts(out)


def _paged_kv_decode_kernel(tbl_ref, pos_ref, q_ref, kq_ref, ks_ref, vq_ref,
                            vs_ref, o_ref, m_ref, l_ref, acc_ref, *,
                            page: int, np_max: int, bits: int, scale: float):
    j = pl.program_id(1)          # logical page (innermost)
    b = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        _decode_init(m_ref, l_ref, acc_ref)

    pos = pos_ref[b, 0]

    # Pages entirely past this slot's position are fully masked — skip
    # them (their table entries may be stale/zero; the guard is what
    # keeps unmapped physical pages, even NaN-poisoned ones, unread).
    @pl.when(j * page <= pos)
    def _step():
        kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, (page, 1, 1),
                                                   0)
        _decode_block(q_ref, kq_ref, ks_ref, vq_ref, vs_ref[0], kpos <= pos,
                      m_ref, l_ref, acc_ref, bits=bits, scale=scale)

    @pl.when(j == np_max - 1)
    def _finish():
        _decode_finish(o_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def paged_kv_decode_attention(q: jax.Array, kq_pool: jax.Array,
                              k_scale: jax.Array, vq_pool: jax.Array,
                              v_scale_pool: jax.Array, tbl: jax.Array,
                              positions: jax.Array, bits: int = 8,
                              interpret: bool = False) -> jax.Array:
    """Fused dequant decode attention over a PAGED quantized KV cache.

    q: (B, H, D) — one query token per slot.
    kq_pool/vq_pool: (P, page, Hkv, D) int8 or (P, page, Hkv, D//2)
    packed-int4 uint8 physical pages; v_scale_pool: (P, page, Hkv) f32
    per-token scales riding their pages; k_scale: (B, Hkv, D) f32
    per-slot per-channel; tbl: (B, n_pages) int32 block table;
    positions: (B,) int32.  Returns (B, H, D) f32.

    Grid (B, n_pages), pages innermost, all heads per step: the block
    table rides in as a SCALAR-PREFETCH operand, so each K/V tile's index
    map dereferences ``tbl[b, j]`` — the kernel streams physical pages
    straight from HBM in logical order, dequantizes in-register, and never
    materializes the gathered sequence (the ref oracle's gather is the
    semantic spec, not the traffic model).  One page (16 rows by default)
    per grid step is sublane-aligned but narrow; fusing multiple pages per
    step is a perf follow-up, not a correctness concern.

    Tensor-parallel note: every count here — the GQA ``group``, ``hkv`` —
    derives from the LOCAL operand shapes, so under ``shard_map`` with
    head-sharded pools each shard streams pages for ITS KV heads through
    the same replicated block table with zero mesh awareness (DESIGN.md
    §3, paged sharding).
    """
    b, h, d = q.shape
    p_phys, page, hkv, dp = kq_pool.shape
    assert h % hkv == 0, (h, hkv)
    group = h // hkv
    parts = 1 if bits == 8 else 2
    assert dp * parts == d, (kq_pool.shape, d, bits)
    assert vq_pool.shape == kq_pool.shape, (vq_pool.shape, kq_pool.shape)
    assert v_scale_pool.shape == kq_pool.shape[:3], v_scale_pool.shape
    np_max = tbl.shape[1]
    pos2 = positions.reshape(b, 1).astype(jnp.int32)

    # index maps receive the grid indices first, then the scalar-prefetch
    # refs (tbl, positions) as trailing arguments; the physical page is
    # clamped so stale entries (masked pages) never index out of the pool
    def kv_map(b, j, t, p):
        return (jnp.clip(t[b, j], 0, p_phys - 1), 0, 0, 0)

    def vs_map(b, j, t, p):
        return (jnp.clip(t[b, j], 0, p_phys - 1), 0, 0)

    def slot_map(b, j, t, p):
        return (b, 0, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,           # tbl, positions
        grid=(b, np_max),
        in_specs=[
            pl.BlockSpec((1, parts, group, hkv, dp), slot_map),
            pl.BlockSpec((1, page, hkv, dp), kv_map),
            pl.BlockSpec((1, parts, hkv, dp),
                         lambda b, j, t, p: (b, 0, 0, 0)),
            pl.BlockSpec((1, page, hkv, dp), kv_map),
            pl.BlockSpec((1, page, hkv), vs_map),
        ],
        out_specs=pl.BlockSpec((1, parts, group, hkv, dp), slot_map),
        scratch_shapes=_decode_scratch(parts, group, hkv, dp),
    )
    out = pl.pallas_call(
        functools.partial(_paged_kv_decode_kernel, page=page, np_max=np_max,
                          bits=bits, scale=d ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, parts, group, hkv, dp),
                                       jnp.float32),
        interpret=interpret,
    )(tbl.astype(jnp.int32), pos2, _q_parts(q, hkv, parts), kq_pool,
      jnp.moveaxis(_to_parts(k_scale, parts), 0, 1), vq_pool, v_scale_pool)
    return _out_from_parts(out)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "interpret", "scale"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, bq: int = 128, bk: int = 128,
                    scale: float | None = None,
                    interpret: bool = False) -> jax.Array:
    """q: (B, H, S, D); k, v: (B, Hkv, S, D) with H % Hkv == 0 -> (B, H, S, D)."""
    b, h, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert h % hkv == 0
    group = h // hkv
    bq = min(bq, sq)
    bk = min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0
    if scale is None:
        scale = d ** -0.5
    grid = (b, h, sq // bq, sk // bk)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, nk=grid[3],
                          causal=causal, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out
