"""Attention variants: GQA/MQA, MLA (DeepSeek-V3), bidirectional encoder.

Memory strategy (TPU-adapted): anything past ~2k sequence runs through
``chunked_attention`` — a pure-JAX online-softmax scan over KV chunks whose
HLO is the XLA counterpart of kernels/flash_attention.py (on TPU the Pallas
kernel takes over via kernels/ops dispatch).  The (S, S) score matrix is
never materialized.

MLA keeps the *compressed* KV cache (c_kv ⊕ k_rope = 576 floats/token):
  - prefill/train: K/V are expanded lazily per KV-chunk inside the scan, so
    expansion memory is O(chunk), not O(S).
  - decode: the absorbed form — q̃ = W_uk^T q attends directly over c_kv and
    the value path up-projects once after the softmax (never materializes
    per-head K/V at 32k context).
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import kv_quant as kvq
from repro.kernels import ops as kops
from repro.models import common
from repro.models.common import init_qdense, qproj

DEFAULT_CHUNK = 512


# ----------------------------------------------------------------- chunked
def chunked_attention(q: jax.Array,
                      kv_fn: Callable[[jax.Array], Tuple[jax.Array, jax.Array]],
                      n_chunks: int, chunk: int,
                      causal: bool, q_offset: int = 0,
                      scale: Optional[float] = None) -> jax.Array:
    """Online-softmax attention over lazily-produced KV chunks.

    q: (B, S, H, D). kv_fn(i) -> (k, v) each (B, chunk, H, D) for chunk i.
    Returns (B, S, H, D).
    """
    b, s, h, d = q.shape
    if scale is None:
        scale = d ** -0.5
    qf = q.astype(jnp.float32) * scale
    q_pos = q_offset + jnp.arange(s)

    def step(carry, i):
        m, l, acc = carry
        k, v = kv_fn(i)
        kf = k.astype(jnp.float32)
        logits = jnp.einsum("bshd,bchd->bhsc", qf, kf)       # (B,H,S,c)
        if causal:
            k_pos = i * chunk + jnp.arange(chunk)
            mask = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(mask[None, None], logits, -1e30)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bhsc,bchd->bhsd", p, v.astype(jnp.float32))
        acc_new = acc * alpha[..., 0][..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, s, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((b, h, s, 1), jnp.float32)
    a0 = jnp.zeros((b, h, s, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), jnp.arange(n_chunks))
    out = acc / jnp.maximum(l[..., 0][..., None], 1e-30)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)   # (B,S,H,D)


def cache_write(cache_arr: jax.Array, new: jax.Array,
                positions: jax.Array,
                layer: Optional[jax.Array] = None) -> jax.Array:
    """Write decode-step entries per request into a (B, S_max, ...) cache,
    or, with ``layer``, into layer ``layer`` of an (L, B, S_max, ...)
    stack.

    new: (B, S, ...) — S consecutive K/V rows per request (S == 1 for the
    scanned decode step, S == k+1 for a speculative verify dispatch);
    positions: (B, S) absolute write positions, PER REQUEST (continuous
    batching slots requests with unequal prompt lengths into one batch, so
    there is no shared scalar position).  Implemented as a batched row
    scatter (O(B·S·H·D) traffic) rather than a one-hot select over the
    whole buffer.  Inside the decode layer scan the stack is the scan's
    carry and the rows land at ``[layer, b, pos]``, so XLA updates the
    carried buffer in place and no layer slab is sliced out or written
    back.  ``mode='drop'`` makes out-of-range positions (>= S_max, e.g. an
    evicted slot that ran past its window) write nothing.  Positions
    within a request are distinct, so the multi-row scatter is
    bit-identical to S sequential writes.
    """
    b = new.shape[0]
    rows = (jnp.arange(b)[:, None], positions)
    if layer is not None:
        rows = (layer,) + rows
    return cache_arr.at[rows].set(new.astype(cache_arr.dtype), mode="drop")


def reads_by_layer(cache) -> bool:
    """Whether ``gqa_apply`` takes this decode cache as a carried layer
    stack plus its ``layer`` index: the contiguous GQA caches (full-dtype
    ``k``/``v``, quantized ``kq``/``vq``), whose rows are written in place
    at ``[layer, b, pos]`` and whose layer the attention reads by index.
    Paged pools, MLA latents and recurrent state are sliced out of the
    stack per layer and written back."""
    return isinstance(cache, dict) and ("k" in cache or "kq" in cache)


def _at(a: jax.Array, layer: Optional[jax.Array]) -> jax.Array:
    """``a`` at this block's layer: read by index from a carried (L, ...)
    stack when ``layer`` is set, else ``a`` itself."""
    return a if layer is None else jax.lax.dynamic_index_in_dim(
        a, layer, 0, keepdims=False)


def _repeat_kv(x: jax.Array, group: int) -> jax.Array:
    """(B, S, Hkv, D) -> (B, S, Hkv*group, D)."""
    if group == 1:
        return x
    b, s, hkv, d = x.shape
    x = jnp.broadcast_to(x[:, :, :, None, :], (b, s, hkv, group, d))
    return x.reshape(b, s, hkv * group, d)


def _dense_decode_attention(q: jax.Array, ck: jax.Array, cv: jax.Array,
                            positions: jax.Array, group: int) -> jax.Array:
    """Per-query masked dense softmax over a contiguous (B, S_max, Hkv, D)
    cache — the full-dtype decode math, shared between the contiguous
    decode branch and the chunked-prefill STAGING read (which must be
    bitwise-identical to it so a staged prefill row computes exactly what
    a full-dtype decode row would).  Returns (B, S, H, D) float32.
    """
    dh = q.shape[-1]
    kk = _repeat_kv(ck, group)
    vv = _repeat_kv(cv, group)
    logits = jnp.einsum("bqhd,bshd->bhqs", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) * (dh ** -0.5)
    s_pos = jnp.arange(ck.shape[1])
    mask = s_pos[None, None, None, :] <= positions[:, None, :, None]
    logits = jnp.where(mask, logits, -1e30)
    pr = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqs,bshd->bqhd", pr, vv.astype(jnp.float32))


# --------------------------------------------------------------------- GQA
def init_gqa(key, cfg) -> dict:
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": init_qdense(ks[0], d, h * dh, cfg.param_dtype),
        "wk": init_qdense(ks[1], d, hkv * dh, cfg.param_dtype),
        "wv": init_qdense(ks[2], d, hkv * dh, cfg.param_dtype),
        "wo": init_qdense(ks[3], h * dh, d, cfg.param_dtype),
    }


def gqa_apply(p, x, bits, cfg, mode: str, cache, positions,
              mrope_positions=None):
    """x: (B, S, d). bits: {'attn_qkv', 'attn_wo'}. Returns (y, cache)."""
    b, s, d = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    group = h // hkv
    causal = cfg.causal

    q = qproj(x, p["wq"], bits["attn_qkv"]).reshape(b, s, h, dh)
    k = qproj(x, p["wk"], bits["attn_qkv"]).reshape(b, s, hkv, dh)
    v = qproj(x, p["wv"], bits["attn_qkv"]).reshape(b, s, hkv, dh)

    if cfg.rope == "rope":
        cos, sin = common.rope_angles(positions, dh, cfg.rope_base)
        q, k = common.apply_rope(q, cos, sin), common.apply_rope(k, cos, sin)
    elif cfg.rope == "mrope":
        cos, sin = common.mrope_angles(mrope_positions, dh,
                                       cfg.mrope_sections, cfg.rope_base)
        q, k = common.apply_rope(q, cos, sin), common.apply_rope(k, cos, sin)

    if mode == "decode" and isinstance(cache, dict) and "pkq" in cache:
        # PAGED quantized serving cache (serve/paging.py): physical page
        # pools + a block table ("tbl", injected per dispatch by the
        # engine).  Identical quantization semantics to the contiguous
        # quantized cache — the new row quantizes against the slot's
        # prefill-calibrated per-channel K grid and its own exact V row
        # scale — only the row addressing goes through the table, so
        # paged decode is bit-exact with contiguous decode.
        tbl = cache["tbl"]
        cbits = kvq.cache_bits(cache)
        role = cache.get("role")
        if role is not None:
            # fused chunked-prefill dispatch (serve/kv_cache.with_staging):
            # prefilling rows must not write provisional codes — their K
            # grid calibrates over the WHOLE prompt at finalize — so their
            # quant-pool writes are suppressed (pos >= n*page drops in
            # paged_write_row) and they write/read full-dtype STAGING
            # buffers instead; decode rows run the quant path untouched
            # and their staging writes drop at the staging sentinel.
            n_virt = jnp.int32(tbl.shape[-1] * cache["pkq"].shape[1])
            main_pos = jnp.where(role[:, None], n_virt, positions)
            stage_pos = jnp.where(role[:, None], positions,
                                  jnp.int32(cache["sk"].shape[1]))
            sk = cache_write(cache["sk"], k, stage_pos)
            sv = cache_write(cache["sv"], v, stage_pos)
            staged = _dense_decode_attention(q, sk, sv, positions, group)
        else:
            main_pos = positions
        kq_new = kvq.quantize_k(k, cache["k_scale"], cbits)
        vs_new = kvq.v_token_scale(v, cbits)
        vq_new = kvq.quantize_v(v, vs_new, cbits)
        ck = kvq.paged_write_row(cache["pkq"], kq_new, main_pos, tbl)
        cv = kvq.paged_write_row(cache["pvq"], vq_new, main_pos, tbl)
        cvs = kvq.paged_write_row(cache["pv_scale"], vs_new, main_pos, tbl)
        if s == 1 and role is None:
            out = kops.paged_kv_cache_attention(
                q[:, 0], ck, cache["k_scale"], cv, cvs, tbl,
                positions[:, 0], cbits)[:, None]
        else:
            # Speculative verify: S = k+1 rows per slot enter the cache in
            # one dispatch, then each query position runs the SAME
            # single-query kernel (vmapped over the query axis) with its
            # own position mask — so per-position outputs are bit-exact
            # with the sequential decode that would have produced them.
            # The K rows quantize against the FIXED prefill-calibrated
            # per-channel grid and V scales are per-row, so the batched
            # write produces byte-identical codes to sequential writes.
            # impl='ref' — a dedicated multi-query Pallas kernel is future
            # work; off-TPU 'auto' resolves to ref anyway.
            def _att(qi, pi):
                return kops.paged_kv_cache_attention(
                    qi, ck, cache["k_scale"], cv, cvs, tbl, pi, cbits,
                    impl="ref")
            out = jax.vmap(_att, in_axes=(1, 1), out_axes=1)(q, positions)
        if role is not None:
            # per-row select: prefilling rows take the staged full-dtype
            # output (bitwise the contiguous full-dtype decode math),
            # decode rows the quant-kernel output; both paths are finite
            # everywhere, so the discarded side never poisons the select
            out = jnp.where(role[:, None, None, None],
                            staged.astype(x.dtype), out.astype(x.dtype))
        out = out.astype(x.dtype).reshape(b, s, h * dh)
        y = qproj(out, p["wo"], bits["attn_wo"])
        new = {"pkq": ck, "k_scale": cache["k_scale"],
               "pvq": cv, "pv_scale": cvs, "tbl": tbl}
        if role is not None:
            new.update(sk=sk, sv=sv, role=role)
        return y, new

    if mode == "decode" and isinstance(cache, dict) and "pk" in cache:
        # PAGED full-dtype serving cache: page pools in the cache dtype.
        # Gather each slot's virtual sequence through its table row, then
        # run EXACTLY the contiguous full-dtype decode math below — masked
        # softmax rows contribute exactly 0 either way, so paged decode is
        # bit-exact with contiguous decode regardless of what unmapped
        # pages hold.
        tbl = cache["tbl"]
        ck = kvq.paged_write_row(cache["pk"], k, positions, tbl)
        cv = kvq.paged_write_row(cache["pv"], v, positions, tbl)
        kk = _repeat_kv(kvq.gather_pages(ck, tbl), group)
        vv = _repeat_kv(kvq.gather_pages(cv, tbl), group)
        s_virt = kk.shape[1]
        logits = jnp.einsum("bqhd,bshd->bhqs", q.astype(jnp.float32),
                            kk.astype(jnp.float32)) * (dh ** -0.5)
        s_pos = jnp.arange(s_virt)
        # per-query causal mask: query at positions[:, i] reads rows
        # <= positions[:, i] — for S == 1 this is the plain decode mask,
        # for a speculative verify dispatch (S = k+1) each draft position
        # sees exactly the prefix a sequential decode would have seen.
        mask = s_pos[None, None, None, :] <= positions[:, None, :, None]
        logits = jnp.where(mask, logits, -1e30)
        pr = jax.nn.softmax(logits, axis=-1)
        # zero masked V rows: their weight is exactly 0, but a poisoned
        # free page's NaN would still smear through 0 * NaN.  Zero past
        # the LAST query position — the in-flight rows before it were
        # just written (finite), and earlier queries give them exactly-0
        # softmax weight, so keeping them is bit-neutral.
        vv = jnp.where(s_pos[None, :, None, None]
                       <= positions[:, -1:, None, None],
                       vv.astype(jnp.float32), 0.0)
        out = jnp.einsum("bhqs,bshd->bqhd", pr, vv)
        out = out.astype(x.dtype).reshape(b, s, h * dh)
        y = qproj(out, p["wo"], bits["attn_wo"])
        return y, {"pk": ck, "pv": cv, "tbl": tbl}

    if mode == "decode" and isinstance(cache, dict) and "kq" in cache:
        # QUANTIZED serving cache (kernels/kv_quant.py): int8 / packed-int4
        # codes + per-channel K / per-token V f32 scales.  The new row is
        # quantized at write (K against the request's prefill-calibrated
        # per-channel grid, V with its own exact row scale) and attention
        # reads the codes through the fused dequant kernel — a
        # full-precision cache is never materialized in HBM.
        # ``layer`` set: the leaves are the layer scan's carried stacks
        # (transformer.apply), written at [layer, b, pos] and read by index
        cbits = kvq.cache_bits(cache)
        layer = cache.get("layer")
        k_scale = _at(cache["k_scale"], layer)
        role = _at(cache["role"], layer) if "role" in cache else None
        if role is not None:
            # fused chunked-prefill dispatch — same staging contract as
            # the paged quant branch above: prefilling rows suppress
            # their quant writes (pos >= S_max drops in cache_write) and
            # run full-dtype through the staging buffers instead.
            main_pos = jnp.where(role[:, None],
                                 jnp.int32(cache["kq"].shape[-3]), positions)
            stage_pos = jnp.where(role[:, None], positions,
                                  jnp.int32(cache["sk"].shape[-3]))
            sk = cache_write(cache["sk"], k, stage_pos, layer)
            sv = cache_write(cache["sv"], v, stage_pos, layer)
            staged = _dense_decode_attention(q, _at(sk, layer),
                                             _at(sv, layer), positions, group)
        else:
            main_pos = positions
        kq_new = kvq.quantize_k(k, k_scale, cbits)
        vs_new = kvq.v_token_scale(v, cbits)
        vq_new = kvq.quantize_v(v, vs_new, cbits)
        ck = cache_write(cache["kq"], kq_new, main_pos, layer)
        cv = cache_write(cache["vq"], vq_new, main_pos, layer)
        cvs = cache_write(cache["v_scale"], vs_new, main_pos, layer)
        if s == 1 and role is None:
            out = kops.kv_cache_attention(q[:, 0], ck, k_scale, cv, cvs,
                                          positions[:, 0], cbits,
                                          layer=layer)[:, None]
        else:
            # Speculative verify (S = k+1): batched writes are
            # byte-identical to sequential writes (K quantizes against
            # the FIXED prefill grid, V scales are per-row), and each
            # query position vmaps the SAME single-query kernel with its
            # own mask — bit-exact per position vs sequential decode.
            # impl='ref': no multi-query Pallas kernel yet (future work).
            def _att(qi, pi):
                return kops.kv_cache_attention(qi, ck, k_scale, cv, cvs, pi,
                                               cbits, impl="ref",
                                               layer=layer)
            out = jax.vmap(_att, in_axes=(1, 1), out_axes=1)(q, positions)
        if role is not None:
            out = jnp.where(role[:, None, None, None],
                            staged.astype(x.dtype), out.astype(x.dtype))
        out = out.astype(x.dtype).reshape(b, s, h * dh)
        y = qproj(out, p["wo"], bits["attn_wo"])
        new = {"kq": ck, "k_scale": cache["k_scale"],
               "vq": cv, "v_scale": cvs}
        if role is not None:
            new.update(sk=sk, sv=sv, role=cache["role"])
        return y, new

    if mode == "decode":
        # cache: {'k','v'} (B, S_max, Hkv, dh); positions: (B, S) abs pos,
        # per request (slots in a continuous batch decode at different
        # positions).  S == 1 for the scanned decode step; S == k+1 for a
        # speculative verify dispatch, where the per-query mask below
        # gives each draft position exactly the prefix a sequential
        # decode would have seen.
        layer = cache.get("layer")
        ck = cache_write(cache["k"], k, positions, layer)
        cv = cache_write(cache["v"], v, positions, layer)
        out = _dense_decode_attention(q, _at(ck, layer), _at(cv, layer),
                                      positions, group)
        out = out.astype(x.dtype).reshape(b, s, h * dh)
        y = qproj(out, p["wo"], bits["attn_wo"])
        return y, {"k": ck, "v": cv}

    if mode == "prefill" and isinstance(cache, dict) and "pk" in cache:
        # SUFFIX prefill over shared prefix pages (paged full-dtype cache,
        # serve/paging.py prefix sharing): the unshared suffix tokens run
        # a normal prefill pass, but their attention extends over the
        # prefix K/V gathered from the shared pages.  ``positions`` carry
        # the absolute offsets (arange(prefix_len, prefix_len + s_pad)),
        # so RoPE and the causal mask line up with what a full-prompt
        # prefill would compute; rows past the valid suffix (right pad /
        # stale pool rows) sit at future positions and stay causally
        # masked.  Exactness vs the full-prompt prefill: the prefix rows
        # are bit-identical (cache dtype == compute dtype in serving) and
        # the only deviation is online-softmax chunk-order noise, which
        # the next activation fake-quant snaps back onto the shared grid
        # (DESIGN.md §3).  Single-request admission path only.
        assert b == 1, "suffix prefill is a single-request admission path"
        tbl = cache["tbl"]
        kk_virt = kvq.gather_pages(cache["pk"], tbl)   # (1, S_virt, hkv, dh)
        vv_virt = kvq.gather_pages(cache["pv"], tbl)
        off = positions[0, 0]
        kk_virt = jax.lax.dynamic_update_slice(
            kk_virt, k.astype(kk_virt.dtype), (0, off, 0, 0))
        vv_virt = jax.lax.dynamic_update_slice(
            vv_virt, v.astype(vv_virt.dtype), (0, off, 0, 0))
        s_virt = kk_virt.shape[1]
        chunk = min(DEFAULT_CHUNK, s_virt)
        n_chunks = -(-s_virt // chunk)
        pad = n_chunks * chunk - s_virt
        kp = jnp.pad(kk_virt, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(vv_virt, ((0, 0), (0, pad), (0, 0), (0, 0)))

        def kv_fn(i):
            kc = jax.lax.dynamic_slice_in_dim(kp, i * chunk, chunk, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(vp, i * chunk, chunk, axis=1)
            return _repeat_kv(kc, group), _repeat_kv(vc, group)

        out = chunked_attention(q, kv_fn, n_chunks, chunk, causal=True,
                                q_offset=off)
        out = out.reshape(b, s, h * dh)
        y = qproj(out, p["wo"], bits["attn_wo"])
        # hand back ONLY the fresh suffix rows — the engine writes them
        # into the slot's unshared pages (serve/paging.write_prefill)
        return y, {"k": k.astype(cfg.cache_dtype),
                   "v": v.astype(cfg.cache_dtype)}

    # train / prefill: chunked flash-style attention.
    chunk = min(DEFAULT_CHUNK, s)
    n_chunks = s // chunk if s % chunk == 0 else -(-s // chunk)
    pad = n_chunks * chunk - s
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    if pad and not causal:
        # mask padded keys for bidirectional attention via -inf value trick:
        # handled by masking in kv_fn below using a large negative logit is
        # not possible here, so pad keys attend-nowhere by zero v and
        # duplicate k — acceptable only if pad==0; enforce instead:
        raise ValueError("bidirectional attention requires S % chunk == 0")

    def kv_fn(i):
        kc = jax.lax.dynamic_slice_in_dim(kp, i * chunk, chunk, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(vp, i * chunk, chunk, axis=1)
        return _repeat_kv(kc, group), _repeat_kv(vc, group)

    out = chunked_attention(q, kv_fn, n_chunks, chunk, causal)
    out = out.reshape(b, s, h * dh)
    y = qproj(out, p["wo"], bits["attn_wo"])
    new_cache = None
    if mode == "prefill":
        new_cache = {"k": k.astype(cfg.cache_dtype), "v": v.astype(cfg.cache_dtype)}
    return y, new_cache


# --------------------------------------------------------------------- MLA
def init_mla(key, cfg) -> dict:
    d, h = cfg.d_model, cfg.n_heads
    ql, kvl = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq_a": init_qdense(ks[0], d, ql, cfg.param_dtype),
        "q_norm": common.init_norm("rms", ql, cfg.param_dtype),
        "wq_b": init_qdense(ks[1], ql, h * (dn + dr), cfg.param_dtype),
        "wkv_a": init_qdense(ks[2], d, kvl + dr, cfg.param_dtype),
        "kv_norm": common.init_norm("rms", kvl, cfg.param_dtype),
        "wk_b": init_qdense(ks[3], kvl, h * dn, cfg.param_dtype),
        "wv_b": init_qdense(ks[4], kvl, h * dv, cfg.param_dtype),
        "wo": init_qdense(ks[5], h * dv, d, cfg.param_dtype),
    }


def mla_apply(p, x, bits, cfg, mode: str, cache, positions,
              mrope_positions=None):
    """DeepSeek-V3 Multi-head Latent Attention with compressed KV cache."""
    b, s, d = x.shape
    h = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvl = cfg.kv_lora_rank
    scale = (dn + dr) ** -0.5

    # Queries.
    q_c = common.rms_norm(qproj(x, p["wq_a"], bits["attn_q_a"]),
                          p["q_norm"]["scale"])
    q_full = qproj(q_c, p["wq_b"], bits["attn_q_b"]).reshape(b, s, h, dn + dr)
    q_nope, q_rope = q_full[..., :dn], q_full[..., dn:]
    cos, sin = common.rope_angles(positions, dr, cfg.rope_base)
    q_rope = common.apply_rope(q_rope, cos, sin)

    # Compressed KV.
    kv_full = qproj(x, p["wkv_a"], bits["attn_q_a"])          # linked with wq_a
    c_kv = common.rms_norm(kv_full[..., :kvl], p["kv_norm"]["scale"])
    k_rope = kv_full[..., kvl:].reshape(b, s, 1, dr)
    k_rope = common.apply_rope(k_rope, cos, sin)              # (B,S,1,dr)

    wk_b_q = common.weight_of(p["wk_b"], bits["attn_kv_b"]).reshape(
        kvl, h, dn)
    wv_b_q = common.weight_of(p["wv_b"], bits["attn_kv_b"]).reshape(
        kvl, h, dv)

    if mode == "decode":
        ckv = cache_write(cache["c_kv"], c_kv, positions)
        ckr = cache_write(cache["k_rope"], k_rope[:, :, 0], positions)
        # Absorbed decode: q̃ = W_uk^T q_nope, attend over c_kv directly.
        q_t = jnp.einsum("bqhd,chd->bqhc", q_nope,
                         wk_b_q.astype(q_nope.dtype))         # (B,1,H,kvl)
        logits = (jnp.einsum("bqhc,bsc->bhqs", q_t.astype(jnp.float32),
                             ckv.astype(jnp.float32)) +
                  jnp.einsum("bqhr,bsr->bhqs", q_rope.astype(jnp.float32),
                             ckr.astype(jnp.float32))) * scale
        s_pos = jnp.arange(ckv.shape[1])
        # per-query mask (S > 1 = speculative verify, same as GQA decode)
        mask = s_pos[None, None, None, :] <= positions[:, None, :, None]
        logits = jnp.where(mask, logits, -1e30)
        pr = jax.nn.softmax(logits, axis=-1)
        o_c = jnp.einsum("bhqs,bsc->bqhc", pr, ckv.astype(jnp.float32))
        out = jnp.einsum("bqhc,chd->bqhd", o_c.astype(x.dtype),
                         wv_b_q.astype(x.dtype))
        out = out.reshape(b, s, h * dv)
        y = qproj(out, p["wo"], bits["attn_wo"])
        return y, {"c_kv": ckv, "k_rope": ckr}

    # train / prefill: lazy per-chunk K/V expansion.
    chunk = min(DEFAULT_CHUNK, s)
    assert s % chunk == 0, (s, chunk)
    n_chunks = s // chunk
    wk_q = wk_b_q.astype(x.dtype)
    wv_q = wv_b_q.astype(x.dtype)
    q_cat = jnp.concatenate([q_nope, q_rope], axis=-1)        # (B,S,H,dn+dr)

    def kv_fn(i):
        cc = jax.lax.dynamic_slice_in_dim(c_kv, i * chunk, chunk, axis=1)
        cr = jax.lax.dynamic_slice_in_dim(k_rope, i * chunk, chunk, axis=1)
        k_nope = jnp.einsum("bsc,chd->bshd", cc, wk_q)
        k_cat = jnp.concatenate(
            [k_nope, jnp.broadcast_to(cr, (b, chunk, h, dr))], axis=-1)
        v = jnp.einsum("bsc,chd->bshd", cc, wv_q)
        # pad v's head_dim up to k's so one scan handles both; slice after.
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dn + dr - dv)))
        return k_cat, v

    out = chunked_attention(q_cat, kv_fn, n_chunks, chunk, causal=True,
                            scale=scale)
    out = out[..., :dv].reshape(b, s, h * dv)
    y = qproj(out, p["wo"], bits["attn_wo"])
    new_cache = None
    if mode == "prefill":
        new_cache = {"c_kv": c_kv.astype(cfg.cache_dtype),
                     "k_rope": k_rope[:, :, 0].astype(cfg.cache_dtype)}
    return y, new_cache


# ------------------------------------------------------------------- cache
def init_gqa_cache(cfg, batch: int, max_seq: int, dtype=None) -> dict:
    dtype = cfg.cache_dtype if dtype is None else dtype
    return {
        "k": jnp.zeros((batch, max_seq, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, max_seq, cfg.n_kv_heads, cfg.head_dim), dtype),
    }


def init_gqa_quant_cache(cfg, batch: int, max_seq: int, bits: int) -> dict:
    """Quantized GQA cache buffers (kernels/kv_quant.py layout).

    Codes: (B, S_max, Hkv, D) int8 or (B, S_max, Hkv, D//2) packed-int4
    uint8.  K scales are per-request per-channel (B, Hkv, D) — calibrated
    at splice/admission from each request's own prefill; V scales are
    per-token (B, S_max, Hkv), written alongside each row.
    """
    assert bits in (4, 8), bits
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    dp = kvq.packed_dim(dh, bits)
    dt = kvq.code_dtype(bits)
    return {
        "kq": jnp.zeros((batch, max_seq, hkv, dp), dt),
        # ones, not zeros: a never-admitted slot's garbage decode writes
        # divide by k_scale, and 0/0 would smear NaN codes into rows the
        # masking argument otherwise keeps harmless.
        "k_scale": jnp.ones((batch, hkv, dh), jnp.float32),
        "vq": jnp.zeros((batch, max_seq, hkv, dp), dt),
        "v_scale": jnp.zeros((batch, max_seq, hkv), jnp.float32),
    }


def init_gqa_paged_cache(cfg, batch: int, n_pages: int, page_size: int,
                         dtype=None) -> dict:
    """Paged full-dtype GQA cache: physical page pools (serve/paging.py).

    Pools are (P, page, Hkv, D) — no batch axis; slots map logical pages
    to physical pages through the engine-held (B, max_pages) block table
    (injected per dispatch as the layer dict's ``tbl`` entry).  Unmapped
    pages are garbage-until-mapped; the decode position mask keeps them
    unread exactly like the contiguous cache's tail rows.
    """
    dtype = cfg.cache_dtype if dtype is None else dtype
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    return {
        "pk": jnp.zeros((n_pages, page_size, hkv, dh), dtype),
        "pv": jnp.zeros((n_pages, page_size, hkv, dh), dtype),
    }


def init_gqa_paged_quant_cache(cfg, batch: int, n_pages: int, page_size: int,
                               bits: int) -> dict:
    """Paged quantized GQA cache (kernels/kv_quant.py code layout).

    Codes and the per-token V scales ride PER PAGE ((P, page, ...) pools);
    the per-channel K scale stays PER SLOT ((B, Hkv, D), exactly the
    contiguous layout) — it is calibrated from the request's own prefill
    and shared by every page the slot maps, which is what keeps paged
    decode bit-exact with contiguous decode (DESIGN.md §3).
    """
    assert bits in (4, 8), bits
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    dp = kvq.packed_dim(dh, bits)
    dt = kvq.code_dtype(bits)
    return {
        "pkq": jnp.zeros((n_pages, page_size, hkv, dp), dt),
        # ones, not zeros — same NaN-avoidance rule as the contiguous
        # quantized cache (a never-admitted slot's garbage decode writes
        # divide by k_scale).
        "k_scale": jnp.ones((batch, hkv, dh), jnp.float32),
        "pvq": jnp.zeros((n_pages, page_size, hkv, dp), dt),
        "pv_scale": jnp.zeros((n_pages, page_size, hkv), jnp.float32),
    }


def init_mla_cache(cfg, batch: int, max_seq: int, dtype=None) -> dict:
    dtype = cfg.cache_dtype if dtype is None else dtype
    return {
        "c_kv": jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_seq, cfg.qk_rope_dim), dtype),
    }
