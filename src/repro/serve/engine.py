"""Serving engine: real integer-quantized weights, prefill + scanned decode.

Two serving weight layouts (DESIGN.md §3):

``quantize_for_serving`` — the **fake_quant** layout: every quant-unit's
weights become int4 codes + fp32 scale (2-bit layers keep a ±2 code range
inside int4 — scan-stacked layers must share a dtype), dequantized at use.
Embedding/LM-head codes are int8 (pinned 8-bit).

``serve.packing.pack_params`` — the **packed** layout: K-major uint8 codes
(2 int4 / 4 int2 per byte) + per-output-channel scales, routed through
kernels/quant_matmul.py (Pallas on TPU; exact ref path on CPU).  Packed
params default to the BUCKETED layout (models/layout.py): contiguous
same-signature layer runs stacked and scanned, so mixed-precision depth
compiles O(#buckets) — the engine derives the cache layout from the
params layout and validates at construction that packed weight buckets
and quantized cache-bit runs share boundaries (re-pack with
``pack_params(..., cache_bits=...)`` if not).  Pick with
``ServeEngine(weights="packed")``; both layouts are greedy-argmax parity
with each other (tests/test_serve.py).  On the CPU/ref path the packed
codes are dequantized ONCE per decode dispatch (before the token scan —
``packing.decode_weight_view``), not once per token: same arithmetic, same
parity, none of the per-step re-unpack cost that made packed decode
measure slower than fake_quant.

``ServeEngine`` is the compute layer of the serving subsystem:

  * prefill — one jitted call over the (left-aligned, right-padded) prompt
    batch; per-request prompt lengths select each request's last valid
    logits, so a batch never needs a shared prompt length.
  * decode  — a ``jax.lax.scan`` over a fixed chunk of steps: decoding N
    tokens is one dispatch, not N (the per-token Python loop paid one
    dispatch + argmax sync per token).  The cache rides in the scan's
    carry, and inside each step's layer scan too: a contiguous GQA cache
    gets only its new rows written, in place at [layer, b, pos], and the
    decode-attention kernel reads its layer out of the carried stack by
    index, so no step copies the cache.  The dispatch does not donate its
    input cache: ``decode_chunk_step`` returns a new cache and leaves the
    one it was given readable, which costs one copy of the cache per
    dispatch (not per step).
  * the KV cache (serve/kv_cache.py) is preallocated (B, S_max) with
    explicit valid-length tracking.  ``cache="full"`` (default) holds it
    in the COMPUTE dtype — holding it in bf16 (cfg.cache_dtype) made
    greedy decode diverge from the full-context reference: the bf16
    rounding of prefill K/V is amplified to a full code step by the
    activation fake-quant grid, flipping argmax from the third generated
    token.  ``cache="quantized"`` stores int8 / packed-int4 codes with
    per-channel K / per-token V f32 scales (kernels/kv_quant.py) and
    decodes through the fused dequant-attention kernel — the cache term
    of the decode roofline drops 2-4x (int8) / 4-8x (int4).  Its parity
    ladder is exact WITHIN the quantized semantics (engine == stepwise
    quantized oracle, packed == fake_quant, scheduler == solo); closeness
    to the full-dtype cache is a bounded logit error, NOT exact argmax —
    the same amplification that outlaws bf16 caches applies to any lossy
    cache (DESIGN.md §3, tests/test_serve.py).

**Cache layouts** (``cache_layout=``, DESIGN.md §3): ``"contiguous"``
(default) preallocates dense (B, S_max) slots; ``"paged"`` stores K/V in
fixed-size physical pages behind a block table (serve/paging.py) — same
quantization semantics, BIT-exact decode parity with contiguous, and
per-token actual residency instead of per-slot worst case.  The
scheduler adds prefix sharing on top (page-aligned prefixes for full
caches, identical prompts for quantized ones, copy-on-write at
admission); ``generate`` runs the paged path solo with capacity-parity
sequential tables so every solo test doubles as a differential oracle.

**Tensor-parallel serving** (``ServeEngine(mesh=...)``, DESIGN.md §3):
packed weights shard along output channels (attention heads for QKV, d_ff
for gate/up) and input channels (heads for O, d_ff for down — repacked so
no nibble byte straddles a shard), the KV cache (codes AND scales) shards
along the KV-head axis, and prefill/decode run under
``jax.shard_map`` with exactly two psums per block (after the
O-projection and after the MLP down-projection).  Both cache layouts
compose: a PAGED cache shards its physical page pools (``pk/pv``,
``pkq/pvq`` + per-page ``pv_scale``) on the same KV-head axis while the
block table and per-slot state stay replicated — page geometry is
head-count-independent, so the host-side allocator/prefix registry never
see the mesh, and the paged decode kernel's grid is derived from LOCAL
shapes (local KV heads per shard).  The scheduler is completely
unchanged — it drives the same ``prefill``/``decode_chunk_step`` surface
and never sees the mesh.  Sharded decode is token-for-token bit-exact
with single-device decode (tests/test_sharding.py): per-head attention
is head-local, every elementwise op acts on replicated or exactly-sliced
data, and the activation fake-quant grid snaps the psum-reassociation
noise back onto the single-device code grid.

Sampling keys (serve/sampling.py): the key for a request's t-th generated
token folds ONLY (per-request admission nonce, t) into the base key, so a
stochastic trajectory is invariant to decode_chunk, scheduler tail-chunk
geometry, slot placement, and batchmates — scheduler == solo holds under
temperature sampling, not just greedy.

Scheduling (admission, eviction, continuous batching) lives one layer up
in serve/scheduler.py; sampling policies in serve/sampling.py.

The decode-time roofline is HBM-bound; int4 streams 4× fewer weight bytes
than bf16 — this is the paper's NorthPole speed/energy claim re-derived for
TPU and measured by benchmarks/serve_bench.py.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import policy as policy_mod
from repro.core import quant
from repro.kernels import ops as kops
from repro.models import transformer as tf
from repro.models.layout import LayerBuckets
from repro.parallel import sharding
from repro.parallel.context import local_context
from repro.serve import kv_cache, packing, paging, residency, sampling
from repro.serve.config import (RECURRENT_MIXERS, DraftSpec, EngineSpec,
                                has_recurrent_state)
from repro.serve.kv_cache import ServeCache
from repro.serve.paging import PagedServeCache

__all__ = ["ServeEngine", "EngineSpec", "DraftSpec", "quantize_for_serving",
           "has_recurrent_state", "RECURRENT_MIXERS"]


def _quantize_qdense(p: dict, bits) -> dict:
    """{'w','sw','sa'} -> {'wq','scale','sa'}; bits: scalar or (L,)/(L,E)."""
    w = p["w"].astype(jnp.float32)
    step = jnp.maximum(jnp.abs(p["sw"]).astype(jnp.float32), 1e-9)
    b = jnp.asarray(bits, jnp.float32)
    # broadcast step/bits over trailing dims of w
    extra = w.ndim - step.ndim
    stepb = step.reshape(step.shape + (1,) * extra)
    bb = b.reshape(b.shape + (1,) * max(w.ndim - b.ndim, 0))
    codes = quant.quantize_int(w, stepb, bb)
    # static dtype decision (bits come from the *host-side* policy arrays)
    int_dtype = jnp.int8 if float(np.max(np.asarray(bits))) > 4 else jnp.int4
    return {"wq": codes.astype(int_dtype), "scale": step, "sa": p["sa"]}


def quantize_for_serving(params: dict, policy_arrays: dict, cfg) -> dict:
    """Tree-walk a trained param pytree into the serve layout.

    policy_arrays: the knapsack outcome ({group: {slot: bits array}}) — each
    unit's codes are clamped to its selected bit range.
    """
    slot_of = _slot_index(cfg)

    def walk(node, path):
        if isinstance(node, dict) and "w" in node and "sw" in node \
                and "sa" in node:
            bits = _bits_for(policy_arrays, slot_of, path)
            return _quantize_qdense(node, bits)
        if isinstance(node, dict):
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        return node

    out = walk(params, ())
    # embedding / head: int8 (pinned 8-bit; codes shared bit-identically
    # with the packed layout via packing.quantize_edge)
    for edge in ("embed", "head"):
        if edge in params and isinstance(params[edge], dict) \
                and "w" in params[edge]:
            out[edge] = packing.quantize_edge(params[edge])
    return out


def _slot_index(cfg) -> Dict[tuple, tuple]:
    """tensor-path prefix -> (group, slot) from the policy registry."""
    policy = tf.build_policy(cfg)
    index = {}
    for u in policy.units:
        for t in u.tensors:
            index[t[:-1] if t[-1] == "w" else t] = (u.group, u.slot)
    return index


def _bits_for(policy_arrays, slot_of, path) -> Any:
    key = slot_of.get(path)
    if key is None:
        return 4.0                      # not a registered unit: safe default
    group, slot = key
    return policy_arrays[group][slot]


# RECURRENT_MIXERS / has_recurrent_state moved to serve/config.py (the
# EngineSpec validation needs them without importing the engine); both
# stay re-exported here for existing callers.

# engine knobs consolidated into EngineSpec, in field order — resolved
# onto the engine as plain attributes at construction
_SPEC_FIELDS = ("decode_chunk", "prefill_chunk", "sampler", "cache_dtype",
                "weights", "cache", "cache_bits", "mesh", "cache_layout",
                "page_size", "n_pages")


class ServeEngine:
    """Batched decoding with a prefilled, length-tracked KV cache.

    Requests are slotted into fixed (B, S_max) buffers; per-request prompt
    lengths ride in as a ``lengths`` array (positions are masked per
    request), so unequal prompts share one batch.  Decode runs as scanned
    chunks of ``decode_chunk`` steps — one dispatch per chunk.

    Unequal-length batches require every mixer's state to be padding-proof
    (attention caches are: garbage rows stay masked).  Configs with
    recurrent blocks (``has_recurrent_state``) reject unequal lengths —
    the scheduler serves them by prefilling each prompt at its exact
    length instead of a padded bucket.

    Every serving knob rides on ``spec=EngineSpec(...)`` (serve/config.py)
    — the historical flat kwargs (``ServeEngine(..., weights="packed")``)
    lived one release behind a DeprecationWarning shim and now raise a
    loud ``TypeError`` with the migration.  After construction each knob
    is a plain attribute (``engine.decode_chunk`` etc.), resolved from
    the spec.

    ``mesh``: a jax Mesh with a ``"model"`` axis enables tensor-parallel
    serving (packed weights only): params are shard-packed and placed at
    construction, caches allocate sharded along the KV-head axis, and
    prefill/decode run under shard_map — the public surface (and the
    scheduler above it) is unchanged.
    """

    def __init__(self, cfg: Any, params: Any, policy_arrays: Any, ctx: Any,
                 max_seq: int, spec: Optional[EngineSpec] = None, **legacy):
        if legacy:
            known = sorted(set(legacy) & set(_SPEC_FIELDS))
            raise TypeError(
                f"ServeEngine() got unexpected keyword argument(s) "
                f"{sorted(legacy)}: flat serving kwargs were removed "
                f"(they lived one release behind the PR-7 "
                f"DeprecationWarning shim) — pass "
                f"ServeEngine(..., spec=EngineSpec("
                + ", ".join(f"{k}=..." for k in (known or sorted(legacy)))
                + ")) instead; every serving knob lives on the spec "
                f"(serve/config.py)")
        self.cfg = cfg
        self.params = params            # serve-layout params
        self.policy_arrays = policy_arrays
        self.ctx = ctx
        self.max_seq = max_seq
        if spec is None:
            spec = EngineSpec()
        elif not isinstance(spec, EngineSpec):
            raise ValueError(f"spec must be an EngineSpec, "
                             f"got {type(spec).__name__}")
        self.spec = spec
        for name in _SPEC_FIELDS:
            setattr(self, name, getattr(self.spec, name))
        self.draft = self.spec.draft
        # every cross-field rule lives in EngineSpec.validate — including
        # the checks that need cfg (paged mixer support) and params
        # (packed-layout agreement)
        self.spec.validate(self.cfg, self.params)
        if self.cache_dtype is None:
            self.cache_dtype = self.cfg.compute_dtype
        # The model's prefill/decode paths emit cache entries in
        # cfg.cache_dtype; serving pins that to the engine's cache dtype so
        # the prefill->decode handoff never round-trips through a narrower
        # type than the attention compute (the old bf16 round-trip is what
        # broke greedy parity with the full-context reference).
        self._cfg = self.cfg.replace(cache_dtype=self.cache_dtype)
        self.has_recurrent_state = has_recurrent_state(self.cfg)
        self._cache_plan = self._resolve_cache_plan()
        if self.mesh is not None:
            self._init_sharded()
        else:
            self._tp_axis = None
            self.n_shards = 1
            self._prefill = jax.jit(self._prefill_impl)
            self._prefill_suffix = jax.jit(self._prefill_suffix_impl)
            # n_steps is the scan length -> static (one compile per distinct
            # chunk size; generate uses at most two: decode_chunk + a tail)
            self._decode = jax.jit(self._decode_impl, static_argnums=(9,))
            # fused multi-token dispatch (speculative verify AND chunked
            # prefill): the token width S is a SHAPE, so jit re-traces per
            # distinct width (k+1 and/or prefill_chunk in practice)
            self._fused = jax.jit(self._fused_impl)

    def _resolve_cache_plan(self):
        """Derive the pattern-cache layout from the PARAMS layout
        (models/layout.py — DESIGN.md §3 bucketing contract).

          * bucketed params (pack_params default) -> bucketed cache with
            the SAME bucket sizes, always — even a full-dtype cache
            buckets, so the decode scan's carry structure matches the
            params-driven apply output.  Validated against the engine's
            own joint (weight, cache) plan: if the packed buckets do not
            refine the mixed cache-bit runs, the engine raises at
            construction with re-pack guidance instead of failing deep
            inside a jit.
          * unrolled (list) params -> per-layer list cache.
          * stacked (fake_quant) params -> the cache-bit runs alone pick
            stacked vs bucketed (init_caches plan=None auto rule).
        """
        if not self.cfg.n_repeats or not isinstance(self.params, dict):
            return None
        pat = self.params.get("pat")
        if isinstance(pat, (list, tuple)):
            return "unrolled"
        if isinstance(pat, LayerBuckets):
            bits = self.cache_bits if self.cache == "quantized" else None
            plan = policy_mod.bucket_plan(
                self.policy_arrays, bits, n_layers=self.cfg.n_repeats)
            if plan.sizes != pat.sizes:
                raise ValueError(
                    f"packed params carry bucket sizes {pat.sizes} but the "
                    f"engine's joint (weight, cache) plan is {plan.sizes} — "
                    "re-pack with serve.packing.pack_params(..., "
                    "cache_bits=<engine cache_bits>) so weight and cache "
                    "buckets share boundaries")
            return pat.sizes
        return None

    # ------------------------------------------------------- sharded setup
    def _init_sharded(self):
        """Tensor-parallel construction (DESIGN.md §3 sharded serving):
        shard-pack + place the params, build the spec trees, and wrap
        prefill in shard_map (decode wrappers build lazily per chunk
        size).  Everything below this layer sees LOCAL shapes via a
        head-sharded cfg; everything above sees the unchanged engine
        surface."""
        if "model" not in getattr(self.mesh, "axis_names", ()):
            raise ValueError("ServeEngine(mesh=...) needs a mesh with a "
                             "'model' axis (tensor-parallel shards)")
        if self.weights != "packed":
            raise ValueError(
                "sharded serving serves the packed layout; build params "
                "with serve.packing.pack_params and pass weights='packed'")
        n = int(self.mesh.shape["model"])
        reason = packing.tp_shardable(self.cfg, n)
        if reason is not None:
            raise ValueError(f"cannot shard serving over {n} devices: "
                             f"{reason}")
        self._tp_axis = "model"
        self.n_shards = n
        self._cfg_local = self._cfg.replace(
            n_heads=self._cfg.n_heads // n,
            n_kv_heads=self._cfg.n_kv_heads // n)
        self.params, self._pspecs = packing.shard_packed_params(
            self.params, self.cfg, n)
        self.params = jax.device_put(self.params,
                                     self._shardings(self._pspecs))
        self._pa_specs = sharding.replicated_specs(self.policy_arrays)
        # cache layouts: decode buffers (possibly quantized) and the
        # full-dtype prefill handoff — both shard on the KV-head axis
        bits = self.cache_bits if self.cache == "quantized" else None
        if self.cache_layout == "paged":
            # Paged pools (pk/pv, pkq/pvq + pv_scale) shard on the KV-head
            # axis exactly like contiguous codes+scales — serve_cache_specs
            # is leaf-NAME driven and already carries the paged rules; the
            # block table and per-slot K scales replicate via its fallback.
            # The decode dispatch sees TABLE-INJECTED layers
            # (paging.with_tables; gqa_apply's paged branches return dicts
            # that retain ``tbl``, so in/out structures match), while the
            # stored cache holds bare pools — two templates, because
            # paging.strip_tables dereferences pool shapes and cannot walk
            # a PartitionSpec tree.
            def tpl(with_tbl):
                c = paging.init_paged_cache(
                    self._cfg, 1, self.max_seq, 1, self.page_size,
                    dtype=self.cache_dtype, cache_bits=bits,
                    plan=self._cache_plan)
                return (paging.with_tables(c.layers, c.block_tbl)
                        if with_tbl else c.layers)
            self._cache_specs = sharding.serve_cache_specs(
                jax.eval_shape(lambda: tpl(True)))
            self._paged_store_specs = sharding.serve_cache_specs(
                jax.eval_shape(lambda: tpl(False)))
        else:
            cache_template = jax.eval_shape(
                lambda: kv_cache.init_cache(self._cfg, 1, self.max_seq,
                                            dtype=self.cache_dtype,
                                            cache_bits=bits,
                                            plan=self._cache_plan).layers)
            self._cache_specs = sharding.serve_cache_specs(cache_template)
        # prefill emits FULL-dtype caches in the params-derived layout
        # (bucketed params -> bucketed prefill output)
        pre_plan = (self._cache_plan
                    if isinstance(self._cache_plan, tuple) else None)
        pre_template = jax.eval_shape(
            lambda: tf.init_caches(self._cfg, 1, 1,
                                   cache_dtype=self.cache_dtype,
                                   plan=pre_plan))
        self._pre_specs = sharding.serve_cache_specs(pre_template)
        # keep the unjitted shard_map'd callables around: they are the
        # exact programs jit compiles, and repro.analysis traces THEM
        # (dispatch_closures) to check the collective-count contract
        self._prefill_sm = jax.shard_map(
            self._prefill_impl, mesh=self.mesh,
            in_specs=(self._pspecs, self._pa_specs, P(None, None), P(None)),
            out_specs=(P(None, None), self._pre_specs),
            check_vma=False)
        self._prefill = jax.jit(self._prefill_sm)
        self._sharded_decode_sms: Dict[tuple, Any] = {}
        self._sharded_decode_fns: Dict[tuple, Any] = {}

    def _shardings(self, specs):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs)

    def _sharded_decode_sm(self, n_steps: int, key_ndim: int):
        """UNJITTED shard_map'd decode chunk, cached per (scan length, key
        rank) — the exact program ``_sharded_decode`` jits, exposed so the
        static analyzer can trace it without executing."""
        k = (n_steps, key_ndim)
        fn = self._sharded_decode_sms.get(k)
        if fn is None:
            def body(params, pa, layers, lengths, tok, active, key, nonces,
                     t0):
                return self._decode_body(
                    params, pa, layers, lengths, tok, active, key, nonces,
                    t0, n_steps, self._cfg_local, self._tp_axis,
                    local_context())
            fn = jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(self._pspecs, self._pa_specs, self._cache_specs,
                          P(None), P(None, None), P(None),
                          P(*([None] * key_ndim)), P(None), P(None)),
                out_specs=(self._cache_specs, P(None, None), P(None, None)),
                check_vma=False)
            self._sharded_decode_sms[k] = fn
        return fn

    def _sharded_decode(self, n_steps: int, key_ndim: int):
        """shard_map'd decode chunk, cached per (scan length, key rank)."""
        k = (n_steps, key_ndim)
        fn = self._sharded_decode_fns.get(k)
        if fn is None:
            fn = jax.jit(self._sharded_decode_sm(n_steps, key_ndim))
            self._sharded_decode_fns[k] = fn
        return fn

    # ------------------------------------------------------------- prefill
    def _positions_batch(self, positions: jax.Array) -> dict:
        """Auxiliary position streams for the batch dict."""
        if self._cfg.rope == "mrope":
            # text-only serving: temporal/h/w streams collapse to the
            # 1-D position (Qwen2-VL's convention for pure-text segments).
            return {"mrope_positions": jnp.broadcast_to(
                positions[None], (3,) + positions.shape).astype(jnp.int32)}
        return {}

    def _prefill_impl(self, params, pa, tokens: jax.Array,
                      lengths: jax.Array):
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :],
                                     (b, s))
        batch = {"tokens": tokens, **self._positions_batch(positions)}
        cfg = self._cfg_local if self._tp_axis else self._cfg
        ctx = local_context() if self._tp_axis else self.ctx
        logits, pre, _ = tf.apply(params, pa, batch, cfg, ctx,
                                  mode="prefill", tp_axis=self._tp_axis)
        last = logits[jnp.arange(b), lengths - 1]          # (B, V) per-request
        return last, pre

    def prefill(self, tokens: jax.Array,
                lengths: Optional[jax.Array] = None,
                ) -> Tuple[jax.Array, Any]:
        """Run the prompt batch; returns (last-valid logits (B, V),
        prefill cache layers sized to the padded prompt)."""
        b, s = tokens.shape
        if lengths is None:
            lengths = jnp.full((b,), s, jnp.int32)
        return self._prefill(self.params, self.policy_arrays, tokens,
                             jnp.asarray(lengths, jnp.int32))

    def _prefill_suffix_impl(self, params, pa, tokens: jax.Array,
                             length: jax.Array, prefix_len: jax.Array,
                             layers):
        """Suffix prefill for a prefix-hit admission (paged full-dtype
        cache): run the unshared suffix tokens at absolute positions
        [prefix_len, prefix_len + S_pad) while every GQA layer's
        attention extends over the shared prefix pages (the
        prefill-with-cache branch of models/attention.gqa_apply).
        Returns (last-valid logits (1, V), suffix cache rows)."""
        b, s = tokens.shape
        positions = prefix_len + jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
        batch = {"tokens": tokens, **self._positions_batch(positions)}
        logits, suf, _ = tf.apply(params, pa, batch, self._cfg, self.ctx,
                                  mode="prefill", caches=layers,
                                  positions=positions)
        last = logits[jnp.arange(b), length - 1]
        return last, suf

    def prefill_suffix(self, tokens: jax.Array, length: int, prefix_len: int,
                       cache: PagedServeCache, slot: int):
        """Prefill only a request's unshared suffix against slot ``slot``'s
        already-mapped prefix pages (scheduler prefix-hit admission;
        full-dtype paged caches — a quantized prefix hit never prefills,
        see serve/paging.py).  ``tokens``: (1, S_pad) suffix tokens;
        ``length``: valid suffix tokens; ``prefix_len``: shared rows
        (page-aligned)."""
        layers = paging.with_tables(
            cache.layers,
            jax.lax.dynamic_slice_in_dim(cache.block_tbl, slot, 1, axis=0))
        return self._prefill_suffix(self.params, self.policy_arrays, tokens,
                                    jnp.int32(length), jnp.int32(prefix_len),
                                    layers)

    def new_cache(self, batch: int) -> ServeCache:
        """Preallocated (B, S_max) cache in this engine's layout: full
        compute-dtype buffers, or — ``cache='quantized'`` — int8 /
        packed-int4 code buffers with per-channel K / per-token V scales
        (GQA layers; MLA-latent and recurrent state stay full precision,
        DESIGN.md §3).  Sharded engines place every leaf along its KV-head
        axis on the mesh."""
        bits = self.cache_bits if self.cache == "quantized" else None
        if self.cache_layout == "paged":
            n_pages = (self.n_pages if self.n_pages is not None
                       else batch * self.max_pages)
            if int(n_pages) < batch:
                # every slot needs at least one writable page or admission
                # can never place it — this used to surface as a silent
                # scheduler deadlock (submit() retries forever)
                raise ValueError(
                    f"n_pages={int(n_pages)} cannot back a {batch}-slot "
                    f"batch: every slot needs >= 1 page (worst case "
                    f"{self.max_pages}/slot at max_seq={self.max_seq}, "
                    f"page_size={self.page_size})")
            c = paging.init_paged_cache(
                self._cfg, batch, self.max_seq, int(n_pages), self.page_size,
                dtype=self.cache_dtype, cache_bits=bits,
                plan=self._cache_plan)
            if self.mesh is None:
                return c
            # pools on the KV-head axis; the block table and lengths are
            # replicated host-of-record state (the allocator mutates the
            # table row-wise — page geometry is head-count-independent)
            return PagedServeCache(
                layers=jax.device_put(
                    c.layers, self._shardings(self._paged_store_specs)),
                block_tbl=jax.device_put(
                    c.block_tbl, NamedSharding(self.mesh, P(None, None))),
                lengths=jax.device_put(
                    c.lengths, NamedSharding(self.mesh, P(None))))
        c = kv_cache.init_cache(self._cfg, batch, self.max_seq,
                                dtype=self.cache_dtype, cache_bits=bits,
                                plan=self._cache_plan)
        if self.mesh is None:
            return c
        return ServeCache(
            layers=jax.device_put(c.layers,
                                  self._shardings(self._cache_specs)),
            lengths=jax.device_put(c.lengths,
                                   NamedSharding(self.mesh, P(None))))

    def new_staging_cache(self, batch: int) -> Optional[ServeCache]:
        """Full-dtype contiguous staging cache for chunked prefill over a
        QUANTIZED cache (contiguous or paged): prefilling rows write
        provisional full-dtype K/V here because the per-request K quant
        grid calibrates over the WHOLE prompt — provisional quantized
        writes would not be bit-exact with whole-prompt admission.  On
        prompt completion the scheduler finalizes the slot with
        whole-prompt calibration (kv_cache.finalize_slot /
        paging.finalize_slot_pages).  Returns None for full-dtype caches,
        which chunk in place (a prefill chunk is just a multi-token
        decode row)."""
        if self.cache != "quantized":
            return None
        return kv_cache.init_cache(self._cfg, batch, self.max_seq,
                                   dtype=self.cache_dtype,
                                   plan=self._cache_plan)

    @property
    def max_pages(self) -> int:
        """Block-table width: logical pages per slot (ceil(S_max/page))."""
        return -(-self.max_seq // self.page_size)

    def cache_batch_axes(self):
        """Per-leaf batch-axis pytree for scheduler slot admission — built
        from THIS engine's cache layout (quantized layouts carry extra
        code/scale leaves the default full-dtype template lacks)."""
        bits = self.cache_bits if self.cache == "quantized" else None
        return kv_cache.batch_axis_index(
            self._cfg, self.max_seq,
            init_fn=lambda b: kv_cache.init_cache(
                self._cfg, b, self.max_seq, dtype=self.cache_dtype,
                cache_bits=bits, plan=self._cache_plan).layers)

    def residency(self, cache: Optional[ServeCache] = None) -> dict:
        """Measured resident/roofline bytes (serve/residency.py — the one
        definition bench, logging and tests share).  Sharded engines also
        report the per-device share of every buffer."""
        return residency.report(self.params, cache)

    # -------------------------------------------------------------- decode
    def _decode_body(self, params, pa, layers, lengths, tok, active, key,
                     nonces, t0, n_steps, cfg, tp_axis, ctx):
        """One scanned chunk: feed ``tok``, emit ``n_steps`` tokens.

        layers/lengths: the ServeCache fields (B, S_max buffers + valid
        lengths); tok: (B, 1) the last emitted-but-unprocessed token;
        active: (B,) bool — inactive slots write nothing (their position is
        pinned out of range) and their outputs are discarded upstream.

        Sampling-key contract (serve/sampling.py): the key for scan step i
        of slot r folds (nonces[r], t0[r] + i) — the slot's admission
        nonce and ITS OWN generated-token index.  No chunk geometry is
        folded, so a trajectory is invariant to decode_chunk, to the
        scheduler's shorter tail chunks, and to when the request was
        admitted relative to its batchmates.

        The cache is the token scan's carry, and ``tf.apply`` carries
        each run's layer stack through its layer scan in turn: contiguous
        GQA caches are written in place, one row per slot and layer, and
        read by layer index (``tf.decode_writes_in_place``; other cache
        kinds slice their layer out and write it back).  ``layers`` is not
        donated (see the module docstring).

        On the CPU/ref path, packed weights are dequantized ONCE here —
        per dispatch, before the scan — instead of once per token
        (packing.decode_weight_view); TPU streams the packed codes through
        the Pallas kernel untouched.
        """
        if self.weights == "packed" and not kops.on_tpu():
            params = packing.decode_weight_view(params)
        off_range = jnp.int32(self.max_seq)

        def body(carry, i):
            layers, positions, tok = carry
            pos = jnp.where(active[:, None], positions, off_range)
            batch = {"tokens": tok, **self._positions_batch(pos)}
            logits, layers, _ = tf.apply(
                params, pa, batch, cfg, ctx,
                mode="decode", caches=layers, positions=pos,
                tp_axis=tp_axis)
            keys = sampling.slot_keys(key, nonces, t0 + i)
            nxt = sampling.sample(logits[:, -1, :], keys, self.sampler)
            return (layers, positions + 1, nxt[:, None]), nxt

        init = (layers, lengths[:, None].astype(jnp.int32), tok)
        (layers, _, tok), toks = jax.lax.scan(
            body, init, jnp.arange(n_steps))
        return layers, tok, toks.swapaxes(0, 1)             # (B, n_steps)

    def _decode_impl(self, params, pa, layers, lengths, tok, active, key,
                     nonces, t0, n_steps):
        return self._decode_body(params, pa, layers, lengths, tok, active,
                                 key, nonces, t0, n_steps, self._cfg, None,
                                 self.ctx)

    def decode_chunk_step(self, cache: ServeCache, tok: jax.Array,
                          key: jax.Array, *,
                          nonces: Optional[jax.Array] = None,
                          step0: Any = 1,
                          active: Optional[jax.Array] = None,
                          n_steps: Optional[int] = None,
                          ) -> Tuple[ServeCache, jax.Array, jax.Array]:
        """Advance every slot by one scanned chunk of ``n_steps``
        (default ``decode_chunk``; a shorter tail chunk avoids paying
        full-chunk decode steps for a short remaining budget).

        ``nonces``: (B,) per-slot admission nonce (default: the batch row
        index); ``step0``: scalar or (B,) — each slot's generated-token
        count so far (the prefill-sampled token is index 0).  Together
        they fully determine the sampling keys — see ``_decode_body``.
        Both are KEYWORD-ONLY: the old positional slot here was the
        global chunk index, and an int is a valid (broadcast) nonce — a
        stale positional caller must fail loudly, not sample silently
        wrong trajectories.

        Returns (cache, next feed token (B, 1), emitted tokens
        (B, n_steps)).
        """
        b = cache.lengths.shape[0]
        if active is None:
            active = jnp.ones((b,), bool)
        if n_steps is None:
            n_steps = self.decode_chunk
        if nonces is None:
            nonces = jnp.arange(b, dtype=jnp.int32)
        nonces = jnp.broadcast_to(jnp.asarray(nonces, jnp.int32), (b,))
        t0 = jnp.broadcast_to(jnp.asarray(step0, jnp.int32), (b,))
        paged = isinstance(cache, PagedServeCache)
        layers_in = (paging.with_tables(cache.layers, cache.block_tbl)
                     if paged else cache.layers)
        if self.mesh is None:
            layers, tok, toks = self._decode(
                self.params, self.policy_arrays, layers_in, cache.lengths,
                tok, active, key, nonces, t0, n_steps)
        else:
            fn = self._sharded_decode(int(n_steps),
                                      int(jnp.asarray(key).ndim))
            layers, tok, toks = fn(
                self.params, self.policy_arrays, layers_in, cache.lengths,
                tok, active, key, nonces, t0)
        if paged:
            cache = paging.advance(cache, layers, steps=n_steps,
                                   active=active)
        else:
            cache = kv_cache.advance(cache, layers, steps=n_steps,
                                     active=active)
        return cache, tok, toks

    # ------------------------- fused multi-token dispatch (verify/chunk)
    def _fused_impl(self, params, pa, layers, lengths, tokens, n_valid,
                    active, key, nonces, t_idx):
        """Score up to S positions per slot in ONE decode-mode forward —
        the shared core of speculative verify AND fused chunked prefill.

        tokens: (B, S); row r's first ``n_valid[r]`` tokens are real
        (a verify row feeds [feed, draft_0..draft_{k-1}] with n_valid =
        k+1; a prefill-chunk row feeds its next prompt-chunk tokens; a
        plain decode row fused into the dispatch feeds one token with
        n_valid = 1).  Valid rows enter the cache at positions
        lengths .. lengths+n_valid-1; positions past a row's n_valid (and
        inactive rows) pin out of range exactly like the decode scan, so
        their writes drop and their outputs are garbage-but-finite.  The
        per-query causal mask in models/attention gives position i the
        prefix a sequential decode would have seen — so the returned
        greedy tokens (B, S) are bit-exact with n_valid scanned decode
        steps fed the same tokens (the verify parity bar, DESIGN.md §3).

        Sampling rides per row: ``sampled[r]`` draws from row r's LAST
        valid logits (index n_valid[r]-1) with the scheduler-invariant
        key (nonces[r], t_idx[r]) — a prefill row completing its prompt
        samples its first token exactly like whole-prompt admission
        (t_idx 0), a fused decode row exactly like the scanned chunk.

        Returns (written cache layers, sampled (B,), greedy argmax (B, S),
        logits (B, S, V)).
        """
        if self.weights == "packed" and not kops.on_tpu():
            params = packing.decode_weight_view(params)
        b, s = tokens.shape
        pos = lengths[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        valid = active[:, None] \
            & (jnp.arange(s, dtype=jnp.int32)[None, :] < n_valid[:, None])
        pos = jnp.where(valid, pos, jnp.int32(self.max_seq))
        batch = {"tokens": tokens, **self._positions_batch(pos)}
        logits, layers, _ = tf.apply(
            params, pa, batch, self._cfg, self.ctx,
            mode="decode", caches=layers, positions=pos)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        keys = sampling.slot_keys(key, nonces, t_idx)
        last = logits[jnp.arange(b), n_valid - 1]
        sampled = sampling.sample(last, keys, self.sampler)
        return layers, sampled, greedy, logits

    def fused_step(self, cache, tokens: jax.Array, n_valid, key: jax.Array,
                   *, nonces, t_idx, active: Optional[jax.Array] = None,
                   staging=None, role=None):
        """One fused prefill-chunk + decode/verify dispatch (scheduler
        chunked admission — DESIGN.md §3 chunked-prefill contract).

        ``n_valid``: (B,) tokens each row actually consumes; ``t_idx``:
        (B,) per-row generated-token index for the sampling key (0 for a
        prefill row completing its prompt); ``staging``/``role``: the
        full-dtype staging cache + (B,) bool prefilling mask, required
        whenever a QUANTIZED cache serves prefilling rows
        (kv_cache.with_staging — full-dtype caches chunk in place and
        pass staging=None).

        The cache is NOT advanced: the caller commits per-row counts via
        ``commit_verified`` (prefill rows their chunk length, decode rows
        1, verify rows their accepted count) — uncommitted rows are
        stale-by-construction, same watermark argument as ``verify_step``.

        Returns (scored layers, updated staging cache or None,
        sampled (B,), greedy (B, S), logits).
        """
        if self.mesh is not None:
            raise ValueError(
                "fused_step is single-device: the role-masked fused "
                "prefill/decode body has no shard_map wrapper — plain "
                "decode (contiguous or paged) does (EngineSpec refuses "
                "prefill_chunk + mesh=)")
        b = cache.lengths.shape[0]
        if active is None:
            active = jnp.ones((b,), bool)
        paged = isinstance(cache, PagedServeCache)
        layers_in = (paging.with_tables(cache.layers, cache.block_tbl)
                     if paged else cache.layers)
        if staging is not None:
            layers_in = kv_cache.with_staging(
                layers_in, staging.layers,
                jnp.asarray(np.asarray(role, bool)))
        layers, sampled, greedy, logits = self._fused(
            self.params, self.policy_arrays, layers_in, cache.lengths,
            tokens, jnp.asarray(n_valid, jnp.int32), jnp.asarray(active),
            key, jnp.asarray(nonces, jnp.int32),
            jnp.asarray(t_idx, jnp.int32))
        if staging is not None:
            layers, staged = kv_cache.strip_staging(layers, staging.layers)
            staging = dataclasses.replace(staging, layers=staged)
        return layers, staging, sampled, greedy, logits

    def verify_step(self, cache, tokens: jax.Array,
                    active: Optional[jax.Array] = None):
        """Speculative verify dispatch (serve/spec.py drives this).

        ``tokens``: (B, k+1) — each slot's next feed token followed by
        its k draft tokens.  All k+1 rows are WRITTEN to the cache, but
        the cache is NOT advanced: the caller computes the accepted
        prefix length j per slot (1 <= j <= k+1 for greedy acceptance)
        and commits via ``commit_verified``.  Rows past the committed
        length are stale-by-construction: contiguous reads mask on the
        valid length, paged rows sit on the slot's own already-claimed
        pages (admission claims worst-case pages) and overruns drop
        through the block table's -1 sentinel — so rejection is a pure
        length-watermark rollback, no data movement (DESIGN.md §3).

        Returns (scored layers, greedy tokens (B, k+1), logits).
        """
        if self.mesh is not None:
            raise ValueError(
                "verify_step is single-device: the (B, k+1) verify "
                "dispatch has no shard_map wrapper — plain decode "
                "(contiguous or paged) does (EngineSpec refuses "
                "draft= + mesh=)")
        b, s_v = tokens.shape
        if active is None:
            active = jnp.ones((b,), bool)
        paged = isinstance(cache, PagedServeCache)
        layers_in = (paging.with_tables(cache.layers, cache.block_tbl)
                     if paged else cache.layers)
        # the fused core with every row full-width (n_valid = k+1) IS the
        # historical verify dispatch — the valid mask reduces to the
        # active mask, bit-exact with the pre-fusion implementation
        zeros = jnp.zeros((b,), jnp.int32)
        layers, _, greedy, logits = self._fused(
            self.params, self.policy_arrays, layers_in, cache.lengths,
            tokens, jnp.full((b,), s_v, jnp.int32), active,
            sampling.base_key(), zeros, zeros)
        return layers, greedy, logits

    def commit_verified(self, cache, layers, steps,
                        active: Optional[jax.Array] = None):
        """Adopt a verify dispatch's cache writes: advance each slot's
        valid length by its accepted count ``steps`` ((B,) int array; 0
        for inactive slots).  The k+1-j rejected rows stay physically
        written but sit past the watermark — provably unread (same
        argument as re-admission over stale slot rows, DESIGN.md §3)."""
        if isinstance(cache, PagedServeCache):
            return paging.advance(cache, layers, steps=steps, active=active)
        return kv_cache.advance(cache, layers, steps=steps, active=active)

    # ------------------------------------------------------------ generate
    def generate(self, tokens: jax.Array, n_new: int,
                 lengths: Optional[jax.Array] = None,
                 key: Optional[jax.Array] = None,
                 nonces: Optional[jax.Array] = None) -> jax.Array:
        """tokens: (B, S_prompt) left-aligned (right-padded) prompts ->
        (B, n_new) continuation.  Greedy by default (engine.sampler).

        ``nonces``: (B,) per-request admission nonces for the sampling
        keys (default: the batch row index).  Pass the scheduler-assigned
        nonce to reproduce a continuous-batching trajectory solo."""
        b, s_prompt = tokens.shape
        if n_new <= 0:
            return jnp.zeros((b, 0), jnp.int32)
        if s_prompt + n_new > self.max_seq:
            raise ValueError(f"prompt {s_prompt} + n_new {n_new} exceeds "
                             f"max_seq {self.max_seq}")
        if key is None:
            key = sampling.base_key()
        lengths = (jnp.full((b,), s_prompt, jnp.int32) if lengths is None
                   else jnp.asarray(lengths, jnp.int32))
        if np.any(np.asarray(lengths) < 1) \
                or np.any(np.asarray(lengths) > s_prompt):
            raise ValueError("per-request lengths must be in [1, S_prompt]")
        if self.has_recurrent_state and np.any(np.asarray(lengths)
                                               != s_prompt):
            raise ValueError(
                "unequal prompt lengths need right-padding, which corrupts "
                "recurrent (mamba/xlstm) block state — serve such configs "
                "through the scheduler (exact-length prefill per request)")
        nonces = (jnp.arange(b, dtype=jnp.int32) if nonces is None
                  else jnp.asarray(nonces, jnp.int32))
        last, pre = self.prefill(tokens, lengths)
        fresh = self.new_cache(b)
        cache = (paging.splice_prefill(fresh, pre, lengths)
                 if isinstance(fresh, PagedServeCache)
                 else kv_cache.splice_prefill(fresh, pre, lengths))
        first = sampling.sample(
            last, sampling.slot_keys(key, nonces, 0), self.sampler)
        tok = first[:, None]
        out = [tok]
        remaining = n_new - 1
        t0 = 1                      # the prefill-sampled token was index 0
        while remaining > 0:
            n_steps = min(self.decode_chunk, remaining)
            cache, tok, toks = self.decode_chunk_step(
                cache, tok, key, nonces=nonces, step0=t0, n_steps=n_steps)
            out.append(toks)
            remaining -= n_steps
            t0 += n_steps
        return jnp.concatenate(out, axis=1)

    # --------------------------- static-analysis surface (repro.analysis)
    def dispatch_closures(self, batch: int = 1,
                          prompt_tokens: int = 8,
                          ) -> Dict[str, "DispatchClosure"]:
        """The serving dispatches as TRACEABLE closures — the exact
        callables ``jax.jit`` wraps (shard_map'd on a mesh engine), paired
        with argument pytrees shaped like the scheduler's traffic, so
        ``jax.make_jaxpr`` sees the deployed program without running it.

        This is the contract surface ``repro.analysis`` checks: params
        enter as ARGUMENTS here (a closure that baked them as trace-time
        constants is exactly the PR 4 bug class the baked-const detector
        exists for), cache buffers enter in this engine's real layout
        (quantized codes+scales, paged tables, staging where the
        scheduler would pass it), and the fused widths are the ones the
        scheduler dispatches (``max(prefill_chunk, k+1)`` and ``k+1``).

        Keys: ``prefill`` always; ``decode`` (scanned chunk — shard_map'd
        when ``mesh=``); ``spec_verify`` when a draft is configured;
        ``fused_prefill_decode`` when ``prefill_chunk`` is set.
        """
        b = batch
        cache = self.new_cache(b)
        paged = isinstance(cache, PagedServeCache)
        layers = (paging.with_tables(cache.layers, cache.block_tbl)
                  if paged else cache.layers)
        tok = jnp.zeros((b, 1), jnp.int32)
        active = jnp.ones((b,), bool)
        key = sampling.base_key()
        nonces = jnp.arange(b, dtype=jnp.int32)
        t0 = jnp.ones((b,), jnp.int32)
        s_p = min(int(prompt_tokens), self.max_seq)
        ptoks = jnp.zeros((b, s_p), jnp.int32)
        plens = jnp.full((b,), s_p, jnp.int32)
        out: Dict[str, DispatchClosure] = {}
        if self.mesh is not None:
            out["prefill"] = DispatchClosure(
                "prefill", self._prefill_sm,
                (self.params, self.policy_arrays, ptoks, plens),
                sharded=True)
            out["decode"] = DispatchClosure(
                "decode",
                self._sharded_decode_sm(self.decode_chunk,
                                        int(jnp.asarray(key).ndim)),
                (self.params, self.policy_arrays, layers, cache.lengths,
                 tok, active, key, nonces, t0),
                sharded=True)
            return out
        out["prefill"] = DispatchClosure(
            "prefill", self._prefill_impl,
            (self.params, self.policy_arrays, ptoks, plens))
        out["decode"] = DispatchClosure(
            "decode", self._decode_impl,
            (self.params, self.policy_arrays, layers, cache.lengths, tok,
             active, key, nonces, t0, self.decode_chunk),
            static_argnums=(9,))

        def fused(name, s_w, layers_in):
            return DispatchClosure(
                name, self._fused_impl,
                (self.params, self.policy_arrays, layers_in, cache.lengths,
                 jnp.zeros((b, s_w), jnp.int32),
                 jnp.full((b,), s_w, jnp.int32), active, key, nonces,
                 jnp.zeros((b,), jnp.int32)))

        if self.draft is not None:
            out["spec_verify"] = fused("spec_verify", self.draft.k + 1,
                                       layers)
        if self.prefill_chunk is not None:
            s_w = max(self.prefill_chunk,
                      (self.draft.k + 1) if self.draft is not None else 1)
            layers_in = layers
            staging = self.new_staging_cache(b)
            if staging is not None:
                layers_in = kv_cache.with_staging(
                    layers_in, staging.layers, jnp.ones((b,), bool))
            out["fused_prefill_decode"] = fused("fused_prefill_decode",
                                                s_w, layers_in)
        return out

    def jit_cache_sizes(self) -> Dict[str, int]:
        """Live jit-cache entry count per serving dispatch — the measured
        side of the retrace audit (``dispatch_budget`` is the documented
        ceiling).  Sharded decode sums across the per-(n_steps, key rank)
        wrappers; a dispatch that never ran reports 0."""
        def n(fn):
            return int(fn._cache_size()) if fn is not None else 0
        sizes = {"prefill": n(self._prefill)}
        if self.mesh is not None:
            sizes["decode"] = sum(
                n(f) for f in self._sharded_decode_fns.values())
            return sizes
        sizes["prefill_suffix"] = n(self._prefill_suffix)
        sizes["decode"] = n(self._decode)
        sizes["fused"] = n(self._fused)
        return sizes

    def dispatch_budget(self, prompt_bucket: Optional[int] = None,
                        ) -> Dict[str, int]:
        """Documented ceiling on DISTINCT jit traces per dispatch
        (DESIGN.md §8) — the retrace contract ``repro.analysis`` gates:

          * ``prefill`` / ``prefill_suffix``: one trace per padded prompt
            width; the scheduler pads to ``prompt_bucket`` multiples
            capped at ``max_seq``, so at most ceil(max_seq/bucket).
          * ``decode``: the full ``decode_chunk`` scan plus the
            scheduler's power-of-two tail chunks below it.
          * ``fused``: the token width S is a shape and the staging
            attachment changes the input pytree STRUCTURE, so one trace
            per distinct (width, staging) pair — the fused prefill+decode
            round runs ``max(prefill_chunk, k+1)`` wide WITH staging on a
            quantized cache (the scheduler always attaches it), spec
            verify runs ``k+1`` wide on bare layers (PR 8).

        A measured ``jit_cache_sizes`` above these means a retrace leak:
        some argument that should be an array (or a stable static) is
        feeding new trace keys per call — the recompile bug class.
        """
        pb = int(prompt_bucket) if prompt_bucket else self.max_seq
        n_prefill = -(-self.max_seq // pb)
        tails = {self.decode_chunk}
        w = 1
        while w < self.decode_chunk:
            tails.add(w)
            w *= 2
        fused_keys = set()
        if self.draft is not None:
            fused_keys.add((self.draft.k + 1, False))
        if self.prefill_chunk is not None:
            s_w = max(self.prefill_chunk,
                      (self.draft.k + 1) if self.draft is not None else 1)
            fused_keys.add((s_w, self.cache == "quantized"))
        return {"prefill": n_prefill, "prefill_suffix": n_prefill,
                "decode": len(tails), "fused": len(fused_keys)}

    def n_scan_bodies(self) -> int:
        """Distinct transformer-block bodies in one traced decode step:
        prefix layers unroll individually; the repeated pattern runs as
        one scan per bucket (bucketed), one body per layer (unrolled), or
        one scan total (stacked).  The collective-count contract expects
        exactly ``2 * n_scan_bodies()`` psums in a sharded decode trace
        (DESIGN.md §3: one after attention out-proj, one after the FFN
        down-proj, per body)."""
        n_prefix = len(getattr(self.cfg, "prefix", ()) or ())
        plan = self._cache_plan
        if isinstance(plan, tuple):
            return n_prefix + len(plan)
        if plan == "unrolled":
            return n_prefix + int(self.cfg.n_repeats)
        return n_prefix + (1 if self.cfg.n_repeats else len(self.cfg.pattern))


@dataclasses.dataclass(frozen=True)
class DispatchClosure:
    """One serving dispatch as (exact jitted callable, example args) —
    see ``ServeEngine.dispatch_closures``.  ``trace()`` returns the
    ClosedJaxpr the analyzer walks; nothing executes."""
    name: str
    fn: Any
    args: tuple
    static_argnums: tuple = ()
    sharded: bool = False

    def trace(self):
        return jax.make_jaxpr(self.fn, static_argnums=self.static_argnums)(
            *self.args)
