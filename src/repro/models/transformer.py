"""Unified model stack: prefix blocks (unrolled) + scanned repeat pattern.

Depth never appears in the HLO: the repeating pattern is stacked (vmap-init)
and scanned (lax.scan), so lower+compile cost is O(1) in n_layers — this is
what makes the 61-layer/671B dry-run tractable and is also the right answer
for 1000-node compile times.

Quantization policy bits ride through the scan as stacked (n_repeats,)
arrays next to the stacked params; caches likewise.  MIXED per-layer
serving precision (packed weights / quantized caches) keeps the scan via
the BUCKETED layout (models/layout.py): maximal contiguous
same-signature runs, each stacked and scanned, python-stepped across
boundaries — O(#buckets) program size instead of O(depth).  Modes:

  train   — full sequence, loss-ready logits, per-block remat
  prefill — full sequence + returns per-layer caches/states
  decode  — one token, cache update, logits for the new position
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant
from repro.core.policy import (CACHE_FULL_BITS, PIN_MIN_IN_FEATURES,
                               PIN_EDGE_BITS, PIN_NARROW_BITS, CacheUnit,
                               PrecisionPolicy, QuantUnit)
from repro.models import attention as attn
from repro.models import common, layout, mlp, ssm
from repro.models.common import BlockDef
from repro.models.layout import LayerBuckets


# ==================================================================== blocks
def init_block(key, cfg, bdef: BlockDef) -> dict:
    k1, k2 = jax.random.split(key)
    p = {"norm1": common.init_norm(cfg.norm, cfg.d_model, cfg.param_dtype)}
    if bdef.mixer in ("gqa", "bidir"):
        p["attn"] = attn.init_gqa(k1, cfg)
    elif bdef.mixer == "mla":
        p["attn"] = attn.init_mla(k1, cfg)
    elif bdef.mixer == "mamba":
        p["mamba"] = ssm.init_mamba(k1, cfg)
    elif bdef.mixer == "mlstm":
        p["lstm"] = ssm.init_mlstm(k1, cfg)
    elif bdef.mixer == "slstm":
        p["lstm"] = ssm.init_slstm(k1, cfg)
    else:
        raise ValueError(bdef.mixer)

    if bdef.ffn != "none":
        p["norm2"] = common.init_norm(cfg.norm, cfg.d_model, cfg.param_dtype)
    if bdef.ffn == "swiglu":
        p["mlp"] = mlp.init_dense_mlp(k2, cfg, d_ff=bdef.d_ff, gated=True)
    elif bdef.ffn == "gelu":
        p["mlp"] = mlp.init_dense_mlp(k2, cfg, d_ff=bdef.d_ff, gated=False)
    elif bdef.ffn == "moe":
        p["moe"] = mlp.init_moe(k2, cfg)
    elif bdef.ffn == "slstm_ffn":
        p["mlp"] = mlp.init_dense_mlp(k2, cfg, d_ff=cfg.slstm_d_ff, gated=True)
    elif bdef.ffn != "none":
        raise ValueError(bdef.ffn)
    return p


def block_apply(p, x, bits, cfg, ctx, bdef: BlockDef, mode: str, cache,
                positions, mrope_positions=None, tp_axis=None):
    """Returns (x, new_cache, aux).

    ``tp_axis``: set ONLY inside a serving shard_map body (DESIGN.md §3
    sharded serving).  Projections are column-parallel into the mixer/FFN
    and row-parallel out of it, so the block output of each is a PARTIAL
    sum — completed by exactly one psum after the O-projection and one
    after the MLP down-projection (the minimal TP collective set); the
    residual stream and everything on it stays replicated.
    """
    aux = jnp.float32(0.0)
    h = common.apply_norm(cfg.norm, x, p["norm1"])
    if bdef.mixer in ("gqa", "bidir"):
        y, new_cache = attn.gqa_apply(p["attn"], h, bits, cfg, mode, cache,
                                      positions, mrope_positions)
    elif bdef.mixer == "mla":
        y, new_cache = attn.mla_apply(p["attn"], h, bits, cfg, mode, cache,
                                      positions, mrope_positions)
    elif bdef.mixer == "mamba":
        y, new_cache = ssm.mamba_apply(p["mamba"], h, bits, cfg, mode, cache)
    elif bdef.mixer == "mlstm":
        y, new_cache = ssm.mlstm_apply(p["lstm"], h, bits, cfg, mode, cache)
    elif bdef.mixer == "slstm":
        y, new_cache = ssm.slstm_apply(p["lstm"], h, bits, cfg, mode, cache,
                                       ctx)
    else:
        raise ValueError(bdef.mixer)
    if tp_axis is not None:
        y = jax.lax.psum(y, tp_axis)          # completes the O-projection
    x = x + y
    x = ctx.constrain(x, ctx.batch_spec, None, None)

    if bdef.ffn in ("swiglu", "gelu", "slstm_ffn"):
        h = common.apply_norm(cfg.norm, x, p["norm2"])
        act = "gelu" if bdef.ffn == "gelu" else cfg.activation
        y = mlp.dense_mlp_apply(p["mlp"], h, bits, act)
        if tp_axis is not None:
            y = jax.lax.psum(y, tp_axis)      # completes the down-projection
        x = x + y
    elif bdef.ffn == "moe":
        h = common.apply_norm(cfg.norm, x, p["norm2"])
        y, aux = mlp.moe_apply(p["moe"], h, bits, cfg, ctx)
        if tp_axis is not None:
            # expert down-projections are row-parallel and the combine is
            # linear in them, so one psum after the whole MoE completes
            # every expert (and the shared expert) at once.
            y = jax.lax.psum(y, tp_axis)
        x = x + y
    x = ctx.constrain(x, ctx.batch_spec, None, None)
    return x, new_cache, aux


def init_block_cache(cfg, bdef: BlockDef, batch: int, max_seq: int,
                     cache_dtype=None, cache_bits=None, page_geom=None):
    """``cache_bits`` 4/8 selects the quantized GQA cache layout; None or
    16 keeps the full-dtype buffers.  Only GQA caches quantize: MLA's
    cache is already the compressed latent (its memory story), and
    recurrent/SSM states have no sequence axis — all stay full precision
    (DESIGN.md §3).

    ``page_geom`` = (n_pages, page_size) selects the PAGED pool layout
    (serve/paging.py) instead of the contiguous (B, S_max) buffers.
    Only GQA caches page: MLA's latent and recurrent state have no
    shareable per-token sequence rows (a 16-passthrough GQA layer in a
    paged config would need full-dtype rows addressed per page, which
    ``init_gqa_paged_cache`` provides)."""
    if bdef.mixer in ("gqa",):
        if page_geom is not None:
            n_pages, page_size = page_geom
            if cache_bits in (4, 8):
                return attn.init_gqa_paged_quant_cache(
                    cfg, batch, n_pages, page_size, cache_bits)
            return attn.init_gqa_paged_cache(cfg, batch, n_pages, page_size,
                                             cache_dtype)
        if cache_bits in (4, 8):
            return attn.init_gqa_quant_cache(cfg, batch, max_seq, cache_bits)
        return attn.init_gqa_cache(cfg, batch, max_seq, cache_dtype)
    if page_geom is not None and bdef.mixer in ("mla", "mamba", "mlstm",
                                                "slstm"):
        raise ValueError(
            f"paged KV cache supports GQA attention only; {bdef.mixer!r} "
            f"state has no per-token page structure (serve paged configs "
            f"with cache_layout='contiguous')")
    if bdef.mixer == "mla":
        return attn.init_mla_cache(cfg, batch, max_seq, cache_dtype)
    if bdef.mixer == "mamba":
        return ssm.init_mamba_state(cfg, batch)
    if bdef.mixer == "mlstm":
        return ssm.init_mlstm_state(cfg, batch)
    if bdef.mixer == "slstm":
        return ssm.init_slstm_state(cfg, batch)
    return None  # bidir encoder: no cache


# ===================================================================== model
def init_params(cfg, key) -> dict:
    keys = jax.random.split(key, 4 + len(cfg.prefix))
    params: dict = {}
    if not cfg.embed_input:
        table = jax.random.normal(keys[0], (cfg.vocab, cfg.d_model),
                                  cfg.param_dtype) * 0.02
        params["embed"] = {"w": table,
                           "sw": quant.init_step_from_tensor(table, 8.0)}
    for i, bdef in enumerate(cfg.prefix):
        params[f"prefix{i}"] = init_block(keys[1 + i], cfg, bdef)

    if cfg.n_repeats:
        def one_repeat(k):
            ks = jax.random.split(k, len(cfg.pattern))
            return {f"p{j}": init_block(ks[j], cfg, bd)
                    for j, bd in enumerate(cfg.pattern)}
        rep_keys = jax.random.split(keys[-3], cfg.n_repeats)
        params["pat"] = jax.vmap(one_repeat)(rep_keys)

    params["final_norm"] = common.init_norm(cfg.norm, cfg.d_model,
                                            cfg.param_dtype)
    if not cfg.tie_embeddings:
        head = jax.random.normal(keys[-2], (cfg.d_model, cfg.vocab),
                                 cfg.param_dtype) * (cfg.d_model ** -0.5)
        params["head"] = {"w": head,
                          "sw": quant.init_step_from_tensor(head, 8.0),
                          "sa": jnp.float32(0.05)}
    if cfg.mtp:
        params["mtp"] = {
            "norm": common.init_norm(cfg.norm, cfg.d_model, cfg.param_dtype),
            "proj": common.init_qdense(keys[-1], 2 * cfg.d_model, cfg.d_model,
                                       cfg.param_dtype),
        }
    return params


def _cache_bits_for(cache_bits, group: str, layer: int):
    """Resolve the per-layer cache bit-width: int (uniform), or
    {group: per-layer array} (PrecisionPolicy.cache_bits_arrays()).
    Returns 4/8, or None for full precision (missing group / 16)."""
    if cache_bits is None:
        return None
    if isinstance(cache_bits, (int, float)):
        b = int(round(float(cache_bits)))
    else:
        arr = cache_bits.get(group)
        if arr is None:
            return None
        # HOST-side numpy on purpose: bit-widths are compile-time layout
        # decisions (they pick buffer dtypes/shapes) and must stay concrete
        # under jit/eval_shape.
        a = np.asarray(arr, np.float32).reshape(-1)
        if layer >= a.shape[0]:
            raise ValueError(
                f"cache_bits[{group!r}] has {a.shape[0]} entries but layer "
                f"{layer} was requested — the array must cover every layer "
                f"of the group (PrecisionPolicy.cache_bits_arrays() does)")
        b = int(round(float(a[layer])))
    if b not in (4, 8, 16):
        raise ValueError(f"cache bits must be 4, 8 or 16(full), got {b}")
    return None if b == 16 else b


def init_caches(cfg, batch: int, max_seq: int, cache_dtype=None,
                cache_bits=None, page_geom=None, plan=None) -> dict:
    """Preallocated per-layer decode caches (attention: (B, S_max, ...)).

    Cache contract (serve/kv_cache.py builds on this):
      - prefill returns caches sized to the processed sequence; they are
        spliced into these preallocated buffers at position 0 (quantized
        on the way in when the buffers are a quantized layout).
      - decode writes one row per request at its OWN absolute position
        (attention.cache_write), so requests in a batch may sit at
        different sequence offsets (continuous batching).
      - rows at/beyond a request's valid length are garbage until
        overwritten; the decode attention mask (s_pos <= position) keeps
        them unread.
      - ``cache_dtype`` overrides cfg.cache_dtype (serving holds the cache
        in the compute dtype for bit-exact prefill->decode parity;
        cfg.cache_dtype stays the memory-saving default for training runs).
      - ``cache_bits`` (8/4/16, scalar or {group: per-layer array}) selects
        the QUANTIZED cache layout per layer.  Uniform bits across a
        pattern slot keep the stacked scan layout; MIXED per-layer bits
        give per-layer shapes/dtypes, so ``caches['pat']`` becomes
        BUCKETED — a LayerBuckets of stacked runs with uniform bits each
        (models/layout.py; apply scans within each run).
      - ``plan`` overrides the pattern-cache layout: a bucket-sizes tuple
        (or core/policy.BucketPlan) forces that exact partition — the
        engine passes the JOINT weight+cache plan here so packed params
        and cache buckets share boundaries; ``'unrolled'`` forces the
        legacy per-layer list (the differential oracle).  Cache bits must
        be uniform within every requested bucket.
      - ``page_geom`` = (n_pages, page_size) swaps the per-slot buffers
        for physical page POOLS (serve/paging.py — GQA only); the block
        table addressing them lives in the engine's PagedServeCache and
        is injected per dispatch.
    """
    caches: dict = {}
    for i, bdef in enumerate(cfg.prefix):
        caches[f"prefix{i}"] = init_block_cache(
            cfg, bdef, batch, max_seq, cache_dtype,
            _cache_bits_for(cache_bits, f"prefix{i}", 0), page_geom)
    if cfg.n_repeats:
        bits_grid = [[_cache_bits_for(cache_bits, f"pat{j}", r)
                      for j, _ in enumerate(cfg.pattern)]
                     for r in range(cfg.n_repeats)]
        mixed = any(len({bits_grid[r][j] for r in range(cfg.n_repeats)}) > 1
                    for j, _ in enumerate(cfg.pattern))
        sizes = None
        if plan is not None and not (isinstance(plan, str)
                                     and plan == "unrolled"):
            sizes = tuple(int(s) for s in getattr(plan, "sizes", plan))
            if sum(sizes) != cfg.n_repeats:
                raise ValueError(f"cache plan sizes {sizes} sum to "
                                 f"{sum(sizes)}, expected {cfg.n_repeats}")
        elif plan is None and mixed:
            # Auto plan: maximal contiguous runs of identical per-slot
            # cache bits (the cache-only bucket signature).
            sizes = []
            for r in range(cfg.n_repeats):
                if sizes and bits_grid[r] == bits_grid[r - 1]:
                    sizes[-1] += 1
                else:
                    sizes.append(1)
            sizes = tuple(sizes)

        def stack(c, n):
            return jax.tree.map(
                lambda t: jnp.broadcast_to(t, (n,) + t.shape), c)

        if isinstance(plan, str) and plan == "unrolled":
            caches["pat"] = [
                {f"p{j}": init_block_cache(cfg, bd, batch, max_seq,
                                           cache_dtype, bits_grid[r][j],
                                           page_geom)
                 for j, bd in enumerate(cfg.pattern)}
                for r in range(cfg.n_repeats)]
        elif sizes is None:
            caches["pat"] = {
                f"p{j}": stack(init_block_cache(cfg, bd, batch, max_seq,
                                                cache_dtype, bits_grid[0][j],
                                                page_geom), cfg.n_repeats)
                for j, bd in enumerate(cfg.pattern)}
        else:
            buckets, start = [], 0
            for m in sizes:
                for r in range(start, start + m):
                    if bits_grid[r] != bits_grid[start]:
                        raise ValueError(
                            f"cache plan bucket [{start}:{start + m}) mixes "
                            f"cache bits {bits_grid[start]} vs "
                            f"{bits_grid[r]} at layer {r} — bucket "
                            "boundaries must refine the cache-bit runs")
                buckets.append({
                    f"p{j}": stack(init_block_cache(cfg, bd, batch, max_seq,
                                                    cache_dtype,
                                                    bits_grid[start][j],
                                                    page_geom), m)
                    for j, bd in enumerate(cfg.pattern)})
                start += m
            caches["pat"] = LayerBuckets(tuple(buckets), sizes)
    return caches


def _embed(params, cfg, batch: Dict) -> jax.Array:
    if "embeds" in batch:
        x = batch["embeds"]
    elif "wq" in params["embed"]:     # serve layout: int8 codes, gather-first
        rows = jnp.take(params["embed"]["wq"], batch["tokens"], axis=0)
        x = rows.astype(cfg.compute_dtype) \
            * params["embed"]["scale"].astype(cfg.compute_dtype)
    else:
        table = quant.lsq_fake_quant(params["embed"]["w"],
                                     params["embed"]["sw"],
                                     jnp.float32(PIN_EDGE_BITS))
        x = jnp.take(table, batch["tokens"], axis=0)
    return x.astype(cfg.compute_dtype)


def _head(params, cfg, x: jax.Array) -> jax.Array:
    """LM head; weights and input activations pinned 8-bit (softmax rule)."""
    if cfg.tie_embeddings:
        p = params["embed"]
        if "wq" in p:
            w = (p["wq"].astype(x.dtype) * p["scale"].astype(x.dtype)).T
        else:
            w = quant.lsq_fake_quant(p["w"], p["sw"],
                                     jnp.float32(PIN_EDGE_BITS)).T
        sa = jnp.float32(0.05)
    else:
        p = params["head"]
        if "wq" in p:
            w = p["wq"].astype(x.dtype) * p["scale"].astype(x.dtype)
        else:
            w = quant.lsq_fake_quant(p["w"], p["sw"],
                                     jnp.float32(PIN_EDGE_BITS))
        sa = p.get("sa", jnp.float32(0.05))
    xq = quant.lsq_fake_quant(x, sa, jnp.float32(PIN_EDGE_BITS))
    return xq @ w.astype(x.dtype)


def _pattern_bits(policy_arrays, cfg) -> list:
    """Per-pattern-position bits dicts with stacked (n_repeats, ...) leaves."""
    return [policy_arrays[f"pat{j}"] for j in range(len(cfg.pattern))]


def _slot_index(cfg) -> Dict[tuple, tuple]:
    """tensor-path prefix -> (group, slot) from the policy registry."""
    index = {}
    for u in build_policy(cfg).units:
        for t in u.tensors:
            index[t[:-1] if t[-1] == "w" else t] = (u.group, u.slot)
    return index


def prequantize_params(params, policy_arrays, cfg):
    """Fake-quantize every registered weight ONCE per step, stacked, before
    the layer scan (EXPERIMENTS.md §Perf A3).

    Per-layer quantization inside the scan body gets loop-invariant-hoisted
    by XLA as a full-stack f32 intermediate that then rides the scan and the
    FSDP gathers at 2× the bytes; doing it explicitly here (a) keeps the
    scan xs in bf16, (b) computes each weight's quantization once per step
    instead of once per microbatch, and (c) leaves gradients identical (the
    stacked fake-quant carries the same LSQ custom-VJP).
    """
    slot_of = _slot_index(cfg)

    def walk(node, path):
        if isinstance(node, dict) and "w" in node and "sw" in node \
                and "sa" in node:
            key = slot_of.get(path)
            bits = (policy_arrays[key[0]][key[1]] if key is not None
                    else jnp.float32(4.0))
            w = node["w"]
            step = jnp.asarray(node["sw"], jnp.float32)
            b = jnp.asarray(bits, jnp.float32)
            extra_s = w.ndim - step.ndim
            extra_b = w.ndim - b.ndim
            qw = quant.lsq_fake_quant(
                w, step.reshape(step.shape + (1,) * extra_s),
                b.reshape(b.shape + (1,) * extra_b))
            return {"wpre": qw, "sa": node["sa"]}
        if isinstance(node, dict):
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        return node

    return walk(params, ())


def _layer_view(stack, i):
    """Layer ``i`` of one block's carried decode-cache stack, as the block
    reads it: a contiguous GQA cache stays the stack, tagged with its
    ``layer`` (attention.reads_by_layer); any other cache kind is sliced
    out."""
    if stack is None:
        return None
    if attn.reads_by_layer(stack):
        return dict(stack, layer=i)
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        stack)


def _layer_store(stack, new, i):
    """The carried stack after layer ``i`` wrote ``new`` (what the block
    returned for ``_layer_view(stack, i)``): a contiguous GQA block hands
    back the stack it wrote in place; a sliced layer is written back."""
    if stack is None:
        return None
    if attn.reads_by_layer(stack):
        return new
    return jax.tree.map(
        lambda a, n: jax.lax.dynamic_update_index_in_dim(a, n, i, 0),
        stack, new)


def decode_writes_in_place(caches) -> bool:
    """Whether a decode-mode ``apply`` over ``caches`` writes each layer's
    new rows in place: every stacked (scanned) block cache is a contiguous
    GQA cache, so no layer is sliced out and written back.  Unstacked
    per-layer caches (prefix blocks, an unrolled list) are their own
    buffers and never sliced."""
    pat = (caches or {}).get("pat")
    if pat is None or isinstance(pat, (list, tuple)):
        return True
    runs = pat.buckets if isinstance(pat, LayerBuckets) else (pat,)
    return all(c is None or attn.reads_by_layer(c)
               for run in runs for c in run.values())


def apply(params, policy_arrays, batch: Dict, cfg, ctx, mode: str = "train",
          caches: Optional[dict] = None, positions=None, tp_axis=None):
    """Returns (logits, new_caches, aux_loss).

    batch: {'tokens': (B,S) int32} and/or {'embeds': (B,S,d)}, plus
    'mrope_positions': (3,B,S) when cfg.rope == 'mrope'.
    positions: (B,S) absolute positions (decode: (B,1)); defaults to arange.
    tp_axis: mesh axis name when running INSIDE a serving shard_map body
    with column/row-sharded params and a head-sharded cfg (block_apply
    inserts the two completing psums; ServeEngine(mesh=...) is the caller).
    """
    x = _embed(params, cfg, batch)
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    mrope_positions = batch.get("mrope_positions")
    x = ctx.constrain(x, ctx.batch_spec, None, None)

    # train/prefill: quantize all weights once, outside the scan (§Perf A3).
    # decode reuses caller-provided (already-quantized serve) weights; a raw
    # checkpoint decodes via the per-layer path.
    if mode in ("train", "prefill"):
        block_params = {k: v for k, v in params.items()
                        if k == "pat" or k.startswith("prefix")}
        block_params = prequantize_params(block_params, policy_arrays, cfg)
        params = dict(params, **block_params)

    aux_total = jnp.float32(0.0)
    new_caches: dict = {}

    # ---- prefix blocks (unrolled) ----
    for i, bdef in enumerate(cfg.prefix):
        bits = {k: v[0] for k, v in policy_arrays[f"prefix{i}"].items()}
        cache = (caches or {}).get(f"prefix{i}")
        x, nc, aux = block_apply(params[f"prefix{i}"], x, bits, cfg, ctx,
                                 bdef, mode, cache, positions,
                                 mrope_positions, tp_axis)
        new_caches[f"prefix{i}"] = nc
        aux_total = aux_total + aux

    # ---- repeats: stacked scan | bucketed scans | python-unrolled ----
    # The layout is a single VALIDATED property resolved from params and
    # cache jointly (models/layout.resolve_pattern): a stacked-vs-list (or
    # mismatched-bucket) disagreement raises instead of silently zipping
    # wrong.  All three drivers share ``pattern_step`` — the exact same
    # per-layer op order — which is the bit-exactness oracle between them.
    if cfg.n_repeats:
        pat_caches = (caches or {}).get("pat")
        lay = layout.resolve_pattern(params["pat"], pat_caches,
                                     cfg.n_repeats)

        def pattern_step(layer_params, layer_bits, layer_cache, xx, aux_c):
            """One repeat of the pattern (layer_bits: list indexed by slot)."""
            out_cache = {}
            for j, bdef in enumerate(cfg.pattern):
                cache_j = (None if layer_cache is None
                           else layer_cache[f"p{j}"])
                xx, nc, aux = block_apply(
                    layer_params[f"p{j}"], xx, layer_bits[j], cfg, ctx, bdef,
                    mode, cache_j, positions, mrope_positions, tp_axis)
                out_cache[f"p{j}"] = nc if nc is not None else 0
                aux_c = aux_c + aux
            return xx, out_cache, aux_c

        if lay.kind == "unrolled":
            # Python-unrolled pattern (O(n_layers) compile) — the escape
            # hatch for per-layer structure no bucket plan stacks, and the
            # differential oracle (pack_params(layout='unrolled') /
            # init_caches(plan='unrolled')).  Stacked operands on the other
            # side are sliced per layer; a list cache comes back as a list
            # so the decode scan carry keeps a stable structure.
            pat_is_list = lay.params_kind == "unrolled"
            cache_is_list = lay.cache_kind == "unrolled"
            per_layer_caches = []
            for layer in range(cfg.n_repeats):
                layer_params = (params["pat"][layer] if pat_is_list else
                                jax.tree.map(lambda a, i=layer: a[i],
                                             params["pat"]))
                if pat_caches is None:
                    layer_cache = None
                elif cache_is_list:
                    layer_cache = pat_caches[layer]
                else:
                    layer_cache = jax.tree.map(lambda t, i=layer: t[i],
                                               pat_caches)
                bits = [{k: v[layer]
                         for k, v in policy_arrays[f"pat{j}"].items()}
                        for j in range(len(cfg.pattern))]
                x, out_cache, aux_total = pattern_step(
                    layer_params, bits, layer_cache, x, aux_total)
                per_layer_caches.append(out_cache)
            if cache_is_list:
                new_caches["pat"] = per_layer_caches
            else:
                new_caches["pat"] = jax.tree.map(
                    lambda *ls: jnp.stack([jnp.asarray(l) for l in ls]),
                    *per_layer_caches)
        else:
            pat_bits = _pattern_bits(policy_arrays, cfg)

            def body(carry, xs):
                xx, aux_c = carry
                layer_params, layer_bits, layer_cache = xs
                xx, out_cache, aux_c = pattern_step(
                    layer_params, layer_bits, layer_cache, xx, aux_c)
                return (xx, aux_c), out_cache

            def carried_body(carry, xs):
                # decode: the run's cache stack rides in the carry with
                # the layer index, so each layer writes only its new rows
                # in place and reads its layer by index (_layer_view)
                xx, aux_c, stack, i = carry
                layer_params, layer_bits = xs
                view = {n: _layer_view(c, i) for n, c in stack.items()}
                xx, out_cache, aux_c = pattern_step(
                    layer_params, layer_bits, view, xx, aux_c)
                stack = {n: _layer_store(c, out_cache[n], i)
                         for n, c in stack.items()}
                return (xx, aux_c, stack, i + 1), None

            body_fn = jax.checkpoint(body) if mode == "train" else body

            def run(bp, bb, bc, xx, aux_c):
                """Scan one stacked run of layers -> (x, aux, cache)."""
                if mode == "decode" and bc is not None:
                    (xx, aux_c, bc, _), _ = jax.lax.scan(
                        carried_body, (xx, aux_c, bc, jnp.int32(0)),
                        (bp, bb))
                    return xx, aux_c, bc
                (xx, aux_c), cs = jax.lax.scan(body_fn, (xx, aux_c),
                                               (bp, bb, bc))
                return xx, aux_c, cs

            if lay.kind == "stacked":
                x, aux_total, new_caches["pat"] = run(
                    params["pat"], pat_bits, pat_caches, x, aux_total)
            else:
                # Bucketed (DESIGN.md §3): python-step only across
                # signature boundaries, lax.scan within each contiguous
                # run — program size is O(#buckets) at any depth, with
                # the unrolled path's per-layer op order preserved.
                out_buckets, start = [], 0
                for bi, m in enumerate(lay.sizes):
                    def _slice(t, s=start, mm=m):
                        return jax.tree.map(lambda a: a[s:s + mm], t)
                    bp = (params["pat"].buckets[bi]
                          if lay.params_kind == "bucketed"
                          else _slice(params["pat"]))
                    bb = [_slice(sb) for sb in pat_bits]
                    if pat_caches is None:
                        bc = None
                    elif lay.cache_kind == "bucketed":
                        bc = pat_caches.buckets[bi]
                    else:
                        bc = _slice(pat_caches)
                    x, aux_total, cs = run(bp, bb, bc, x, aux_total)
                    out_buckets.append(cs)
                    start += m
                new_caches["pat"] = LayerBuckets(tuple(out_buckets),
                                                 lay.sizes)

    x = common.apply_norm(cfg.norm, x, params["final_norm"])
    logits = _head(params, cfg, x)
    return logits, new_caches, {"aux": aux_total, "hidden": x}


# ===================================================================== loss
def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None,
                  z_weight: float = 1e-4):
    """Mean CE + z-loss; SPMD-safe (no gather over the sharded vocab dim)."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)            # (B,S)
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                          logits.ndim - 1)
    label_logit = jnp.sum(
        jnp.where(vocab_iota == labels[..., None], logits, 0.0), axis=-1)
    nll = lse - label_logit
    zloss = z_weight * lse ** 2
    per_tok = nll + zloss
    if mask is None:
        mask = jnp.ones_like(nll)
    mask = mask.astype(jnp.float32)
    loss = jnp.sum(per_tok * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    acc = jnp.sum((jnp.argmax(logits, -1) == labels) * mask) \
        / jnp.maximum(jnp.sum(mask), 1.0)
    return loss, acc


def loss_fn(params, policy_arrays, batch: Dict, cfg, ctx):
    """Next-token LM loss (or masked classification for encoders).

    batch: inputs + 'labels' (B,S) [+ 'loss_mask'].  Returns (loss, metrics).
    """
    logits, _, extras = apply(params, policy_arrays, batch, cfg, ctx,
                              mode="train")
    labels = batch["labels"]
    mask = batch.get("loss_mask")
    loss, acc = cross_entropy(logits, labels, mask)
    total = loss + extras["aux"]
    metrics = {"loss": loss, "accuracy": acc, "aux_loss": extras["aux"]}

    if cfg.mtp and "tokens" in batch and labels.shape[1] > 2:
        # Multi-token prediction: predict t+2 from [h_t ; embed(tok_{t+1})]
        # through a lightweight projection + the shared LM head
        # (single-depth MTP head, simplified vs the paper's extra block —
        # DESIGN.md §9).
        hidden = extras["hidden"]
        e = _embed(params, cfg, batch)
        hh = common.apply_norm(cfg.norm, hidden[:, :-1, :],
                               params["mtp"]["norm"])
        zcat = jnp.concatenate([hh, e[:, 1:, :]], axis=-1)
        hm = common.qproj(zcat, params["mtp"]["proj"], jnp.float32(4.0))
        mtp_logits = _head(params, cfg, hm)
        mtp_loss, _ = cross_entropy(mtp_logits, labels[:, 1:],
                                    None if mask is None else mask[:, 1:])
        total = total + cfg.mtp_weight * mtp_loss
        metrics["mtp_loss"] = mtp_loss
    return total, metrics


# ============================================================ policy builder
def _unit(group, layer, slot, tensors, n_params, macs, in_features, sub=None,
          pinned=None) -> QuantUnit:
    name = f"{group}.{slot}" + (f".e{sub}" if sub is not None else "") \
        + f".L{layer}"
    if pinned is None and in_features < PIN_MIN_IN_FEATURES:
        pinned = PIN_NARROW_BITS
    return QuantUnit(name=name, group=group, layer=layer, slot=slot,
                     tensors=tuple(tensors), n_params=int(n_params),
                     macs_per_token=float(macs), in_features=int(in_features),
                     sub=sub, pinned_bits=pinned)


def _block_units(cfg, bdef: BlockDef, group: str, layer: int, base: tuple):
    """Quant units of one block; `base` = param path prefix of the block."""
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    f = cfg.d_ff
    units = []
    if bdef.mixer in ("gqa", "bidir"):
        nqkv = d * (h * dh + 2 * hkv * dh)
        units.append(_unit(group, layer, "attn_qkv",
                           [base + ("attn", w, "w") for w in
                            ("wq", "wk", "wv")], nqkv, nqkv, d))
        units.append(_unit(group, layer, "attn_wo",
                           [base + ("attn", "wo", "w")], h * dh * d,
                           h * dh * d, h * dh))
    elif bdef.mixer == "mla":
        ql, kvl = cfg.q_lora_rank, cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
        n_a = d * ql + d * (kvl + dr)
        units.append(_unit(group, layer, "attn_q_a",
                           [base + ("attn", "wq_a", "w"),
                            base + ("attn", "wkv_a", "w")], n_a, n_a, d))
        n_qb = ql * h * (dn + dr)
        units.append(_unit(group, layer, "attn_q_b",
                           [base + ("attn", "wq_b", "w")], n_qb, n_qb, ql))
        n_kvb = kvl * h * (dn + dv)
        units.append(_unit(group, layer, "attn_kv_b",
                           [base + ("attn", "wk_b", "w"),
                            base + ("attn", "wv_b", "w")], n_kvb, n_kvb, kvl))
        units.append(_unit(group, layer, "attn_wo",
                           [base + ("attn", "wo", "w")], h * dv * d,
                           h * dv * d, h * dv))
    elif bdef.mixer == "mamba":
        di, ds, dtr = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
        units.append(_unit(group, layer, "mamba_in",
                           [base + ("mamba", "in", "w")], d * 2 * di,
                           d * 2 * di, d))
        nx = di * (dtr + 2 * ds)
        units.append(_unit(group, layer, "mamba_x",
                           [base + ("mamba", "x", "w")], nx, nx, di))
        units.append(_unit(group, layer, "mamba_dt",
                           [base + ("mamba", "dt", "w")], dtr * di, dtr * di,
                           dtr))
        units.append(_unit(group, layer, "mamba_out",
                           [base + ("mamba", "out", "w")], di * d, di * d, di))
    elif bdef.mixer == "mlstm":
        di, nh = cfg.xlstm_d_inner, cfg.n_heads
        units.append(_unit(group, layer, "lstm_up",
                           [base + ("lstm", "up", "w")], d * 2 * di,
                           d * 2 * di, d))
        units.append(_unit(group, layer, "lstm_qkv",
                           [base + ("lstm", w, "w") for w in
                            ("wq", "wk", "wv")], 3 * di * di, 3 * di * di, di))
        units.append(_unit(group, layer, "lstm_if",
                           [base + ("lstm", "wif", "w")], di * 2 * nh,
                           di * 2 * nh, di))
        units.append(_unit(group, layer, "lstm_down",
                           [base + ("lstm", "down", "w")], di * d, di * d, di))
    elif bdef.mixer == "slstm":
        nh = cfg.n_heads
        dh_s = d // nh
        units.append(_unit(group, layer, "lstm_w",
                           [base + ("lstm", "w", "w")], d * 4 * d, d * 4 * d,
                           d))
        units.append(_unit(group, layer, "lstm_r",
                           [base + ("lstm", "r")], nh * dh_s * 4 * dh_s,
                           nh * dh_s * 4 * dh_s, dh_s))

    if bdef.ffn in ("swiglu", "gelu", "slstm_ffn"):
        ff = cfg.slstm_d_ff if bdef.ffn == "slstm_ffn" else (bdef.d_ff or f)
        gated = bdef.ffn != "gelu"
        tensors = ([base + ("mlp", "gate", "w"), base + ("mlp", "up", "w")]
                   if gated else [base + ("mlp", "up", "w")])
        n_up = (2 if gated else 1) * d * ff
        units.append(_unit(group, layer, "mlp_gateup", tensors, n_up, n_up, d))
        units.append(_unit(group, layer, "mlp_down",
                           [base + ("mlp", "down", "w")], ff * d, ff * d, ff))
    elif bdef.ffn == "moe":
        e, k = cfg.n_experts, cfg.top_k
        units.append(_unit(group, layer, "moe_router",
                           [base + ("moe", "router", "w")], d * e, d * e, d,
                           pinned=PIN_EDGE_BITS))
        for ei in range(e):
            n_gu = 2 * d * f
            units.append(_unit(group, layer, "moe_gateup",
                               [base + ("moe", "gate", "w"),
                                base + ("moe", "up", "w")], n_gu,
                               n_gu * k / e, d, sub=ei))
            units.append(_unit(group, layer, "moe_down",
                               [base + ("moe", "down", "w")], f * d,
                               f * d * k / e, f, sub=ei))
        if cfg.n_shared_experts:
            fs = f * cfg.n_shared_experts
            units.append(_unit(group, layer, "mlp_gateup",
                               [base + ("moe", "shared", "gate", "w"),
                                base + ("moe", "shared", "up", "w")],
                               2 * d * fs, 2 * d * fs, d))
            units.append(_unit(group, layer, "mlp_down",
                               [base + ("moe", "shared", "down", "w")],
                               fs * d, fs * d, fs))
    return units


def _block_cache_unit(cfg, bdef: BlockDef, group: str, layer: int):
    """KV-cache precision atom of one block (None if the block keeps no
    per-token cache).  GQA caches are selectable int8/int4; MLA's
    compressed latent is pinned full precision (the compression IS its
    memory story) and recurrent/SSM state has no sequence axis — both are
    accounted, never selected (DESIGN.md §3)."""
    name = f"{group}.cache.L{layer}"
    if bdef.mixer in ("gqa",):
        elems = 2 * cfg.n_kv_heads * cfg.head_dim
        return CacheUnit(name=name, group=group, layer=layer,
                         kv_elems_per_token=elems)
    if bdef.mixer == "mla":
        elems = cfg.kv_lora_rank + cfg.qk_rope_dim
        return CacheUnit(name=name, group=group, layer=layer,
                         kv_elems_per_token=elems,
                         pinned_bits=CACHE_FULL_BITS)
    return None   # bidir: no cache; recurrent state: O(1), not per-token


def build_policy(cfg, b_hi: float = 4.0, b_lo: float = 2.0) -> PrecisionPolicy:
    """Enumerate every quant-unit of an architecture (+ pinned edges) and
    every per-layer KV-cache unit (serving state precision)."""
    units = []
    cache_units = []
    if not cfg.embed_input:
        units.append(_unit("embed", 0, "embed", [("embed", "w")],
                           cfg.vocab * cfg.d_model, 0.0, cfg.vocab,
                           pinned=PIN_EDGE_BITS))
    for i, bdef in enumerate(cfg.prefix):
        units.extend(_block_units(cfg, bdef, f"prefix{i}", 0, (f"prefix{i}",)))
        cu = _block_cache_unit(cfg, bdef, f"prefix{i}", 0)
        if cu is not None:
            cache_units.append(cu)
    for r in range(cfg.n_repeats):
        for j, bdef in enumerate(cfg.pattern):
            units.extend(_block_units(cfg, bdef, f"pat{j}", r,
                                      ("pat", f"p{j}")))
            cu = _block_cache_unit(cfg, bdef, f"pat{j}", r)
            if cu is not None:
                cache_units.append(cu)
    if not cfg.tie_embeddings:
        units.append(_unit("head", 0, "head", [("head", "w")],
                           cfg.d_model * cfg.vocab, cfg.d_model * cfg.vocab,
                           cfg.d_model, pinned=PIN_EDGE_BITS))
    return PrecisionPolicy(units, b_hi=b_hi, b_lo=b_lo,
                           cache_units=cache_units)


def fetch_unit_tensor(params, unit: QuantUnit, path: tuple):
    """Weight tensor + LSQ step for one member tensor of a unit."""
    node = params
    for pth in path:
        node = node[pth]
    w = node
    # step: sibling 'sw' (slstm 'r' stores it as 'r_sw' next to 'r')
    parent = params
    for pth in path[:-1]:
        parent = parent[pth]
    step = parent.get(path[-1] + "_sw", None)
    if step is None:
        step = parent["sw"] if "sw" in parent else None
    if step is None:
        raise KeyError(f"no step size for {path}")
    if unit.group.startswith("pat"):
        w = w[unit.layer]
        step = step[unit.layer] if getattr(step, "ndim", 0) >= 1 else step
    if unit.sub is not None:
        w = w[unit.sub]
        step = step[unit.sub] if getattr(step, "ndim", 0) >= 1 else step
    return w, step
