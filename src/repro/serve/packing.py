"""Offline packed-weight deployment pass (DESIGN.md §3).

``pack_params`` walks a QAT checkpoint with a knapsack-selected
``PrecisionPolicy`` (as arrays) and converts every selectable unit's
weights into the **packed serving layout**:

  * int4 units -> K-major uint8, 2 codes/byte  (4× fewer HBM bytes vs bf16)
  * int2 units -> K-major uint8, 4 codes/byte  (8×)
  * pinned 8-bit edges (embedding / LM head / routers) -> int8 codes
  * per-output-channel f32 scales (a per-tensor LSQ step is stored
    broadcast, so per-channel calibration needs no format change)

Codes are computed with the same clip(round(w/s)) arithmetic as the
fake-quant path, so a packed model is greedy-argmax bit-parity with the
fake-quant serving layout on the CPU ref path (kernels/ref.dequant_matmul);
on TPU the packed buffers feed kernels/quant_matmul.py directly.

Mixed-precision packed buffers have bit-width-dependent shapes, so the
repeat pattern cannot stay ONE stacked scan operand — but a knapsack
policy only emits a handful of bit-levels, so by default ``pack_params``
emits the BUCKETED layout (models/layout.LayerBuckets): maximal
contiguous runs of layers with identical joint (weight-bits, cache-bits)
signatures (core/policy.bucket_plan), each run's ``PackedLinear`` leaves
stacked on a leading axis and driven by one ``lax.scan`` — O(#buckets)
compile instead of O(depth).  ``layout='unrolled'`` keeps the legacy
per-layer list (the differential oracle).  MoE expert banks stay
per-expert ``PackedLinear`` lists inside each bucket (per-expert bits
enter the bucket signature, so a bucket's expert banks stack cleanly).

``resident_weight_bytes`` measures the bytes a params tree actually keeps
resident — summed over real buffers, not a bits×params formula — which is
what benchmarks/serve_bench.py reports as the memory axis of the
mixed-precision frontier.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import policy as policy_mod
from repro.core import quant
from repro.core.quant import PackedLinear
from repro.models.layout import LayerBuckets
from repro.serve import residency


def quantize_edge(p: dict) -> dict:
    """Pinned 8-bit edge (embedding / LM head): int8 codes + scalar scale.

    Shared by quantize_for_serving (serve/engine.py) and pack_params so the
    two serving layouts carry bit-identical edge codes (greedy parity
    depends on it — the LM head decides the argmax).
    """
    w = p["w"].astype(jnp.float32)
    step = jnp.maximum(jnp.abs(p["sw"]).astype(jnp.float32), 1e-9)
    codes = quant.quantize_int(w, step, jnp.float32(8.0))
    out = {"wq": codes.astype(jnp.int8), "scale": step}
    if "sa" in p:
        out["sa"] = p["sa"]
    return out


def _is_quant_node(node) -> bool:
    return isinstance(node, dict) and "w" in node and "sw" in node \
        and "sa" in node


def _scalar(a, e):
    """Per-expert slice of a possibly-per-expert step/sa array."""
    a = jnp.asarray(a)
    return a[e] if a.ndim >= 1 else a


def _pack_node(node: dict, bits):
    """One qdense ({'w','sw','sa'}) -> PackedLinear; expert banks
    ((E, K, N) weights with (E,) steps/bits) -> per-expert list."""
    w = node["w"]
    if w.ndim == 3:                          # MoE expert bank
        e = w.shape[0]
        b = np.broadcast_to(np.asarray(bits, np.float32), (e,))
        return [quant.pack_linear(w[i], _scalar(node["sw"], i),
                                  _scalar(node["sa"], i), _int_bits(b[i]))
                for i in range(e)]
    assert w.ndim == 2, w.shape
    b = np.asarray(bits, np.float32).reshape(-1)[0]
    return quant.pack_linear(w, node["sw"], node["sa"], _int_bits(b))


def _int_bits(b) -> int:
    bi = int(round(float(b)))
    if bi not in (2, 4, 8):
        raise ValueError(f"packable bit-widths are 2/4/8, got {b}")
    return bi


def _walk(node, path, layer, slot_of, policy_arrays):
    if _is_quant_node(node):
        key = slot_of.get(path)
        if key is None:
            bits = 4.0                       # unregistered unit: safe default
        else:
            group, slot = key
            bits = np.asarray(policy_arrays[group][slot])[layer]
        return _pack_node(node, bits)
    if isinstance(node, dict):
        return {k: _walk(v, path + (k,), layer, slot_of, policy_arrays)
                for k, v in node.items()}
    return node


def _stack_layer_trees(trees):
    """Stack per-layer packed trees onto a leading bucket axis.

    Within a bucket every layer shares the joint bit signature, so the
    PackedLinear leaves (and MoE per-expert lists) have identical
    treedefs/static metadata and stack leaf-wise.
    """
    try:
        return jax.tree.map(
            lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *trees)
    except ValueError as e:
        raise ValueError(
            "pack_params: layers inside one bucket do not share a packed "
            "structure — the bucket plan does not match the policy arrays "
            f"({e})") from e


def pack_params(params: dict, policy_arrays: Dict[str, Dict[str, Any]],
                cfg, cache_bits=None, layout: str = "bucketed") -> dict:
    """Convert a raw QAT checkpoint into the packed serving layout.

    params: the trained param pytree ({'w','sw','sa'} quant-units).
    policy_arrays: the knapsack outcome, ``PrecisionPolicy.as_arrays()``
    (HOST-side numpy — bit-widths become compile-time constants of the
    packed layout).

    ``layout='bucketed'`` (default) partitions the repeat pattern with
    ``core.policy.bucket_plan`` and stacks each run's packed leaves
    (models/layout.LayerBuckets) so transformer.apply scans within runs.
    Pass ``cache_bits`` (the engine's cache_bits value) when serving a
    QUANTIZED mixed-bits cache: the weight buckets must refine the joint
    weight+cache signature so params and cache share boundaries — the
    engine validates this at construction.  ``layout='unrolled'`` emits
    the legacy per-layer list (python-unrolled apply).
    """
    if layout not in ("bucketed", "unrolled"):
        raise ValueError(f"pack_params layout must be 'bucketed' or "
                         f"'unrolled', got {layout!r}")
    from repro.models import transformer as tf
    slot_of = tf._slot_index(cfg)

    out: dict = {}
    for key, node in params.items():
        if key in ("embed", "head") and isinstance(node, dict) \
                and "w" in node:
            out[key] = quantize_edge(node)
        elif key == "pat":
            def pack_layer(lyr):
                sub = jax.tree.map(lambda a, i=lyr: a[i], node)
                return _walk(sub, ("pat",), lyr, slot_of, policy_arrays)

            if layout == "unrolled":
                out[key] = [pack_layer(lyr) for lyr in range(cfg.n_repeats)]
            else:
                plan = policy_mod.bucket_plan(policy_arrays, cache_bits,
                                              n_layers=cfg.n_repeats)
                buckets, start = [], 0
                for m in plan.sizes:
                    buckets.append(_stack_layer_trees(
                        [pack_layer(start + i) for i in range(m)]))
                    start += m
                out[key] = LayerBuckets(tuple(buckets), plan.sizes)
        else:
            out[key] = _walk(node, (key,), 0, slot_of, policy_arrays)
    return out


# ------------------------------------------------------- tensor parallelism
# Shard-axis contract for the packed serving layout (DESIGN.md §3):
#   column-parallel (output channels sharded, input replicated):
#     wq/wk/wv (attention heads), gate/up (d_ff) — wp (Kp//pack, N) shards
#     along N, per-channel scales shard with it.  K-major nibble bytes pack
#     along K, so an N slice never splits a byte.
#   row-parallel (input channels sharded, output partial -> one psum):
#     wo (attention heads), down (d_ff) — K is the packed axis, so the
#     global buffer is REPACKED per shard (`_shard_row_packed`): each
#     shard's K-slab is nibble-packed independently and zero-padded to the
#     pack factor, so no byte ever straddles a shard boundary.  The
#     PackedLinear's static k_dim becomes the LOCAL K (what the shard_map
#     body sees); per-output-channel scales are replicated.
#   replicated: pinned int8 edges (embed/head/router), norms, steps.

_COLUMN_PARALLEL = ("wq", "wk", "wv", "gate", "up")
_ROW_PARALLEL = ("wo", "down")
MODEL_AXIS = "model"


def tp_shardable(cfg, n_shards: int) -> Optional[str]:
    """None if the config can serve tensor-parallel over ``n_shards``;
    otherwise the human-readable reason it cannot."""
    if n_shards < 2:
        return None
    blocks = tuple(cfg.prefix) + tuple(cfg.pattern)
    for b in blocks:
        if b.mixer != "gqa":
            return (f"sharded serving supports GQA attention blocks only "
                    f"(got mixer={b.mixer!r}; MLA/recurrent state has no "
                    f"KV-head axis to shard)")
        if b.ffn not in ("swiglu", "gelu", "moe", "none"):
            return f"sharded serving does not support ffn={b.ffn!r}"
        ff = b.d_ff or cfg.d_ff
        if b.ffn in ("swiglu", "gelu", "moe") and ff % n_shards:
            return f"d_ff {ff} % n_shards {n_shards} != 0"
    if cfg.n_heads % n_shards:
        return f"n_heads {cfg.n_heads} % n_shards {n_shards} != 0"
    if cfg.n_kv_heads % n_shards:
        return (f"n_kv_heads {cfg.n_kv_heads} % n_shards {n_shards} != 0 "
                f"(the KV cache shards along the KV-head axis)")
    return None


def _shard_row_packed(p: PackedLinear, n_shards: int) -> PackedLinear:
    """Repack a row-parallel (K-sharded) PackedLinear so every shard holds
    an independently K-major-packed slab: no byte straddles a shard.

    The returned buffer is the concatenation of the per-shard packed slabs
    (equal sizes: each slab zero-pads its K_local to the pack factor), to
    be sharded P(model, None) along axis 0; ``k_dim`` is set to the LOCAL
    K — the length of the activation slice each shard contracts against.
    """
    assert p.k_dim % n_shards == 0, (p.k_dim, n_shards)
    k_local = p.k_dim // n_shards
    if p.bits == 8:                     # 1 byte/code: slices already align
        return PackedLinear(wp=p.wp, scale=p.scale, sa=p.sa, bits=8,
                            k_dim=k_local)

    def repack(codes2d):
        slabs = [quant.pack_codes_kmajor(
            codes2d[i * k_local:(i + 1) * k_local], p.bits)
            for i in range(n_shards)]
        return jnp.concatenate(slabs, axis=0)

    codes = quant.unpack_codes_kmajor(p.wp, p.bits,
                                      jnp.int8)[..., :p.k_dim, :]
    if codes.ndim == 2:
        wp = repack(codes)
    else:                               # bucketed (m, Kp, N) layer stack
        wp = jnp.stack([repack(codes[lyr]) for lyr in range(codes.shape[0])])
    return PackedLinear(wp=wp, scale=p.scale,
                        sa=p.sa, bits=p.bits, k_dim=k_local)


def _pl_spec(kind: str, axis: str, p: PackedLinear) -> PackedLinear:
    """Spec tree node mirroring a PackedLinear (data fields hold specs).

    Specs count from the TRAILING axes so bucketed (leading layer-stack)
    leaves get the same sharding with a leading None prepended.
    """
    def lead(arr, *tail):
        nd = getattr(arr, "ndim", 0)
        return P(*(((None,) * (nd - len(tail))) + tail)) if tail else \
            P(*((None,) * nd))

    if kind == "col":       # wp (..., Kp, N) shards N; scales shard with it
        return PackedLinear(wp=lead(p.wp, None, axis),
                            scale=lead(p.scale, axis), sa=lead(p.sa),
                            bits=p.bits, k_dim=p.k_dim)
    if kind == "row":       # wp (..., Kp, N) shards the packed K slabs
        return PackedLinear(wp=lead(p.wp, axis, None),
                            scale=lead(p.scale), sa=lead(p.sa),
                            bits=p.bits, k_dim=p.k_dim)
    return PackedLinear(wp=lead(p.wp), scale=lead(p.scale), sa=lead(p.sa),
                        bits=p.bits, k_dim=p.k_dim)


def shard_packed_params(pparams: dict, cfg, n_shards: int,
                        axis: str = MODEL_AXIS) -> Tuple[dict, Any]:
    """(packed params, n_shards) -> (shard-ready params, PartitionSpec tree).

    Row-parallel leaves are repacked per shard (`_shard_row_packed`) and
    carry the LOCAL k_dim; everything else keeps its buffers and gets the
    column/replicated spec.  The spec tree has the same treedef as the
    params tree (P leaves), ready for ``jax.shard_map`` in_specs and
    ``jax.device_put`` placement.
    """
    reason = tp_shardable(cfg, n_shards)
    if reason is not None:
        raise ValueError(f"config not tensor-parallel-shardable: {reason}")

    def walk(node, name):
        if isinstance(node, PackedLinear):
            if name in _COLUMN_PARALLEL:
                return node, _pl_spec("col", axis, node)
            if name in _ROW_PARALLEL:
                local = _shard_row_packed(node, n_shards)
                return local, _pl_spec("row", axis, local)
            return node, _pl_spec("repl", axis, node)      # router etc.
        if isinstance(node, LayerBuckets):
            pairs = [walk(b, name) for b in node.buckets]
            return (LayerBuckets(tuple(v[0] for v in pairs), node.sizes),
                    LayerBuckets(tuple(v[1] for v in pairs), node.sizes))
        if isinstance(node, dict):
            pairs = {k: walk(v, k) for k, v in node.items()}
            return ({k: v[0] for k, v in pairs.items()},
                    {k: v[1] for k, v in pairs.items()})
        if isinstance(node, (list, tuple)):
            pairs = [walk(v, name) for v in node]
            return [v[0] for v in pairs], [v[1] for v in pairs]
        return node, P(*([None] * getattr(node, "ndim", 0)))

    out, specs = walk(pparams, "")
    return out, specs


def decode_weight_view(params):
    """Hoistable dequant view for the CPU/ref decode path.

    ``ref.dequant_matmul`` re-unpacks and re-dequantizes the full weight
    matrix EVERY decode step — which is why packed CPU decode measured
    slower than fake-quant despite streaming fewer resident bytes.  This
    view maps each PackedLinear to ``{'wpre': codes*scale (f32), 'sa'}``
    — the exact fake-quant dequant op order (codes*scale elementwise
    first, matmul in the activation dtype via models/common.qproj), so
    greedy-argmax bit-parity with the fake-quant layout is preserved —
    computed ONCE per decode dispatch (inside the jitted chunk, before
    the token scan) instead of once per token.  Nothing extra stays
    resident: the dense view is a per-dispatch temporary.

    TPU keeps the PackedLinear tree: the Pallas quant_matmul streams the
    packed bytes from HBM, which is the whole point there.
    """
    def conv(node):
        if isinstance(node, PackedLinear):
            return {"wpre": quant.packed_weight_dense(node, jnp.float32),
                    "sa": node.sa}
        return node

    return jax.tree.map(conv, params,
                        is_leaf=lambda n: isinstance(n, PackedLinear))


def params_are_packed(params) -> bool:
    """True if the tree contains any PackedLinear (packed serving layout)."""
    found = [False]

    def visit(x):
        if isinstance(x, PackedLinear):
            found[0] = True
        return x

    jax.tree.map(visit, params,
                 is_leaf=lambda x: isinstance(x, PackedLinear))
    return found[0]


def resident_weight_bytes(params) -> int:
    """Measured bytes the params tree actually keeps resident — delegates
    to serve/residency.py, the single definition bench, engine logging and
    tests all share (kept here for API stability)."""
    return residency.resident_bytes(params)


def bf16_resident_weight_bytes(params) -> int:
    """Bytes the same tree would keep resident served in bf16 — delegates
    to serve/residency.py (single definition)."""
    return residency.bf16_resident_bytes(params)
