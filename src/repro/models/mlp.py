"""MLP variants: SwiGLU / GeLU dense blocks and expert-parallel MoE.

MoE dispatch is sort-based (no one-hot dispatch matmuls) and runs under
``shard_map`` over the ``model`` axis — EP-as-TP:

  Activations are replicated across the model axis between blocks (Megatron
  TP convention), so every model shard already *has* every token; each shard
  simply selects the tokens routed to its local experts, runs its expert
  FFNs, and the per-token combine is completed by the same psum that TP
  needs anyway.  No standalone all-to-all, no replicated (E, C, d) buffer.

Per-expert quantization: every expert is its own quant-unit (finer
granularity than the paper needed, same formalism) — bits/steps are (E,)
vectors sliced per shard.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import quant
from repro.kernels import ops as kops
from repro.models.common import init_qdense, qproj


def act_fn(kind: str, x):
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    raise ValueError(kind)


# ------------------------------------------------------------------- dense
def init_dense_mlp(key, cfg, d_ff: Optional[int] = None, gated: bool = True,
                   d_in: Optional[int] = None) -> dict:
    d = d_in or cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"up": init_qdense(ks[1], d, f, cfg.param_dtype),
         "down": init_qdense(ks[2], f, d, cfg.param_dtype)}
    if gated:
        p["gate"] = init_qdense(ks[0], d, f, cfg.param_dtype)
    return p


def dense_mlp_apply(p, x, bits, activation: str = "silu"):
    """bits: {'mlp_gateup', 'mlp_down'}."""
    if "gate" in p:
        g = qproj(x, p["gate"], bits["mlp_gateup"])
        u = qproj(x, p["up"], bits["mlp_gateup"])
        h = act_fn(activation, g) * u
    else:
        h = act_fn(activation, qproj(x, p["up"], bits["mlp_gateup"]))
    return qproj(h, p["down"], bits["mlp_down"])


# --------------------------------------------------------------------- MoE
def init_moe(key, cfg) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    scale = d ** -0.5

    def expert_bank(k, d_in, d_out):
        w = jax.random.normal(k, (e, d_in, d_out), cfg.param_dtype) * scale
        sw = jax.vmap(lambda wi: quant.init_step_from_tensor(wi, 4.0))(w)
        sa = jnp.full((e,), 2.0 / jnp.sqrt(2.0 ** 3 - 1), jnp.float32)
        return {"w": w, "sw": sw, "sa": sa}

    p = {
        "router": init_qdense(ks[0], d, e, cfg.param_dtype),  # pinned 8-bit
        "gate": expert_bank(ks[1], d, f),
        "up": expert_bank(ks[2], d, f),
        "down": expert_bank(ks[3], f, d),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_dense_mlp(
            jax.random.split(ks[4])[0], cfg,
            d_ff=cfg.d_ff * cfg.n_shared_experts, gated=True)
    return p


def _quant_bank(bank, bits):
    """Quantized stacked expert weight bank (El, din, dout): pre-quantized
    (§Perf A3), LSQ fake-quant with per-expert steps/bits, or int4-code
    dequant in the serve layout."""
    if "wpre" in bank:
        return bank["wpre"]
    if "wq" in bank:
        return (bank["wq"].astype(jnp.float32)
                * bank["scale"].astype(jnp.float32)[:, None, None])
    sw = bank["sw"].astype(jnp.float32)[:, None, None]
    return quant.lsq_fake_quant(bank["w"], sw, bits[:, None, None])


def _expert_matmul(x, p):
    """One expert projection: PackedLinear (packed serving) or its
    per-dispatch dequant view {'wpre','sa'} (CPU decode path)."""
    if isinstance(p, quant.PackedLinear):
        return kops.packed_matmul(x, p)
    return x @ p["wpre"].astype(x.dtype)


def _expert_sa(p):
    return p.sa if isinstance(p, quant.PackedLinear) else p["sa"]


def _moe_local(x_flat, top_ids, top_w, gate_w, up_w, down_w, sa_gate,
               sa_down, bits_gateup, bits_down, e0, n_local, capacity,
               activation):
    """Per-shard expert compute. x_flat: (T, d) replicated across the model
    axis; experts [e0, e0+n_local) are local, weights pre-quantized
    (El, din, dout). Returns (T, d) partial output (this shard's experts
    only — caller psums)."""
    t, d = x_flat.shape
    k = top_ids.shape[1]
    flat_ids = top_ids.reshape(-1)                      # (T*k,)
    flat_w = top_w.reshape(-1)
    tok_ids = jnp.repeat(jnp.arange(t), k)

    local = flat_ids - e0
    valid = (local >= 0) & (local < n_local)
    sort_key = jnp.where(valid, local, n_local)         # invalid last
    order = jnp.argsort(sort_key, stable=True)
    local_s = jnp.where(valid, local, n_local)[order]
    tok_s = tok_ids[order]
    w_s = flat_w[order]
    valid_s = valid[order]

    counts = jnp.bincount(jnp.where(valid, local, n_local),
                          length=n_local + 1)[:n_local]
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(t * k) - starts[jnp.minimum(local_s, n_local - 1)]
    keep = valid_s & (pos < capacity)
    dest = jnp.where(keep, local_s * capacity + pos, n_local * capacity)

    # Dispatch: (El*C, d) buffer; out-of-range dest rows are dropped.
    buf = jnp.zeros((n_local * capacity, d), x_flat.dtype)
    buf = buf.at[dest].set(x_flat[tok_s], mode="drop")
    buf = buf.reshape(n_local, capacity, d)

    # Expert FFN (weights pre-quantized; per-expert act fake-quant here).
    if isinstance(gate_w, (list, tuple)):
        # Packed serving layout (serve/packing.py): each expert is its own
        # PackedLinear — mixed per-expert bit-widths give mixed packed
        # shapes, so the bank cannot stay one stacked einsum operand.  The
        # python loop unrolls over the (small) local expert count; each
        # expert's matmuls route through kops.packed_matmul (or the
        # per-dispatch dequant view {'wpre','sa'} on the CPU decode path —
        # serve/packing.decode_weight_view).  Under the BUCKETED pattern
        # layout the per-expert bits row is part of the layer signature
        # (core/policy.bucket_plan), so a bucket's expert banks stack on
        # the layer axis and the pattern scan slices them back to exactly
        # this per-layer list — no per-expert special-casing here.
        sa_g = sa_gate.astype(jnp.float32)
        sa_d = sa_down.astype(jnp.float32)
        outs = []
        for e in range(n_local):
            xq = quant.lsq_fake_quant(buf[e], sa_g[e], bits_gateup[e])
            g = _expert_matmul(xq, gate_w[e])
            u = _expert_matmul(xq, up_w[e])
            h = act_fn(activation, g) * u
            hq = quant.lsq_fake_quant(h, sa_d[e], bits_down[e])
            outs.append(_expert_matmul(hq, down_w[e]))
        out = jnp.stack(outs).reshape(n_local * capacity, d)
    else:
        def wmat(bank, dt):
            if isinstance(bank, dict):  # serve: int4 codes gathered, dequant
                return (bank["wq"].astype(jnp.float32)
                        * bank["scale"].astype(jnp.float32)[:, None, None]
                        ).astype(dt)
            return bank.astype(dt)

        sa_g = sa_gate.astype(jnp.float32)[:, None, None]
        xq = quant.lsq_fake_quant(buf, sa_g, bits_gateup[:, None, None])
        g = jnp.einsum("ecd,edf->ecf", xq, wmat(gate_w, xq.dtype))
        u = jnp.einsum("ecd,edf->ecf", xq, wmat(up_w, xq.dtype))
        h = act_fn(activation, g) * u
        sa_d = sa_down.astype(jnp.float32)[:, None, None]
        hq = quant.lsq_fake_quant(h, sa_d, bits_down[:, None, None])
        out = jnp.einsum("ecf,efd->ecd", hq, wmat(down_w, hq.dtype))
        out = out.reshape(n_local * capacity, d)

    # Combine: gather expert rows back, weight by router prob, scatter-add.
    rows = jnp.where(keep[:, None], out[jnp.minimum(dest, out.shape[0] - 1)],
                     0.0)
    y = jnp.zeros((t, d), x_flat.dtype)
    y = y.at[tok_s].add(rows * w_s[:, None].astype(rows.dtype), mode="drop")
    return y


def moe_apply(p, x, bits, cfg, ctx):
    """x: (B, S, d). bits: {'moe_gateup': (E,), 'moe_down': (E,),
    'moe_router': scalar, 'mlp_gateup'/'mlp_down': scalars for the shared
    expert}. Returns (y, aux_loss)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    x_flat = x.reshape(b * s, d)
    t = b * s

    # Router (pinned 8-bit; its output feeds a softmax — paper §3.4.2).
    logits = qproj(x_flat, p["router"], bits["moe_router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_ids = jax.lax.top_k(probs, k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, axis=-1, keepdims=True), 1e-9)

    # Load-balance auxiliary loss (Switch/GShard form).
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_ids, e, dtype=jnp.float32), axis=1), axis=0)
    aux = jnp.sum(me * ce) * e * cfg.moe_aux_weight

    n_shards = ctx.model_size
    assert e % n_shards == 0, (e, n_shards)
    n_local = e // n_shards

    # Fake-quantize the banks OUTSIDE the expert-parallel region: the
    # quantization is elementwise over the (possibly 2D-sharded) storage
    # layout, and the FSDP all-gather that feeds the experts then moves
    # bf16 — XLA would otherwise hoist the f32 upcast of the fake-quant
    # above the gather and ship f32 (§Perf A1).  Serve-layout banks stay
    # int4 codes THROUGH the gather (8× less wire) and dequantize inside.
    packed = isinstance(p["gate"], (list, tuple))
    serve = packed or "wq" in p["gate"]
    if serve:
        qgate, qup, qdown = p["gate"], p["up"], p["down"]
    else:
        # pre-quantized once per step by transformer.prequantize_params
        # (§Perf A3), or fake-quantized here for raw checkpoints.
        qgate = _quant_bank(p["gate"], bits["moe_gateup"])
        qup = _quant_bank(p["up"], bits["moe_gateup"])
        qdown = _quant_bank(p["down"], bits["moe_down"])
    if packed:
        sa_gate = jnp.stack([_expert_sa(e) for e in p["gate"]])
        sa_down = jnp.stack([_expert_sa(e) for e in p["down"]])
    else:
        sa_gate = p["gate"]["sa"]
        sa_down = p["down"]["sa"]

    if packed and ctx.mesh is not None and n_shards > 1:
        raise NotImplementedError(
            "packed MoE banks are a single-host serving layout; shard-mapped "
            "expert parallelism serves the int-code layout "
            "(quantize_for_serving) instead")
    if ctx.mesh is not None and n_shards > 1:
        # Tokens are sharded over the batch axes when divisible (decode with
        # tiny batches replicates its handful of tokens instead).
        batch_shardable = t % max(ctx.batch_size, 1) == 0
        t_local = t // ctx.batch_size if batch_shardable else t
        capacity = _round_up(
            max(int(t_local * k / e * cfg.capacity_factor + 0.999), 8), 8)
        ma = ctx.model_axis
        bspec = ctx.batch_spec if batch_shardable else None

        def shard_fn(x_r, ids_r, w_r, gate_w, up_w, down_w, sg, sd, bg, bd):
            e0 = jax.lax.axis_index(ma) * n_local
            y = _moe_local(x_r, ids_r, w_r, gate_w, up_w, down_w, sg, sd,
                           bg, bd, e0, n_local, capacity, cfg.activation)
            return jax.lax.psum(y, ma)

        def wspec(bank):
            if isinstance(bank, dict):
                return {k: (P(ma, None, None) if k in ("w", "wq") else P(ma))
                        for k in bank}
            return P(ma, None, None)

        y_flat = jax.shard_map(
            shard_fn, mesh=ctx.mesh,
            in_specs=(P(bspec, None), P(bspec, None), P(bspec, None),
                      wspec(qgate), wspec(qup), wspec(qdown),
                      P(ma), P(ma), P(ma), P(ma)),
            out_specs=P(bspec, None),
            check_vma=False,
        )(x_flat, top_ids, top_w, qgate, qup, qdown, sa_gate, sa_down,
          bits["moe_gateup"], bits["moe_down"])
    else:
        capacity = _round_up(
            max(int(t * k / e * cfg.capacity_factor + 0.999), 8), 8)
        y_flat = _moe_local(x_flat, top_ids, top_w, qgate, qup, qdown,
                            sa_gate, sa_down, bits["moe_gateup"],
                            bits["moe_down"], 0, e, capacity, cfg.activation)

    y = y_flat.reshape(b, s, d)
    if "shared" in p:
        y = y + dense_mlp_apply(p["shared"], x, bits, cfg.activation)
    return y, aux


def _round_up(x, m):
    return -(-x // m) * m
