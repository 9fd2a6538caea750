"""Plain reference of a GQA + mixture-of-experts decoder (dbrx's block):
GQA attention with RoPE, a softmax router over E experts with top-k
routing and renormalised weights, SwiGLU experts, LSQ fake-quantized
weights and activations at per-layer (per-expert) bits, and an int8 KV
cache on the decode positions.

Straight ``jax.numpy`` at ``highest`` matmul precision over each sequence
at once, with nothing imported from the program.  It reads the
benchmark's own weights one layer at a time (``weights.Source``: the
program's checkpoint layout, unstacked), so its float32 peak is about one
layer, and runs every checked sequence through a layer before it takes
the next.

The model, per layer l, with the bits b of layer l (one per projection
group; per expert for the expert groups) and the functions of
``dense_gqa`` (``fq``, ``proj``, ``rope``, the int8 cache rule)::

    h = norm(x)           # ln: (x - mean) / sqrt(var + eps) * scale + bias
    q, k, v = proj(h, wq), proj(h, wk), proj(h, wv)
    q, k, v = clip(q, -c, c), clip(k, -c, c), clip(v, -c, c)  # arch.clip_qkv c
    q, k = rope(q), rope(k)          # half-split rotation, base rope_theta
    x  += proj(causal_softmax(q k^T / sqrt(hd)) v, wo)   # kv head = h // group
    h   = norm(x)
    p   = softmax(proj8(h, router))                       # (T, E), float32
    top = the k largest of p;  w_e = p_e / sum_{top} p    # e in top, else 0
    y_e = proj(silu(proj(h, gate_e)) * proj(h, up_e), down_e)   # at b_e
    x  += sum_e w_e y_e
    logits = fq(norm(x), head_sa, 8) @ fq(W_head, sw_head, 8)

``proj8(h, P) = fq(h, P.sa, 8) @ W`` with ``W = clip(round(P.w / s),
-128, 127) * s`` (s = P.sw) held in the compute type, as the program holds
an 8-bit projection; the router is pinned to 8 bits.  Every expert is
computed for every token and weighted by ``w_e`` (zero off the top k): no
capacity, no token dropped.

Departures from the published model (databricks/dbrx-base): the weights
and activations are LSQ fake-quantized at the served policy's bits (the
published model is bf16, its router a bf16 linear); the LayerNorm holds a
bias, zero, as the program's layout does (dbrx has none); the clamp is
applied only where the configuration states ``arch.clip_qkv`` (dbrx: 8);
decode positions read K and V through the int8 cache.

``act`` rounds every value held in the compute type, as in ``dense_gqa``:
the embedding rows and 8-bit weights, quantized activations,
projection outputs (the router's too), rotated q and k, attention
outputs, silu(g) and its product with u, each weighted expert output
w_e y_e (w_e rounded first) and their sum, the residual stream and the
logits.  The routing weights are computed from the rounded router logits
in float32.  A norm's output goes to the quantizer unrounded.
"""
from __future__ import annotations

import functools
from typing import Callable, Iterator, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.references import dense_gqa as dense
from benchmarks.chip.references.dense_gqa import F32, HI

SLOTS = ("attn_qkv", "attn_wo", "moe_gateup", "moe_down")
CFG_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim", "norm",
            "norm_eps", "rope_theta", "tie_word_embeddings", "head_act_step",
            "num_experts_per_tok")


def _norm(cfg: dict, x, p):
    if cfg["norm"] != "ln":
        return dense._norm(cfg, x, p)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + cfg["norm_eps"])
    return y * p["scale"].astype(F32) + p["bias"].astype(F32)


def _qkv(cfg: dict, act, h, a, bits):
    """q (T, hq, hd), k, v (T, hkv, hd) before the rotation, each clamped to
    +-``clip_qkv`` where the configuration states it."""
    t = h.shape[0]
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    clip = cfg.get("clip_qkv")

    def one(w, heads):
        y = dense._proj(h, a[w], bits, act)
        if clip is not None:
            y = jnp.clip(y, -clip, clip)
        return y.reshape(t, heads, hd)

    return one("wq", hq), one("wk", hkv), one("wv", hkv)


def _router(h, p, act):
    """The router's logits, its 8-bit weight held as the program serves an
    8-bit projection: each code times the step, in the compute type."""
    s = jnp.maximum(jnp.abs(p["sw"].astype(F32)), 1e-9)
    w = act(jnp.clip(jnp.round(p["w"].astype(F32) / s), -128.0, 127.0) * s)
    return act(jnp.dot(act(dense.fq(h, p["sa"], 8)), w, precision=HI))


def _experts(cfg: dict, act, h, m, b):
    """The expert layer's output (T, d): the router's top k of E, their
    renormalised weights, each expert's SwiGLU at its own bits."""
    logits = _router(h, m["router"], act)
    probs = jax.nn.softmax(logits.astype(F32), -1)
    top_w, top_i = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    n_exp = probs.shape[-1]
    weight = jnp.sum(jax.nn.one_hot(top_i, n_exp, dtype=F32)
                     * top_w[..., None], 1)                      # (T, E)

    def expert(acc, e):
        gate, up, down, w_e, bg, bd = e
        g = dense._proj(h, gate, bg, act)
        u = dense._proj(h, up, bg, act)
        y = dense._proj(act(act(jax.nn.silu(g)) * u), down, bd, act)
        return acc + act(act(w_e)[:, None] * y), None

    banks = (m["gate"], m["up"], m["down"], weight.T, b["moe_gateup"],
             b["moe_down"])
    acc, _ = jax.lax.scan(expert, jnp.zeros_like(h), banks)
    return act(acc)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(frozen: tuple, act, x, lp, b, prompt_len):
    cfg = dict(frozen)
    t = x.shape[0]
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    a = lp["attn"]
    h = _norm(cfg, x, lp["norm1"])
    pos = jnp.arange(t)
    q, k, v = _qkv(cfg, act, h, a, b["attn_qkv"])
    q = act(dense._rope(q, pos, cfg["rope_theta"]))
    k = act(dense._rope(k, pos, cfg["rope_theta"]))
    group = hq // hkv
    o = dense._attend(q, k, v, group, hd, pos)
    if cfg["cache_bits"] == 8:
        kq, vq = dense._int8_kv(k, v, prompt_len)
        o = jnp.where((pos >= prompt_len)[:, None, None],
                      dense._attend(q, kq, vq, group, hd, pos), o)
    o = act(o.reshape(t, hq * hd))
    x = act(x + dense._proj(o, a["wo"], b["attn_wo"], act))
    h = _norm(cfg, x, lp["norm2"])
    return act(x + _experts(cfg, act, h, lp["moe"], b)), (k, v)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _embed(frozen: tuple, act, embed, tokens):
    """The embedding rows of ``tokens`` as held: each row's codes times the
    step (the rows gathered first, so the table is never held in float32)."""
    return act(dense._edge({"w": embed["w"][tokens], "sw": embed["sw"]},
                           act))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(frozen: tuple, act, x, edges):
    cfg = dict(frozen)
    x = _norm(cfg, x, edges["final_norm"])
    if cfg["tie_word_embeddings"]:
        w, sa = dense._edge(edges["embed"], act).T, jnp.float32(
            cfg["head_act_step"])
    else:
        w, sa = dense._edge(edges["head"], act), edges["head"]["sa"]
    return act(jnp.dot(act(dense.fq(x, sa, 8)), w, precision=HI))


def _frozen(cfg: dict) -> tuple:
    eng = cfg["engine"]
    if eng.get("cache") == "quantized" and eng.get("cache_bits") != 8:
        raise ValueError(f"no reference for a {eng.get('cache_bits')}-bit "
                         "KV cache")
    return tuple((k, cfg[k]) for k in CFG_KEYS) + (
        ("clip_qkv", cfg.get("arch", {}).get("clip_qkv")),
        ("cache_bits", eng.get("cache_bits") if eng.get("cache") ==
         "quantized" else None))


def _layer_bits(bits: dict, i: int) -> dict:
    return {s: jnp.asarray(bits[s][i], F32) for s in SLOTS}


def logits_by_layer(cfg: dict, source, bits: dict,
                    samples: Sequence[Tuple[Sequence[int], int]],
                    acts: Sequence[Callable], n_kv: int = 0,
                    ) -> Iterator[List[tuple]]:
    """For each sample (the tokens fed, of which the first ``prompt_len``
    are the prompt), for each rounding in ``acts``: (T, V) float32 logits
    of the sample's first T positions -- its tokens, padded to a multiple
    of ``prompt_bucket`` -- and the keys (rotated) and values of its first
    ``n_kv`` layers, each (n_kv, T, kv heads, head dim).  ``source`` gives
    the weights (``edges()``, ``layer(i)``, ``release()``); ``bits``:
    policy slot -> per-layer bits (per expert for the expert slots).
    Every sample goes through a layer before the next layer is made; the
    head runs one sample at a time, as the results are taken."""
    frozen = _frozen(cfg)
    bucket, width = cfg["prompt_bucket"], cfg["max_seq"]
    edges = source.edges()
    runs = []
    for fed, p_len in samples:
        t = min(-(-len(fed) // bucket) * bucket, width)
        toks = np.zeros(t, np.int32)
        toks[:len(fed)] = fed
        runs.append((jnp.asarray(toks), jnp.int32(p_len)))
    xs = [[_embed(frozen, act, edges["embed"], toks) for act in acts]
          for toks, _ in runs]
    kvs = [[[] for _ in acts] for _ in runs]
    for i in range(cfg["num_hidden_layers"]):
        lp = source.layer(i)
        b = _layer_bits(bits, i)
        for s, (_, p_len) in enumerate(runs):
            for j, act in enumerate(acts):
                xs[s][j], kv = _layer(frozen, act, xs[s][j], lp, b, p_len)
                if i < n_kv:
                    kvs[s][j].append(kv)
        del lp
        source.release()
    for s in range(len(runs)):
        out = []
        for j, act in enumerate(acts):
            kv = kvs[s][j]
            stacked = ((jnp.stack([k for k, _ in kv]),
                        jnp.stack([v for _, v in kv])) if kv else None)
            out.append((_head(frozen, act, xs[s][j], edges), stacked))
        xs[s] = kvs[s] = None
        yield out
