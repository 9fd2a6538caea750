"""Plain reference of a dense decoder: GQA attention with RoPE, SwiGLU MLP,
LSQ fake-quantized weights and activations at per-layer bits, and an int8
KV cache on the decode positions.

Straight ``jax.numpy`` at ``highest`` matmul precision over the whole
sequence at once: no kernels, no cache, no batching, and nothing imported
from the program.  It reads the benchmark's own weight tree (``weights.py``;
the program's checkpoint layout) and the per-layer bits of the policy being
served.

The model, per layer l with bits b (one per projection group)::

    fq(x, s, b) = clip(round(x / s), -2^(b-1), 2^(b-1) - 1) * s
    proj(x, P)  = fq(x, P.sa, b) @ fq(P.w, P.sw, b)
    h   = norm(x);  q, k, v = proj(h, wq), proj(h, wk), proj(h, wv)
    q, k = rope(q), rope(k)          # half-split rotation, base rope_theta
    x  += proj(causal_softmax(q k^T / sqrt(hd)) v, wo)   # kv head = h // group
    h   = norm(x);  x += proj(silu(proj(h, gate)) * proj(h, up), down)
    logits = fq(norm(x), head_sa, 8) @ fq(W_head, sw_head, 8)

with the embedding rows read from fq(E, sw_E, 8) and W_head = E^T when
the embeddings are tied (activation step ``head_act_step``).

The KV cache (``engine.cache_bits`` 8): a query at or past ``prompt_len``
-- a decode step -- reads every key and value through int8 codes: K with
one scale per (head, channel), 1.5 x the largest |k| over the prompt's rows
over 127, codes clipped to +-127; V with one scale per (row, head), its
largest |v| over 127.  Queries inside the prompt -- the prefill -- read
them exact.

``act`` rounds every value the configuration holds in its compute type:
the embedding rows and 8-bit edge weights, quantized activations,
projection outputs, rotated q and k, attention outputs, silu(g) and its
product with u, the residual stream and the logits.  A norm's output goes
to the quantizer unrounded: the compiled program never holds it (on a
v5e, the reference agrees with the program's first-layer keys and values
to the bit on 92-100 % of them this way, on 20-82 % when it rounds there).
The reference rounds to the configuration's own type; the control to the
one below it (``check.py``).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
SLOTS = ("attn_qkv", "attn_wo", "mlp_gateup", "mlp_down")


def fq(x, step, bits):
    s = jnp.maximum(jnp.abs(step.astype(F32)), 1e-9)
    q = jnp.clip(jnp.round(x / s), -(2.0 ** (bits - 1)), 2.0 ** (bits - 1) - 1)
    return q * s


def _norm(cfg: dict, x, p):
    if cfg["norm"] == "nonparam_ln":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + cfg["norm_eps"])
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + cfg["norm_eps"])
    return y * p["scale"].astype(F32)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freqs                  # (T, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _proj(x, p, bits, act):
    """Integer weight codes first, their step after: the sums of products
    of held activations and codes are exact, in any order."""
    s = jnp.maximum(jnp.abs(p["sw"].astype(F32)), 1e-9)
    codes = jnp.clip(jnp.round(p["w"].astype(F32) / s),
                     -(2.0 ** (bits - 1)), 2.0 ** (bits - 1) - 1)
    return act(jnp.dot(act(fq(x, p["sa"], bits)), codes, precision=HI) * s)


def _edge(p, act):
    """An 8-bit edge weight as held: codes times the step, in the compute
    type."""
    s = jnp.maximum(jnp.abs(p["sw"].astype(F32)), 1e-9)
    q = jnp.clip(jnp.round(p["w"].astype(F32) / s), -128.0, 127.0)
    return act(q * act(s))


def _int8_kv(k, v, prompt_len):
    """K and V as a decode step reads them from the int8 cache."""
    rows = jnp.arange(k.shape[0])[:, None, None] < prompt_len
    k_scale = jnp.maximum(jnp.max(jnp.where(rows, jnp.abs(k), 0.0), 0)
                          * 1.5, 1e-8) / 127.0                  # (hkv, hd)
    kq = jnp.clip(jnp.round(k / k_scale), -127.0, 127.0) * k_scale
    v_scale = jnp.maximum(jnp.max(jnp.abs(v), -1, keepdims=True),
                          1e-8) / 127.0                         # (T, hkv, 1)
    return kq, jnp.round(v / v_scale) * v_scale


def _attend(q, k, v, group, hd, pos):
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k, precision=HI) * hd ** -0.5
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v, precision=HI)


def _layer(cfg: dict, act, prompt_len, x, lp):
    t = x.shape[0]
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    b = lp["bits"]
    a = lp["attn"]
    h = _norm(cfg, x, lp["norm1"])
    pos = jnp.arange(t)
    q = act(_rope(_proj(h, a["wq"], b["attn_qkv"], act).reshape(t, hq, hd),
                  pos, cfg["rope_theta"]))
    k = act(_rope(_proj(h, a["wk"], b["attn_qkv"], act).reshape(t, hkv, hd),
                  pos, cfg["rope_theta"]))
    v = _proj(h, a["wv"], b["attn_qkv"], act).reshape(t, hkv, hd)
    group = hq // hkv
    o = _attend(q, k, v, group, hd, pos)
    if cfg["cache_bits"] == 8:
        kq, vq = _int8_kv(k, v, prompt_len)
        o = jnp.where((pos >= prompt_len)[:, None, None],
                      _attend(q, kq, vq, group, hd, pos), o)
    o = act(o.reshape(t, hq * hd))
    x = act(x + _proj(o, a["wo"], b["attn_wo"], act))
    m = lp["mlp"]
    h = _norm(cfg, x, lp["norm2"])
    g = _proj(h, m["gate"], b["mlp_gateup"], act)
    u = _proj(h, m["up"], b["mlp_gateup"], act)
    y = _proj(act(act(jax.nn.silu(g)) * u), m["down"], b["mlp_down"], act)
    return act(x + y), (k, v)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _logits(frozen: tuple, act, n_kv: int, params, bits, tokens, prompt_len):
    cfg = dict(frozen)
    table = _edge(params["embed"], act)
    x = act(table[tokens])
    layers = dict(params["pat"]["p0"], bits=bits)
    step = functools.partial(_layer, cfg, act, prompt_len)
    head = jax.tree.map(lambda a: a[:n_kv], layers)
    x, kv = jax.lax.scan(step, x, head)
    tail = jax.tree.map(lambda a: a[n_kv:], layers)
    x, _ = jax.lax.scan(lambda c, lp: (step(c, lp)[0], None), x, tail)
    x = _norm(cfg, x, params["final_norm"])
    if cfg["tie_word_embeddings"]:
        w, sa = table.T, jnp.float32(cfg["head_act_step"])
    else:
        w, sa = _edge(params["head"], act), params["head"]["sa"]
    return act(jnp.dot(act(fq(x, sa, 8)), w, precision=HI)), kv


CFG_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim", "norm",
            "norm_eps", "rope_theta", "tie_word_embeddings", "head_act_step",
            "cache_bits")


def logits(cfg: dict, params, bits: dict, tokens, prompt_len: int,
           act: Callable, n_kv: int = 0):
    """(T, V) float32 logits of one token sequence whose first
    ``prompt_len`` tokens are the prompt, and the keys (rotated) and
    values of its first ``n_kv`` layers, each (n_kv, T, kv heads, head
    dim).  ``bits``: policy slot -> per-layer bits (length
    num_hidden_layers); ``act``: the rounding of every value held in the
    compute type."""
    eng = cfg["engine"]
    if eng.get("cache") == "quantized" and eng.get("cache_bits") != 8:
        raise ValueError(f"no reference for a {eng.get('cache_bits')}-bit "
                         "KV cache")
    frozen = tuple((k, cfg[k]) for k in CFG_KEYS[:-1]) + (
        ("cache_bits", eng.get("cache_bits") if eng.get("cache") ==
         "quantized" else None),)
    b = {s: jnp.asarray(bits[s], F32) for s in SLOTS}
    return _logits(frozen, act, n_kv, params, b,
                   jnp.asarray(tokens, jnp.int32), jnp.int32(prompt_len))
