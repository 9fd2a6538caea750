"""Operations and bytes of each kernel call and each decode step, counted
from the model's shapes and the traffic served -- never from a kernel's
grid, its padding or its HLO, so a change to a kernel cannot change what
its work is said to be.

On a mesh of ``shards`` chips (a configuration's ``"mesh": {"model": n}``)
everything is one chip's share, as chip 0 runs it: a column projection
(q, k, v, gate, up) has N / n output channels and a row projection (o,
down) K / n input channels, each with its whole input or output; attention
covers hq / n query and hkv / n KV heads; the replicated LM head counts in
full.  Against one chip's time and peak, the shares give one chip's
roofline and MFU.  With ``shards`` 1 it is the whole model.

Bytes are the least the algorithm must move: packed weight codes at the
policy's bits plus one f32 scale per output channel; the live K/V codes
and scales of each active slot; each input and output once, in bf16.
A kernel that reads more than that (padding, a whole allocated cache)
reads low against its roofline.

A served round, as the harness records it:

  * prefill: ``{"kind": "prefill", "tokens": n}`` -- one request's real
    prompt tokens (padding is not work);
  * decode: ``{"kind": "decode", "steps": s, "rows": [[ctx0, n], ...]}``
    -- the scan length the program ran and, for each slot that delivered
    tokens, the cache length before the round and the tokens it delivered;
    step ``i`` of such a row processes the token at position ``ctx0 + i``
    and attends over ``ctx0 + i + 1`` rows.  Steps past the longest row's
    ``n`` are not work.

An expert layer (``num_local_experts`` E held, ``num_experts_per_tok``
k, expert width f) has q, k, v and o as a dense layer, the router (d, E)
at its 8 bits, and three expert projections (gate, up: d -> f; down:
f -> d).  In a step of M tokens the experts' work is

  * FLOPs: 2 M k 3 d f -- each token through its k experts;
  * bytes: the codes (and per-channel scales) of the experts that the
    M k draws reach, E (1 - (1 - k/E)^M) of them in expectation, each at
    its own bits, plus each routed row's input and output once in bf16.

Each token picks k distinct experts of E; where routing spreads tokens
evenly, one token misses a given expert with probability 1 - k/E and all
M miss it with (1 - k/E)^M, and an expert no token reached is no work.
The program computes every held expert over a buffer of capacity rows;
what it does past the tokens routed reads low against the roofline.
With no expert layers every count is the dense one.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Dict, Iterable, List, Tuple

BF16 = 2
F32 = 4


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    layers: int
    hq: int
    hkv: int
    hd: int
    f: int
    vocab: int
    cache_bits: int
    shards: int = 1             # chips of the "model" mesh axis
    experts: int = 0            # experts held in each layer; 0: dense MLP
    top_k: int = 0
    f_expert: int = 0

    @classmethod
    def of(cls, cfg: dict) -> "Dims":
        return cls(d=cfg["hidden_size"], layers=cfg["num_hidden_layers"],
                   hq=cfg["num_attention_heads"],
                   hkv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
                   f=cfg.get("intermediate_size", 0),
                   vocab=cfg["vocab_size"],
                   cache_bits=cfg["engine"]["cache_bits"],
                   shards=int(cfg.get("mesh", {}).get("model", 1)),
                   experts=int(cfg.get("num_local_experts", 0)),
                   top_k=int(cfg.get("num_experts_per_tok", 0)),
                   f_expert=int(cfg.get("moe_intermediate_size", 0)))

    @property
    def hq_chip(self) -> int:
        return self.hq // self.shards

    @property
    def hkv_chip(self) -> int:
        return self.hkv // self.shards

    def projections(self) -> List[Tuple[str, int, int]]:
        """(policy slot, K, N) of each packed projection of one layer, one
        chip's share."""
        q, kv = self.hq_chip * self.hd, self.hkv_chip * self.hd
        attn = [("attn_qkv", self.d, q), ("attn_qkv", self.d, kv),
                ("attn_qkv", self.d, kv), ("attn_wo", q, self.d)]
        if self.experts:            # the expert banks: expert_matmuls
            return attn + [("moe_router", self.d, self.experts)]
        f = self.f // self.shards
        return attn + [("mlp_gateup", self.d, f), ("mlp_gateup", self.d, f),
                       ("mlp_down", f, self.d)]

    @property
    def proj_params(self) -> int:
        """Weights of the projections of all layers that every token
        passes through (with expert layers, the k experts' too), one chip's
        share."""
        every = self.layers * sum(k * n for _, k, n in self.projections())
        return every + self.layers * self.top_k * 3 * self.d * self.f_expert


def quant_matmul(m: int, k: int, n: int, bits: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of x (m, k) @ packed W (k, n) at ``bits``."""
    flops = 2.0 * m * k * n
    data = k * n * bits / 8 + n * F32 + m * k * BF16 + m * n * BF16
    return flops, data


def expert_matmuls(m: int, dims: Dims, gateup_bits: List[int],
                   down_bits: List[int]) -> List[Tuple[float, float]]:
    """(FLOPs, bytes) of one expert layer's gate, up and down projections
    for ``m`` tokens, with each expert's bits (the module's rule)."""
    d, f, k = dims.d, dims.f_expert, dims.top_k
    reach = 1.0 - (1.0 - k / dims.experts) ** m
    rows = m * k

    def one(k_in, n_out, bits):
        codes = sum(k_in * n_out * b / 8 + n_out * F32 for b in bits)
        return (2.0 * rows * k_in * n_out,
                reach * codes + rows * (k_in + n_out) * BF16)

    return [one(d, f, gateup_bits), one(d, f, gateup_bits),
            one(f, d, down_bits)]


def decode_attention(ctxs: Iterable[int], dims: Dims) -> Tuple[float, float]:
    """(FLOPs, bytes) of one layer's decode attention for one step: one
    query per active slot over its ``ctx`` live cache rows (int codes, an
    f32 scale per V row, an f32 K scale per channel), one chip's heads."""
    flops = data = 0.0
    code = dims.cache_bits / 8
    hq, hkv = dims.hq_chip, dims.hkv_chip
    for ctx in ctxs:
        flops += 4.0 * hq * dims.hd * ctx
        data += (2 * ctx * hkv * dims.hd * code + ctx * hkv * F32
                 + hkv * dims.hd * F32 + 2 * hq * dims.hd * BF16)
    return flops, data


def token_flops(dims: Dims, ctx: int) -> float:
    """Model FLOPs of one decoded token attending ``ctx`` rows: the
    projections (k experts of an expert layer), the LM head and
    attention's two products (one chip's share; the replicated head in
    full)."""
    return (2.0 * dims.proj_params + 2.0 * dims.d * dims.vocab
            + 4.0 * dims.layers * dims.hq_chip * dims.hd * ctx)


def least_time(flops: float, data: float, peak_flops: float,
               peak_bytes: float) -> float:
    return max(flops / peak_flops, data / peak_bytes)


@dataclasses.dataclass
class Totals:
    qmm_least_s: float = 0.0
    attn_least_s: float = 0.0
    decode_flops: float = 0.0
    decode_tokens: int = 0
    decode_steps: int = 0           # scan steps the decode program ran
    prefill_tokens: int = 0


def totals(rounds: Iterable[dict], dims: Dims,
           bits: Dict[str, List[int]], peak_flops: float,
           peak_bytes: float) -> Totals:
    """Least kernel times and model FLOPs of the served rounds.  ``bits``:
    policy slot -> bits per layer (per layer, a list over the experts of
    an expert slot)."""
    layers_at = {slot: collections.Counter(per_layer)
                 for slot, per_layer in bits.items()
                 if not slot.startswith("moe_") or slot == "moe_router"}

    def qmm_least(m: int) -> float:
        t = 0.0
        for slot, k, n in dims.projections():
            for b, count in layers_at[slot].items():
                t += count * least_time(*quant_matmul(m, k, n, b),
                                        peak_flops, peak_bytes)
        if dims.experts:
            for gateup, down in zip(bits["moe_gateup"], bits["moe_down"]):
                for work in expert_matmuls(m, dims, gateup, down):
                    t += least_time(*work, peak_flops, peak_bytes)
        return t

    out = Totals()
    for r in rounds:
        if r["kind"] == "prefill":
            out.prefill_tokens += r["tokens"]
            out.qmm_least_s += qmm_least(r["tokens"])
            continue
        out.decode_steps += r["steps"]
        steps = max((n for _, n in r["rows"]), default=0)
        for i in range(steps):
            ctxs = [c0 + i + 1 for c0, n in r["rows"] if n > i]
            out.qmm_least_s += qmm_least(len(ctxs))
            out.attn_least_s += dims.layers * least_time(
                *decode_attention(ctxs, dims), peak_flops, peak_bytes)
            out.decode_flops += sum(token_flops(dims, c) for c in ctxs)
            out.decode_tokens += len(ctxs)
    return out
