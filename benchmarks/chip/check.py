"""The comparison that decides ``correct``.

Before the window a sample of requests is drawn from the seed among those
the window will finish -- the longest answer, then others until
``check_tokens`` answer tokens are covered -- and when each finishes, its
slot's int8 cache rows in the first ``check.kv_layers`` layers are kept.
Once the window has closed and the program's state is freed, each sampled
request goes through the configuration's plain reference, computed in the
type the configuration states and teacher-forced on the prompt and the
served tokens; a reference that reads its weights a layer at a time
(``logits_by_layer``, for expert layers) takes every sampled request
through a layer before it makes the next.  Read:

* the cache rows the timed path wrote, prompt rows (the prefill) and
  decode rows (one written by every decode step), against the reference's
  keys and values put through the same int8 rule: a row's rms distance
  relative to the reference's rms, averaged over the rows of each distinct
  token of a request, and the mean over all those tokens of the sample
  (``*_kv_token_mean``; the worst of the layers compared).  Over distinct
  tokens: a token whose first quantizer the program's rounding tipped the
  other way (a few in a hundred, about 0.04 each, ``PERF.md``) counts
  once, however often its request repeats it; a rounding of every row
  moves the mean by its own size, and rows written from other tokens, at
  other positions or not at all (over 1 each) move it from a tenth of the
  tokens on;
* each served token's gap below the reference's best logit at its
  position, and the rms distance of the program's prefill logits, both in
  units of the reference logits' standard deviation there.

The control puts the same reference, computed one type lower, in the
program's place: its cache rows, its first choice at each position and its
prefill logits, read the same way.
"""
from __future__ import annotations

import importlib
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import traffic

Sample = Tuple[List[int], List[int], Any, Any]  # prompt, served tokens,
#                                                prefill logits, cache rows


def candidates(reqs: Sequence[traffic.Request], seed: int, check_tokens: int,
               seconds: float, backlog: bool, n_slots: int) -> List[str]:
    """The requests to check, drawn before the window so that their cache
    rows can be kept when they finish: of those the window will finish --
    every request due in it (open loop), the first ``n_slots`` (a backlog:
    admitted at the open) -- the longest answer, then others in a seeded
    order until ``check_tokens`` answer tokens are covered."""
    pool = (list(reqs[:n_slots]) if backlog
            else [r for r in reqs if r.due_s <= seconds])
    if not pool:
        return []
    longest = max(pool, key=lambda r: (r.n_out, r.uid))
    rest = sorted((r for r in pool if r is not longest), key=lambda r: r.uid)
    order = traffic.rng_for(seed, 2).permutation(len(rest))
    out, n = [longest.uid], longest.n_out
    for i in order:
        if n >= check_tokens:
            break
        out.append(rest[i].uid)
        n += rest[i].n_out
    return out


def reference_module(name: str):
    return importlib.import_module(f"benchmarks.chip.references.{name}")


@jax.jit
def _read(ref, pos, tok, other, got_first):
    """At positions ``pos`` of the reference logits ``ref``: the gap of
    ``tok`` below the best and the top-1 margin, in std units; the rms
    distance of the program's prefill logits ``got_first`` from the row
    at ``pos[0]``; and, given ``other`` (the control's logits), the gap of
    its first choice and its own rms distance at ``pos[0]``."""
    lg = ref[pos]
    best = lg.max(-1)
    std = lg.std(-1)
    out = {"served": (best - jnp.take_along_axis(lg, tok[:, None], -1)[:, 0])
           / std,
           "margin": (best - jnp.sort(lg, -1)[:, -2]) / std}
    if got_first is not None:
        out["prefill"] = _rms(got_first, lg[0], std[0])
    if other is not None:
        pick_ = other[pos].argmax(-1)
        out["ctrl_served"] = (best - jnp.take_along_axis(
            lg, pick_[:, None], -1)[:, 0]) / std
        out["ctrl_prefill"] = _rms(other[pos[0]], lg[0], std[0])
    return out


def _rms(got, want, std):
    d = got.astype(jnp.float32).reshape(want.shape) - want
    return jnp.sqrt(jnp.mean(d * d)) / std


# Rounding goes through ``reduce_precision``: the compiler may drop a cast
# to a narrower type and straight back (it allows excess precision), but
# never this.

def fp8(x):
    """Round to float8 e4m3fn -- 3 mantissa bits, subnormal steps of 2^-9
    below 2^-6, saturating at 448: one step below bf16."""
    sub = jnp.round(x * 512.0) / 512.0
    y = jnp.where(jnp.abs(x) < 2.0 ** -6, sub,
                  jax.lax.reduce_precision(x, 5, 3))
    return jnp.clip(y, -448.0, 448.0)


def bf16(x):
    """Round to bfloat16 (8 exponent, 7 mantissa bits): one step below
    float32."""
    return jax.lax.reduce_precision(x, 8, 7)


def f32(x):
    return x


def rounding_for(cfg: dict) -> Callable:
    """The reference's rounding: the type the configuration computes in."""
    return {"bfloat16": bf16, "float32": f32}[cfg["dtype"]]


def control_for(cfg: dict) -> Callable:
    """The control's rounding: the nearest type below the one the
    configuration computes in."""
    return {"bfloat16": fp8, "float32": bf16}[cfg["dtype"]]


def _int8_rows(k, v, prompt_len: int):
    """K and V as the int8 cache holds them (``references``' rule)."""
    rows = np.arange(k.shape[1])[None, :, None, None] < prompt_len
    k_scale = np.maximum(np.abs(np.where(rows, k, 0.0)).max(1, keepdims=True)
                         * 1.5, 1e-8) / 127.0
    kq = np.clip(np.round(k / k_scale), -127.0, 127.0) * k_scale
    v_scale = np.maximum(np.abs(v).max(-1, keepdims=True), 1e-8) / 127.0
    return kq, np.round(v / v_scale) * v_scale


def held_rows(snap: dict):
    """A cache snapshot (``serving.slot_rows``) dequantized: K, V each
    (layers, S, kv heads, head dim)."""
    k = (np.asarray(snap["kq"], np.float64)
         * np.asarray(snap["k_scale"], np.float64)[:, None])
    v = (np.asarray(snap["vq"], np.float64)
         * np.asarray(snap["v_scale"], np.float64)[..., None])
    return k, v


def _token_gaps(got, want, lo: int, hi: int, toks) -> np.ndarray:
    """Rows [lo, hi), one per token fed there: per layer, each row's rms
    distance (the larger of K's and V's), relative to the reference's rms
    over the rows, averaged over the rows of each distinct token.
    Returns (distinct tokens, layers)."""
    errs = None
    for g, w in zip(got, want):
        d = (g[:, lo:hi] - w[:, lo:hi]).reshape(g.shape[0], hi - lo, -1)
        ref = np.sqrt((w[:, lo:hi] ** 2).reshape(w.shape[0], -1).mean(1))
        rel = np.sqrt((d * d).mean(2)) / np.maximum(ref, 1e-30)[:, None]
        errs = rel if errs is None else np.maximum(errs, rel)
    toks = np.asarray(toks)
    return np.stack([errs[:, toks == t].mean(1) for t in np.unique(toks)])


def _reference_runs(ref_mod, cfg: dict, params, bits, feds, acts, n_kv):
    """Per sample, for each rounding in ``acts``, the reference's logits and
    first-layer keys and values: a sample at a time over the whole tree,
    or, where the reference reads its weights a layer at a time
    (``logits_by_layer``), every sample through a layer before the next."""
    if hasattr(ref_mod, "logits_by_layer"):
        yield from ref_mod.logits_by_layer(cfg, params, bits, feds, acts,
                                           n_kv)
        return
    width = cfg["max_seq"]
    for fed, p_len in feds:
        seq = np.zeros(width, np.int32)
        seq[:len(fed)] = fed
        yield [ref_mod.logits(cfg, params, bits, seq, p_len, act, n_kv)
               for act in acts]


def readings(cfg: dict, params, bits: Dict[str, list],
             samples: Sequence[Sample], control: Optional[Callable] = None,
             ) -> Dict[str, np.ndarray]:
    """Per served token its gap (``served``) and the reference's top-1
    margin (``margin``); per request the prefill logits' rms distance
    (``prefill``); per distinct token of each request, the distance of the
    int8 cache rows it left in the first ``check.kv_layers`` layers, prompt
    rows (``prefill_kv``) and decode rows (``decode_kv``).  With
    ``control``, the same readings of the control in the program's place
    (``ctrl_``...).  ``params``: the weights as the reference reads them
    (``weights.for_reference``)."""
    ref_mod = reference_module(cfg["reference"])
    n_kv = int(cfg["check"].get("kv_layers", 0))
    acts = [rounding_for(cfg)] + ([control] if control is not None else [])
    feds = [(list(prompt) + list(toks[:-1]), len(prompt))
            for prompt, toks, _, _ in samples]
    acc: Dict[str, list] = {}

    def add(key, value):
        value = np.asarray(value, np.float64)
        acc.setdefault(key, []).append(value if value.ndim else value[None])

    runs = _reference_runs(ref_mod, cfg, params, bits, feds, acts, n_kv)
    for (prompt, toks, first_logits, snap), got_runs in zip(samples, runs):
        (ref, ref_kv), (other, other_kv) = (
            got_runs if control is not None else (got_runs[0], (None, None)))
        p_len, n = len(prompt), len(toks)
        rows = ref.shape[0]
        pos = np.minimum(p_len - 1 + np.arange(rows), rows - 1)
        tok = np.zeros(rows, np.int32)
        tok[:n] = toks
        got = _read(ref, jnp.asarray(pos, jnp.int32), jnp.asarray(tok),
                    other, first_logits)
        for k, v in got.items():
            v = np.asarray(v, np.float64)
            add(k, v[:n] if v.ndim else v)
        if n_kv and snap is not None:
            rows = {"": held_rows(snap)}
            if other_kv is not None:
                rows["ctrl_"] = _int8_rows(*(np.asarray(a, np.float64)
                                             for a in other_kv), p_len)
            want = _int8_rows(*(np.asarray(a, np.float64) for a in ref_kv),
                              p_len)
            for who, got_rows in rows.items():
                add(who + "prefill_kv",
                    _token_gaps(got_rows, want, 0, p_len, prompt))
                if n > 1:                 # a decode step wrote a row
                    add(who + "decode_kv",
                        _token_gaps(got_rows, want, p_len, p_len + n - 1,
                                    toks[:-1]))
        del got_runs, ref, other, ref_kv, other_kv
    return {k: np.concatenate(v) for k, v in acc.items()}


def summarize(r: Dict[str, np.ndarray], prefix: str = "") -> Dict[str, float]:
    """The numbers a run is judged by (see ``PERF.md`` for which carry a
    limit and why)."""
    out = {}
    served, prefill = r.get(prefix + "served"), r.get(prefix + "prefill")
    if served is not None and served.size:
        out["served_gap_max"] = float(served.max())
        out["served_gap_mean"] = float(served.mean())
    if prefill is not None and prefill.size:
        out["prefill_rms_max"] = float(prefill.max())
        out["prefill_rms_mean"] = float(prefill.mean())
    for name in ("prefill_kv", "decode_kv"):
        got = r.get(prefix + name)       # (distinct tokens, layers)
        if got is not None and got.size:
            out[name + "_token_mean"] = float(got.mean(0).max())
    return out


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, List[dict]]:
    """Every number with a limit must not exceed it."""
    rows, ok = [], True
    for name, value in numbers.items():
        limit = limits.get(name)
        row = {"name": name, "value": value, "limit": limit}
        if limit is not None and not value <= limit:
            ok = False
        rows.append(row)
    return ok, rows
