"""The one traffic generator: a mix file's parameters + a seed -> requests.

A mix is a JSON file under ``traffic/`` (see ``traffic/chat.json``):

  * ``arrival``: ``{"kind": "gamma", "rate_per_s": r, "shape": k}`` for an
    open loop whose gaps between arrivals are gamma-distributed with mean
    ``1/r`` (shape 1 is Poisson; shape < 1 is burstier), or
    ``{"kind": "backlog", "n_requests": n}`` for a batch job whose
    requests are all due at the window's start.
  * ``prompt`` / ``output``: a length distribution,
    ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``
    or ``{"dist": "uniform", "min": a, "max": b}`` (both ends inclusive).

Every seed gets the same multiset of prompt lengths, output lengths and
gaps, in another order: each is the set of evenly spaced quantiles of its
distribution, shuffled by the seed.  Runs with different seeds then do the
same amount of work, and differ only in which request comes when and in
the prompts' token ids.  Outputs run to their length (no EOS).
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    uid: str
    prompt: List[int]
    n_out: int
    due_s: float            # seconds after the window opens


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent NumPy generator per (seed, stream); any integer seed."""
    return np.random.default_rng([seed % 2**64, stream])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of a length distribution, as ints."""
    lo, hi = int(spec["min"]), int(spec["max"])
    u = _quantiles(n)
    if spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(p) for p in u])
        x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    elif spec["dist"] == "uniform":
        x = lo + u * (hi - lo + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def gaps(arrival: dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of the gamma gap distribution (s)."""
    from scipy.stats import gamma
    k = float(arrival["shape"])
    mean = 1.0 / float(arrival["rate_per_s"])
    return gamma.ppf(_quantiles(n), k, scale=mean / k)


def n_requests(mix: dict, seconds: float) -> int:
    arrival = mix["arrival"]
    if arrival["kind"] == "backlog":
        return int(arrival["n_requests"])
    if arrival["kind"] == "gamma":
        return max(1, int(round(float(arrival["rate_per_s"]) * seconds)))
    raise ValueError(f"unknown arrival kind {arrival['kind']!r}")


def generate(mix: dict, seed: int, seconds: float, vocab: int,
             ) -> List[Request]:
    """The mix's requests for one window of ``seconds``, sorted by due
    time.  The same (mix, seed, seconds, vocab) gives the same requests."""
    n = n_requests(mix, seconds)
    rng = rng_for(seed, 0)
    p_len = rng.permutation(lengths(mix["prompt"], n))
    o_len = rng.permutation(lengths(mix["output"], n))
    if mix["arrival"]["kind"] == "backlog":
        due = np.zeros(n)
    else:
        # the first request is due one gap after the window opens; the
        # gaps' sum, hence the last due time, is the same for every seed
        due = np.cumsum(rng.permutation(gaps(mix["arrival"], n)))
    tok_rng = rng_for(seed, 1)
    return [Request(uid=f"r{i:05d}",
                    prompt=tok_rng.integers(0, vocab, int(p)).tolist(),
                    n_out=int(o), due_s=float(d))
            for i, (p, o, d) in enumerate(zip(p_len, o_len, due))]


def prompt_widths(mix: dict, bucket: int, max_seq: int) -> List[int]:
    """Every padded prefill width the mix can produce (the scheduler pads a
    prompt up to a multiple of ``bucket``, capped at ``max_seq``)."""
    lo, hi = int(mix["prompt"]["min"]), int(mix["prompt"]["max"])
    first = math.ceil(lo / bucket) * bucket
    last = math.ceil(hi / bucket) * bucket
    return sorted({min(w, max_seq) for w in range(first, last + 1, bucket)})


def page_writes(mix: dict, bucket: int, max_seq: int, page: int,
                ) -> List[Tuple[int, int]]:
    """(prompt, answer) lengths, one pair for each number of pages a paged
    admission can write for this mix.  The scheduler claims the pages of
    prompt + answer and writes those of the padded prompt that it claimed,
    so the count is min(pages of the padded width, pages of prompt +
    answer); each count is a shape of its own.  Each pair has the shortest
    answer that gives its count."""
    p_min, p_max = int(mix["prompt"]["min"]), int(mix["prompt"]["max"])
    o_min, o_max = int(mix["output"]["min"]), int(mix["output"]["max"])
    by_width: dict = {}
    for n in range(p_min, p_max + 1):
        w = min(math.ceil(n / bucket) * bucket, max_seq)
        lo, hi = by_width.get(w, (n, n))
        by_width[w] = (min(lo, n), max(hi, n))
    out, seen = [], set()
    for w, (lo, hi) in sorted(by_width.items()):
        cap = math.ceil(w / page)
        for k in range(math.ceil((lo + o_min) / page),
                       math.ceil((hi + o_max) / page) + 1):
            if (w, min(k, cap)) in seen:
                continue
            seen.add((w, min(k, cap)))
            total = max((k - 1) * page + 1, lo + o_min)
            n = min(hi, total - o_min)
            out.append((n, total - n))
    return out
