"""Recursive jaxpr walkers — the primitive layer under contracts.py.

Every walker recurses into scan/cond/remat/pjit/shard_map subjaxprs, so a
property holds for the WHOLE traced program, not just its top level (the
decode step hides almost everything inside a ``lax.scan`` body; a sharded
dispatch hides the body under a shard_map/pjit call).  ``count_eqns`` is
the same recursion BENCH_compile gates on — benchmarks/compile_bench.py
imports it from here so the bench and the static gate cannot drift.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Iterator, List, Tuple

import numpy as np

FLOAT_DTYPES = ("float64", "float32", "float16", "bfloat16")


def _subjaxprs(v) -> Iterator[Any]:
    """Yield every (open) Jaxpr reachable from one eqn-param value."""
    if hasattr(v, "jaxpr"):                   # ClosedJaxpr
        yield v.jaxpr
    elif hasattr(v, "eqns"):                  # Jaxpr
        yield v
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _subjaxprs(x)


def iter_eqns(jaxpr, opaque: Tuple[str, ...] = ()) -> Iterator[Any]:
    """Depth-first over every equation, including nested subjaxprs —
    except the bodies of primitives named in ``opaque``.

    Accepts a ClosedJaxpr or an open Jaxpr.
    """
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name in opaque:
            continue
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from iter_eqns(sub, opaque)


def count_eqns(jaxpr) -> int:
    """Total equations including scan/cond/remat/pjit subjaxprs."""
    return sum(1 for _ in iter_eqns(jaxpr))


def count_primitive(jaxpr, name: str) -> int:
    """Occurrences of primitive ``name`` (e.g. "psum") anywhere in the
    program.  Static count: a psum inside a scan body counts ONCE — the
    contract is about program structure, not executed collectives."""
    return sum(1 for eqn in iter_eqns(jaxpr) if eqn.primitive.name == name)


@dataclasses.dataclass(frozen=True)
class ArrayRecord:
    """One flagged array (a baked const or an oversized intermediate)."""
    kind: str                    # "const" | "intermediate"
    shape: Tuple[int, ...]
    dtype: str
    size: int                    # element count
    primitive: str = ""          # producing eqn (intermediates only)

    def describe(self) -> str:
        where = f" <- {self.primitive}" if self.primitive else ""
        return f"{self.kind} {self.dtype}{list(self.shape)} " \
               f"({self.size} elems){where}"


def _closed_consts(closed) -> Iterator[Any]:
    """Every trace-time constant: the top-level ClosedJaxpr's consts plus
    the consts of any nested ClosedJaxpr (pjit/closed_call bodies carry
    their own)."""
    yield from getattr(closed, "consts", ())
    jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    for eqn in iter_eqns(jaxpr):
        for v in eqn.params.values():
            if hasattr(v, "consts"):
                yield from v.consts
            elif isinstance(v, (tuple, list)):
                for x in v:
                    if hasattr(x, "consts"):
                        yield from x.consts


def find_baked_consts(closed, min_elems: int = 2048) -> List[ArrayRecord]:
    """Array constants baked into the trace above ``min_elems`` elements.

    Serving jaxprs must take params/caches as ARGUMENTS — a closure that
    captured them at trace time bakes them as consts, which pins one
    checkpoint into the compiled program and bloats every executable (the
    PR 4 bug class).  Small consts (masks, iota tables, norm epsilons)
    are legitimate; the threshold separates them from anything
    params-sized.
    """
    out = []
    for c in _closed_consts(closed):
        arr = np.asarray(c) if not hasattr(c, "size") else c
        size = int(arr.size)
        if size >= min_elems:
            out.append(ArrayRecord("const", tuple(arr.shape),
                                   str(arr.dtype), size))
    return out


def find_float_intermediates(closed, min_elems: int,
                             require_axis: int = 0) -> List[ArrayRecord]:
    """Full-precision intermediates with >= ``min_elems`` elements (and,
    when ``require_axis`` > 0, at least one dimension of exactly that
    extent).

    The quantized-cache decode contract: codes dequantize in-register
    (Pallas) — the program must never materialize a cache-sized
    full-dtype tensor (the PR 1/PR 3 bug class; ``min_elems`` is the
    element count of one full (B, S_max, Hkv, D) cache buffer and
    ``require_axis`` is S_max, so weight-sized dequants — int8 packed
    weights legitimately dequantize as one [K, N] per dispatch — don't
    alias into the cache check).  Only eqn OUTPUTS count: cache buffers
    legitimately enter full-sized as int8/int4 code arguments, and
    staging buffers enter as full-dtype arguments on the chunked-prefill
    path.  A Pallas kernel body is not entered: its values are one VMEM
    tile at a time, which is what in-register dequant means.
    """
    out = []
    for eqn in iter_eqns(closed, opaque=("pallas_call",)):
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            if aval is None or not hasattr(aval, "shape"):
                continue
            dtype = str(getattr(aval, "dtype", ""))
            if dtype not in FLOAT_DTYPES:
                continue
            shape = tuple(int(d) for d in aval.shape)
            size = int(np.prod(shape)) if shape else 1
            if size < min_elems:
                continue
            if require_axis and require_axis not in shape:
                continue
            out.append(ArrayRecord("intermediate", shape, dtype, size,
                                   eqn.primitive.name))
    return out
