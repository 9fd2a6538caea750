"""The serving program's own spans in a profiler trace, beside chip 0's
idle time.

The scheduler marks its host work with ``serve.*`` spans
(``repro/serve/tracing.py``).  They nest inside the harness's ``bench.*``
spans on one thread and share the device's clock.  ``trace.py`` names
each idle gap after the one harness span that overlaps it most; this
module splits the same idle time by the program's spans and changes
nothing ``trace.py`` reads:

  * ``idle_ns``: an exact partition of chip 0's idle time in the window.
    Each idle nanosecond goes to the innermost ``serve.*`` span covering
    it, otherwise to the harness span ``trace.py`` names its gap after.
    The values sum to window - busy.
  * ``counts``, ``meta``: per span name, the spans that start in the
    window and the sums of their integer stats (``tokens``, ``padded``,
    ``live``, ...).
  * ``gaps``: each idle gap, named ``<bench span>/<serve span covering
    most of it>``, or by the harness span alone where no program span
    overlaps it.

``program_idle_share``, ``admit_idle_ms`` and ``prefill_pad_share`` are
the readings of a ``Spans``; each is None when no ``serve.*`` span starts
in the window, as in a trace of a program without the spans.  On a trace
the harness did not write, the window is the stretch from the first
``serve.*`` span to the end of the last.

    python3 -m benchmarks.chip.program_spans <trace dir or .xplane.pb>
"""
from __future__ import annotations

import argparse
import bisect
import dataclasses
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

from benchmarks.chip import trace

PREFIX = "serve."
ADMIT = "serve.admit"


@dataclasses.dataclass
class Spans:
    window_ns: Tuple[float, float]
    idle_ns: Dict[str, float]                 # span name -> idle ns, chip 0
    counts: Dict[str, int]                    # spans starting in the window
    meta: Dict[str, Dict[str, float]]         # their integer stats, summed
    gaps: List[Tuple[str, float]]             # (name, idle ns), chip 0


def _innermost(spans: List[Tuple[str, float, float]]):
    """Disjoint pieces (start, end, name), in order: each instant a span
    covers goes to the innermost span covering it (spans on one thread
    nest)."""
    out, stack, t = [], [], 0.0
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            top, end = stack.pop()
            if end > t:
                out.append((t, end, top))
            t = max(t, end)
        if stack and s > t:
            out.append((t, s, stack[-1][0]))
        stack.append((name, e))
        t = s
    while stack:
        top, end = stack.pop()
        if end > t:
            out.append((t, end, top))
        t = max(t, end)
    return out


def _overlaps(pieces, starts, s: float, e: float) -> Dict[str, float]:
    """ns of [s, e) each span holds innermost."""
    out: Dict[str, float] = {}
    i = bisect.bisect_left(starts, e)
    while i > 0 and pieces[i - 1][1] > s:
        ps, pe, name = pieces[i - 1]
        out[name] = out.get(name, 0.0) + min(e, pe) - max(s, ps)
        i -= 1
    return out


def reduce(path: str) -> Spans:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    bench, serve, chip0 = [], [], None
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(trace.SPAN_PREFIX):
                        bench.append((ev.name, ev.start_ns, ev.end_ns))
                    elif ev.name.startswith(PREFIX):
                        serve.append((ev.name, ev.start_ns, ev.end_ns,
                                      dict(ev.stats)))
        elif chip0 is None and re.fullmatch(r"/device:TPU:\d+", plane.name):
            chip0 = plane
    if chip0 is None:
        raise ValueError(f"{path}: no TPU device plane")
    windows = [(s, e) for n, s, e in bench if n == trace.WINDOW_SPAN]
    if windows:
        lo, hi = windows[0]
    elif serve:
        lo, hi = min(s[1] for s in serve), max(s[2] for s in serve)
    else:
        raise ValueError(f"{path}: no {trace.WINDOW_SPAN} and no "
                         f"{PREFIX}* span")

    leaf = []
    for line in chip0.lines:
        if line.name == "XLA Ops":
            for ev in line.events:
                iv = trace._clip(ev.start_ns, ev.end_ns, lo, hi)
                if iv and trace.op_name(ev.name)[1] not in \
                        trace.CONTAINER_OPS:
                    leaf.append(iv)
    merged = trace._union(leaf)
    host = sorted(((n, s, e) for n, s, e in bench
                   if n != trace.WINDOW_SPAN), key=lambda h: h[1])
    host_starts = [h[1] for h in host]
    pieces = _innermost([(n, s, e) for n, s, e, _ in serve])
    piece_starts = [p[0] for p in pieces]

    idle: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    edges = [lo] + [t for iv in merged for t in iv] + [hi]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        outer = trace._attribute(s, e, host, host_starts)
        inner = _overlaps(pieces, piece_starts, s, e)
        for name, ns in inner.items():
            idle[name] = idle.get(name, 0.0) + ns
        rest = (e - s) - sum(inner.values())
        if rest > 0:
            idle[outer] = idle.get(outer, 0.0) + rest
        gaps.append((f"{outer}/{max(inner, key=inner.get)}" if inner
                     else outer, e - s))

    counts: Dict[str, int] = {}
    meta: Dict[str, Dict[str, float]] = {}
    for name, s, _, stats in serve:
        if lo <= s < hi:
            counts[name] = counts.get(name, 0) + 1
            sums = meta.setdefault(name, {})
            for k, v in stats.items():
                if isinstance(v, (int, float)):
                    sums[k] = sums.get(k, 0) + v
    return Spans(window_ns=(lo, hi), idle_ns=idle, counts=counts, meta=meta,
                 gaps=gaps)


def program_idle_share(sp: Spans) -> Optional[float]:
    """Device-idle time inside ``serve.*`` spans over the window, in %:
    the part of the idle share the program causes, as against the
    harness or a wait for arrivals."""
    if not sp.counts:
        return None
    ns = sum(v for n, v in sp.idle_ns.items() if n.startswith(PREFIX))
    return 100.0 * ns / (sp.window_ns[1] - sp.window_ns[0])


def admit_idle_ms(sp: Spans) -> Optional[float]:
    """Device-idle time inside the ``serve.admit`` subtree, in ms, over
    the ``serve.admit`` spans that start in the window."""
    n = sp.counts.get(ADMIT, 0)
    if not n:
        return None
    ns = sum(v for k, v in sp.idle_ns.items()
             if k == ADMIT or k.startswith(ADMIT + "."))
    return 1e-6 * ns / n


def prefill_pad_share(sp: Spans) -> Optional[float]:
    """Padding in the prefill programs' width, in %: sum(padded - tokens)
    over sum(padded) across the ``serve.admit`` spans in the window."""
    m = sp.meta.get(ADMIT, {})
    padded = m.get("padded", 0)
    if padded <= 0:
        return None
    return 100.0 * (padded - m.get("tokens", 0)) / padded


def idle_by_span(sp: Spans) -> Dict[str, float]:
    """Idle seconds by span name, largest first."""
    return {n: v * 1e-9 for n, v in sorted(sp.idle_ns.items(),
                                           key=lambda kv: -kv[1])}


def longest_gaps(sp: Spans, top: int = 10) -> List[list]:
    return [[n, v * 1e-9] for n, v in sorted(sp.gaps,
                                             key=lambda g: -g[1])[:top]]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help="an .xplane.pb file, or a directory "
                    "holding one (the newest is read)")
    args = ap.parse_args(argv)
    path = args.trace if os.path.isfile(args.trace) \
        else trace.find_xplane(args.trace)
    sp = reduce(path)
    print(json.dumps({
        "window_s": (sp.window_ns[1] - sp.window_ns[0]) * 1e-9,
        "idle_by_span_s": idle_by_span(sp),
        "program_idle_share": program_idle_share(sp),
        "admit_idle_ms": admit_idle_ms(sp),
        "prefill_pad_share": prefill_pad_share(sp),
        "counts": sp.counts, "meta": sp.meta,
        "longest_gaps_s": longest_gaps(sp)}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
