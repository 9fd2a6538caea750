"""Reduce a profiler trace (``.xplane.pb``) to the numbers the metrics read.

What a TPU trace holds (as seen on a v5e under jax 0.9):

  * one plane per chip, ``/device:TPU:<n>``, with the lines
    ``XLA Modules`` (one event per program run, named
    ``jit__decode_impl(<fingerprint>)``) and ``XLA Ops`` (one event per HLO
    op run, named by the op's HLO text: ``%quant_matmul.312 = f32[32,8192]
    custom-call(...)``; a ``while`` op spans the ops of its body).  An
    ``Async XLA Ops`` line holds DMA starts whose spans overlap compute;
    it is not read.
  * the host plane ``/host:CPU``, whose threads carry the harness's own
    spans (``jax.profiler.TraceAnnotation``), all named ``bench.<what>``.

Device and host events share one clock (ns from the trace's start).  The
traced window is the harness's ``bench.window`` span.  Busy time is the
union of the intervals of leaf ops (containers such as ``while`` excluded)
inside the window; it, each program's and each op's time are averaged over
the chips, so that on a mesh they are one chip's time, as ``costs.py``
counts one chip's work.  Every idle gap of chip 0 is attributed to the
harness span (other than the window) that overlaps it most.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
CONTAINER_OPS = ("while", "conditional", "call")
_SUFFIX_RE = re.compile(r"\.\d+$")


@dataclasses.dataclass
class Trace:
    window_ns: Tuple[float, float]
    busy_ns: float                            # mean over chips
    program_ns: Dict[str, float]              # per program, mean over chips
    program_runs: Dict[str, int]              # chip 0's
    op_ns: Dict[str, float]                   # leaf ops by short name, mean
    gaps: List[Tuple[str, float]]             # (host span, idle ns), chip 0
    n_chips: int

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    def kernel_s(self, name: str) -> float:
        return self.op_ns.get(name, 0.0) * 1e-9

    def program_s(self, name: str) -> float:
        return self.program_ns.get(name, 0.0) * 1e-9


def op_name(hlo_text: str) -> Tuple[str, str]:
    """(short name, opcode) of an ``XLA Ops`` event: ``%quant_matmul.312 =
    f32[..] custom-call(..)`` -> ("quant_matmul", "custom-call")."""
    lhs, _, rhs = hlo_text.partition(" = ")
    name = _SUFFIX_RE.sub("", lhs.strip().lstrip("%"))
    if rhs.startswith("("):                     # tuple type: skip to its end
        depth = 0
        for i, ch in enumerate(rhs):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                rhs = rhs[i + 1:]
                break
    else:
        rhs = rhs.partition(" ")[2]
    return name, rhs.strip().partition("(")[0]


def program_name(event_name: str) -> str:
    """``jit__decode_impl(1113...)`` -> ``jit__decode_impl``."""
    return event_name.split("(", 1)[0]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: float, e: float, lo: float, hi: float):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def reduce(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans: List[Tuple[str, float, float]] = []
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
        elif re.fullmatch(r"/device:TPU:\d+", plane.name):
            devices.append(plane)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span")
    lo, hi = windows[0]
    if not devices:
        raise ValueError(f"{path}: no TPU device plane")

    busy, gaps = [], []
    program_ns: Dict[str, float] = {}
    program_runs: Dict[str, int] = {}
    op_ns: Dict[str, float] = {}
    host = sorted(((n, s, e) for n, s, e in spans if n != WINDOW_SPAN),
                  key=lambda h: h[1])
    starts = [h[1] for h in host]
    for i, plane in enumerate(devices):
        leaf = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    iv = _clip(ev.start_ns, ev.end_ns, lo, hi)
                    if iv:
                        name = program_name(ev.name)
                        program_ns[name] = (program_ns.get(name, 0.0)
                                            + iv[1] - iv[0])
                        if i == 0:
                            program_runs[name] = program_runs.get(name, 0) + 1
            elif line.name == "XLA Ops":
                for ev in line.events:
                    iv = _clip(ev.start_ns, ev.end_ns, lo, hi)
                    if not iv:
                        continue
                    name, opcode = op_name(ev.name)
                    if opcode in CONTAINER_OPS:
                        continue
                    op_ns[name] = op_ns.get(name, 0.0) + iv[1] - iv[0]
                    leaf.append(iv)
        merged = _union(leaf)
        busy.append(sum(e - s for s, e in merged))
        if i == 0:
            edges = [lo] + [t for iv in merged for t in iv] + [hi]
            for s, e in zip(edges[0::2], edges[1::2]):
                if e > s:
                    gaps.append((_attribute(s, e, host, starts), e - s))
    n = len(devices)
    return Trace(window_ns=(lo, hi), busy_ns=sum(busy) / n,
                 program_ns={k: v / n for k, v in program_ns.items()},
                 program_runs=program_runs,
                 op_ns={k: v / n for k, v in op_ns.items()}, gaps=gaps,
                 n_chips=n)


def _attribute(s: float, e: float, host, starts) -> str:
    """The host span that overlaps [s, e) most.  The harness's spans do not
    nest (other than the window), so the candidates are the few that start
    just before ``e``."""
    best, name = 0.0, "bench.none"
    i = bisect.bisect_left(starts, e)
    for n, hs, he in reversed(host[max(0, i - 16):i]):
        ov = min(e, he) - max(s, hs)
        if ov > best:
            best, name = ov, n
    return name


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The driver's ``breakdown``: the device ops that took the most time,
    and the longest idle gaps named by the host span they fell in."""
    ops = sorted(tr.op_ns.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(tr.gaps, key=lambda g: -g[1])[:top]
    return {"device_ops": [[n, v * 1e-9] for n, v in ops],
            "idle_gaps": [[n, v * 1e-9] for n, v in gaps]}


def idle_by_span(tr: Trace) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for n, v in tr.gaps:
        out[n] = out.get(n, 0.0) + v * 1e-9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))
