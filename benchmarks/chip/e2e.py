"""End-to-end metrics from the harness's per-request timestamps.

All times are host-clock seconds on one axis; ``due`` is when the
request was due to be sent (open loop: the clock a user started).  A
request is ``attempted`` when it was due inside the window -- for a
backlog mix, when it was admitted before the window closed -- and
``failed`` when it did not deliver every token it asked for by the end of
the drain (a backlog is not drained: a request still in its slot at the
close is ``cut``, not failed).  A failed request counts against the
stream-rate tail with the time it had waited when the drain ended.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, List, Optional, Sequence


@dataclasses.dataclass
class Served:
    uid: str
    due: float                         # absolute host time
    n_out: int                         # tokens asked for
    prompt_len: int
    noticed: Optional[float] = None    # harness saw it due
    admit_start: Optional[float] = None
    t_first: Optional[float] = None    # host holds the first token
    t_last: Optional[float] = None     # host holds the latest token
    n_done: int = 0                    # tokens delivered
    n_in_window: int = 0               # of those, delivered by the close
    tokens: Optional[List[int]] = None
    cut: bool = False                  # backlog: in a slot at the close
    logits: Any = None                 # the prefill's last-position logits

    @property
    def finished(self) -> bool:
        return self.n_done >= self.n_out


def p95(values: Sequence[float]) -> float:
    """Nearest-rank 95th percentile."""
    if not values:
        raise ValueError("p95 of no values")
    xs = sorted(values)
    return xs[max(0, math.ceil(0.95 * len(xs)) - 1)]


def tpot_ms(reqs: Sequence[Served], t_end: float) -> List[float]:
    """(t_last - t_first) / (n_out - 1) per request: the stream rate a
    client feels.  An unfinished request is charged the drain's end as its
    last token; one that never delivered a token, from when it fell due."""
    out = []
    for r in reqs:
        if r.n_out < 2:
            continue
        first = r.t_first if r.t_first is not None else r.due
        last = r.t_last if r.finished else t_end
        out.append((last - first) / (r.n_out - 1) * 1e3)
    return out


def failed(reqs: Sequence[Served]) -> List[Served]:
    return [r for r in reqs if not r.finished and not r.cut]


def output_tok_s(reqs: Sequence[Served], seconds: float) -> float:
    """Tokens delivered to clients by the window's close, per second."""
    return sum(r.n_in_window for r in reqs) / seconds
