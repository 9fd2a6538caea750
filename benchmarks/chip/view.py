"""What a metric reader is given: the run record and, in a traced run,
the reduced trace and the costs of the rounds it covers."""
from __future__ import annotations

import dataclasses
from typing import List, Optional

from benchmarks.chip import costs as costs_mod
from benchmarks.chip.e2e import Served
from benchmarks.chip.peaks import Peaks
from benchmarks.chip.trace import Trace

DECODE = "jit__decode_impl"        # the engine's scanned decode program
PREFILL = "jit__prefill_impl"      # the engine's prefill program (a mesh's too)
MESH_DECODE = "jit_body"           # a mesh engine's decode: the jitted
#                                    shard_map of ``body`` in
#                                    ServeEngine._sharded_decode_sm


@dataclasses.dataclass
class View:
    run: dict                      # serving.Loop.drive's record
    attempted: List[Served]
    peaks: Peaks
    trace: Optional[Trace] = None
    costs: Optional[costs_mod.Totals] = None
    trace_end: Optional[float] = None      # host clock: the trace stopped
    decode_program: str = DECODE       # MESH_DECODE on a mesh

    @property
    def until(self) -> float:
        """The end of the part of the window the metrics read."""
        return self.run["t_close"] if self.trace_end is None \
            else min(self.trace_end, self.run["t_close"])


def traced_rounds(v: View) -> List[dict]:
    """Rounds started before the trace stopped: it covers them whole (the
    trace stops between rounds)."""
    return [r for r in v.run["rounds"] if r["t0"] < v.until]
