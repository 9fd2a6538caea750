"""Scheduler: device time in prefill programs over device busy time, in
the traced window (whole-prompt admission stalls every decoding slot)."""
from benchmarks.chip.view import PREFILL


def read(view):
    tr = view.trace
    if tr is None or tr.busy_s <= 0 or tr.program_s(PREFILL) <= 0:
        return None
    return 100.0 * tr.program_s(PREFILL) / tr.busy_s
