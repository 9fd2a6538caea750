"""Model step: prefill-program device time per 1,000 real prompt tokens
(padding to the prompt bucket shows up as cost)."""
from benchmarks.chip.view import PREFILL


def read(view):
    tr, c = view.trace, view.costs
    if tr is None or c is None or c.prefill_tokens <= 0 \
            or tr.program_s(PREFILL) <= 0:
        return None
    return 1e3 * tr.program_s(PREFILL) / (c.prefill_tokens / 1e3)
