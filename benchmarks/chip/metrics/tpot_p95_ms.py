"""95th percentile over the attempted requests of (t_last - t_first) /
(n_out - 1): the rate at which a client's stream arrives."""
from benchmarks.chip import e2e


def read(view):
    xs = e2e.tpot_ms(view.attempted, view.run["t_end"])
    return e2e.p95(xs) if xs else None
