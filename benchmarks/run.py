"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--quick`` shrinks sizes for CI.

  frontier      Fig. 3/4/5: per-method loss across the budget sweep
  metric_cost   Table 3: metric computation cost (EAGL vs ALPS vs HAWQ)
  knapsack      §3.1: knapsack solve time at paper-scale item counts
  additivity    Appendix A: pairwise additivity correlation R
  quant         Table 1 (TPU terms): packed-weight matmul bytes/time
  serve         deployment: decode tokens/sec + weight bytes/token per
                policy (also written to BENCH_serve.json for CI)
  compile       bucketed-vs-unrolled decode-step compile cost (trace+lower
                wall time + jaxpr eqns) at depth 8/32/80 under a 4-level
                mixed policy (also written to BENCH_compile.json for CI)
"""
from __future__ import annotations

import argparse
import json
import time


def _row(name, us, derived):
    print(f"{name},{us:.1f},{derived}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json-out", default="BENCH_serve.json",
                    help="where the serve benchmark drops its JSON report")
    args = ap.parse_args()
    only = args.only.split(",") if args.only else None
    q = args.quick
    from repro.launch import compile_cache
    compile_cache.configure()

    print("name,us_per_call,derived")

    if only is None or "serve" in only:
        from benchmarks import serve_bench
        out = serve_bench.run(quick=q)
        for name, r in out.items():
            if name.startswith("_"):
                continue
            _row(f"serve/{name}", r["us_per_token_packed"],
                 f"tokens_per_s_packed={r['tokens_per_s_packed']:.1f};"
                 f"tokens_per_s_fake_quant={r['tokens_per_s_fake_quant']:.1f};"
                 f"resident_weight_bytes_packed="
                 f"{r['resident_weight_bytes_packed']};"
                 f"packed_reduction_vs_bf16="
                 f"{r['packed_reduction_vs_bf16']:.2f}x")
        with open(args.json_out, "w") as f:
            json.dump(out, f, indent=2, sort_keys=True)

    if only is None or "knapsack" in only:
        from benchmarks import knapsack_bench
        kout = knapsack_bench.run(quick=q)
        for name, dt in kout.items():
            _row(f"knapsack/{name}", dt * 1e6, "eps_optimal_dp")
        with open("BENCH_knapsack.json", "w") as f:
            json.dump({k: v * 1e6 for k, v in kout.items()}, f, indent=2,
                      sort_keys=True)

    if only is None or "compile" in only:
        from benchmarks import compile_bench
        cout = compile_bench.run(quick=q)
        for name, r in sorted(cout.items()):
            if name.startswith("_"):
                continue
            _row(f"compile/{name}", r["lower_s"] * 1e6,
                 f"jaxpr_eqns={r['jaxpr_eqns']};n_buckets={r['n_buckets']}")
        with open("BENCH_compile.json", "w") as f:
            json.dump(cout, f, indent=2, sort_keys=True)

    if only is None or "quant" in only:
        from benchmarks import quant_bench
        for name, r in quant_bench.run(quick=q).items():
            _row(f"quant_matmul/{name}", r["us"],
                 f"weight_bytes={r['weight_bytes']}")

    if only is None or "metric_cost" in only:
        from benchmarks import metric_cost_bench
        out = metric_cost_bench.run(quick=q)
        _row("metric_cost/eagl", out["eagl_s"] * 1e6,
             f"n_units={out['n_units']}")
        _row("metric_cost/alps", out["alps_s"] * 1e6,
             f"n_units={out['n_units']}")
        _row("metric_cost/hawq_v3", out["hawq_s"] * 1e6,
             f"n_units={out['n_units']}")

    if only is None or "additivity" in only:
        from benchmarks import additivity_bench
        t0 = time.perf_counter()
        out = additivity_bench.run(n_pairs=10 if q else 20, quick=q)
        _row("additivity/pairwise", (time.perf_counter() - t0) * 1e6,
             f"R={out['R']:.4f}")

    if only is None or "frontier" in only:
        from benchmarks import frontier_bench
        t0 = time.perf_counter()
        out = frontier_bench.run(budgets=(0.75,) if q else (0.9, 0.75, 0.6),
                                 quick=q)
        dt = (time.perf_counter() - t0) * 1e6
        _row("frontier/4bit_baseline", dt, f"loss={out['four_bit_loss']:.4f}")
        _row("frontier/2bit_floor", dt, f"loss={out['two_bit_loss']:.4f}")
        for r in out["rows"]:
            _row(f"frontier/{r['method']}@{r['budget']:.2f}", dt,
                 f"loss={r['loss']:.4f};comp={r['compression']:.1f}x;"
                 f"dropped={r['n_dropped']}")


if __name__ == "__main__":
    main()
