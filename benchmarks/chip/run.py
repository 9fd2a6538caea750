#!/usr/bin/env python3
"""Run one cell of the chip benchmark once, in this process.

    python3 benchmarks/chip/run.py --workload olmo-1b.chat --seed 7 \\
        --seconds 40 --trace 0

Set-up (counted in ``setup_s``, from process start to the window's start):
weights from the seed on the device, EAGL + knapsack + ``pack_params``,
the bf16 weights dropped, then one pass over every shape the cell's
traffic uses.  The window serves the cell's traffic open loop for
``--seconds`` (``serving.Loop``); an open loop then follows every request
due in the window to completion.  With ``--trace 1`` the window runs under
the profiler and the result carries the cell's per-layer metrics; with
``--trace 0`` its end-to-end metrics.

Afterwards, with the program's state freed, a seeded sample of the
finished requests is compared with the plain reference (``check.py``).
Two options serve the setting of the limits and are never part of a
benchmark run: ``--control 1`` also reads the control in the program's
place and judges it by the same limits, and ``--fault <name>`` plants a
fault in the timed path (``faults.py``).

Earlier lines report the device, the policy, resident and peak bytes, the
set-up split, compiles inside the window (there should be none) and how
late the client noticed requests falling due.  The numbers compared for
``correct`` are the last lines on stderr.  The last line on stdout is one
JSON object.  Without a TPU, or with fewer chips than the cell needs, the
run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
DRAIN_S = 60.0
# A traced run traces the first TRACE_S seconds of its window: writing out
# a trace takes about two seconds per traced second, which would otherwise
# run past a run's time limit.
TRACE_S = 15.0


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileLog:
    """Counts compiles (and retraces) and sums compile seconds, from JAX's
    own monitoring events."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.compiles = 0
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, *args, **kwargs):
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.seconds += duration
        elif event == TRACE_EVENT:
            self.traces += 1


def parse(argv):
    from benchmarks.chip.faults import FAULTS, MESH_FAULTS
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the control (the reference one type "
                    "below the configuration's) in the program's place and "
                    "judge it by the same limits; for setting limits, never "
                    "in a benchmark run")
    ap.add_argument("--fault", choices=sorted({**FAULTS, **MESH_FAULTS}),
                    default=None,
                    help="plant a fault in the timed path (faults.py); for "
                    "showing that correct catches it, never in a benchmark "
                    "run")
    return ap.parse_args(argv)


def find_devices(jax, chips: int):
    """The chips this cell runs on, or a reason there are none."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return None, f"needs a TPU, JAX found {devices[0].platform!r}"
    if len(devices) < chips:
        return None, f"needs {chips} chips, JAX found {len(devices)}"
    return devices[:chips], None


def main(argv=None) -> int:
    sys.path[:0] = [REPO, os.path.join(REPO, "src")]
    args = parse(argv)
    try:
        from benchmarks.chip import workload
        bench = workload.load_benchmark()
        cell = workload.resolve(args.workload, bench)
        import repro  # noqa: F401  the program under test
    except (ImportError, FileNotFoundError, KeyError) as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    import jax
    devices, why = find_devices(jax, cell.chips)
    if devices is None:
        print(f"run.py: {cell.name} {why}", file=sys.stderr)
        return 2
    from repro.launch import compile_cache
    log(f"compilation cache: {compile_cache.configure()}")
    # every program the window runs is then in the cache after a first run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from benchmarks.chip.faults import FAULTS, MESH_FAULTS
    return run_cell(cell, args, devices,
                    checked_fault={**FAULTS, **MESH_FAULTS}.get(args.fault))


def run_cell(cell, args, devices, *, checked_fault=None) -> int:
    """Everything after the look for a chip.  ``checked_fault`` lets a test
    break the timed path underneath (benchmarks/chip/tests)."""
    import jax
    from benchmarks.chip import (check, costs, e2e, peaks, serving, trace,
                                 traffic, view, weights, workload)
    cfg, mix = cell.cfg, cell.mix
    kind = devices[0].device_kind
    pk = peaks.for_kind(kind)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices)}
    log(f"device: {kind} x {len(devices)} ({device['platform']})")
    log(f"cell {cell.name}: config {cell.config_name} (model {cfg['model']},"
        f" {cfg['num_hidden_layers']} layers, d {cfg['hidden_size']}, "
        f"{cfg['num_attention_heads']}/{cfg['num_key_value_heads']} heads x "
        f"{cfg['head_dim']}, d_ff {cfg['intermediate_size']}, vocab "
        f"{cfg['vocab_size']}), {cfg['n_slots']} slots x {cfg['max_seq']}, "
        f"{cfg['engine'].get('cache_layout', 'contiguous')} cache, mesh "
        f"{cfg.get('mesh')}, traffic {cell.traffic_name}, seed {args.seed}, "
        f"{args.seconds:g} s")
    comp = CompileLog(jax)
    readers = workload.readers(cell.per_layer if args.trace
                               else cell.end_to_end)

    engine, sched, bits = serving.build(cfg, args.seed, log, devices)
    mesh = engine.mesh
    if checked_fault is not None:
        checked_fault(engine, sched)
    loop = serving.Loop(sched, int(cfg["check"].get("kv_layers", 0)))
    t = time.perf_counter()
    serving.warm_up(loop, mix, cfg, args.seed)
    log(f"[set-up] warm-up {time.perf_counter() - t:.2f} s")
    reqs = traffic.generate(mix, args.seed, args.seconds, cfg["vocab_size"])
    backlog = mix["arrival"]["kind"] == "backlog"
    loop.capture = set(check.candidates(reqs, args.seed,
                                        int(mix["check_tokens"]),
                                        args.seconds, backlog,
                                        cfg["n_slots"]))
    rep = engine.residency(sched.cache)
    log(f"resident bytes: packed weights {rep['resident_weight_bytes']:,}, "
        f"int{cfg['engine']['cache_bits']} KV cache "
        f"{rep['resident_kv_bytes']:,}")

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    trace_s = min(args.seconds, TRACE_S)
    spans = []

    def stop_trace():
        spans.pop().__exit__(None, None, None)
        jax.profiler.stop_trace()

    gc.collect()
    c0, n0, r0 = comp.seconds, comp.compiles, comp.traces
    t_open = time.perf_counter()
    setup_s = t_open - T_START
    hooks = []
    if tdir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        # an annotation records only if the profiler runs when it is made
        spans.append(jax.profiler.TraceAnnotation(trace.WINDOW_SPAN))
        spans[0].__enter__()
        hooks.append((trace_s, stop_trace))
    run = loop.drive(reqs, t_open, args.seconds, DRAIN_S, backlog, hooks)
    n_comp, n_trace = comp.compiles - n0, comp.traces - r0
    log(f"[set-up] {setup_s:.3f} s: compile {c0:.3f} s, the rest "
        f"{setup_s - c0:.3f} s")
    log(f"compiles inside the window: {n_comp} ({comp.seconds - c0:.3f} s),"
        f" retraces {n_trace}")

    att = serving.attempted(run)
    done = [r for r in att if r.finished]
    failed = len(e2e.failed(att))
    late = [(r.noticed - r.due) * 1e3 for r in run["requests"]
            if r.noticed is not None]
    if late:
        qs = statistics.quantiles(late, n=100) if len(late) > 1 else late * 99
        log(f"client lateness (due -> noticed, ms): p50 {qs[49]:.3f} "
            f"p99 {qs[98]:.3f} max {max(late):.3f} over {len(late)}")
    log(f"window: {len(att)} attempted, {len(done)} finished, "
        f"{sum(r.cut for r in att)} cut by the close, {failed} failed, {sum(r.n_in_window for r in run['requests'])} tokens "
        f"delivered in {args.seconds:g} s, drain "
        f"{run['t_end'] - run['closed_at']:.2f} s")
    dec = [r for r in run["rounds"] if r["kind"] == "decode"
           and r["t0"] < run["t_close"]]
    if dec:
        wall = sum(r["t1"] - r["t0"] for r in dec)
        log(f"decode rounds in the window: {len(dec)}, "
            f"{sum(r['steps'] for r in dec)} scan steps, host "
            f"{1e3 * wall / sum(r['steps'] for r in dec):.2f} ms a step")
    if backlog:
        log(f"backlog: fewest requests waiting at a decode round "
            f"{run['min_waiting']} (the queue never emptied: "
            f"{bool(run['min_waiting'])})")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    log(f"peak_bytes_in_use {peak:,} of {pk.hbm_capacity:,}")
    device["memory_peak_bytes"] = peak

    v = view.View(run=run, attempted=att, peaks=pk,
                  decode_program=view.DECODE if mesh is None
                  else view.MESH_DECODE)
    tr = None
    if tdir:
        v.trace_end = t_open + trace_s
        t = time.perf_counter()
        tr = trace.reduce(trace.find_xplane(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        v.trace = tr
        v.costs = costs.totals(view.traced_rounds(v), costs.Dims.of(cfg),
                               bits, pk.bf16_flops, pk.hbm_bytes)
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        log(f"trace: window {tr.window_s:.3f} s, busy {tr.busy_s:.3f} s, "
            f"programs {json.dumps(tr.program_runs)}, reduced in "
            f"{time.perf_counter() - t:.1f} s")
        log(f"idle by host span (s): {json.dumps(trace.idle_by_span(tr))}")

    metrics = {}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if not args.trace:
        metrics[workload.SETUP] = {"value": setup_s, "unit": "s"}
    for name, read in readers.items():
        value = read(v)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": units[name]}

    # correctness, with the program's state freed
    prompts = {r.uid: r.prompt for r in reqs}
    samples = [(prompts[r.uid], r.tokens, r.logits,
                jax.tree.map(np.asarray, loop.snapshots.get(r.uid)))
               for r in done if r.uid in loop.capture]
    del loop, sched, engine, run, v, done
    gc.collect()
    t = time.perf_counter()
    params = weights.make(cfg, args.seed, mesh)
    control = check.control_for(cfg) if args.control else None
    got = check.readings(cfg, params, bits, samples, control=control)
    del params
    limits = cfg["check"]["limits"]
    numbers = check.summarize(got)
    ok, rows = check.judge(numbers, limits)
    ok = ok and bool(samples)
    log(f"checked {len(samples)} requests, "
        f"{sum(len(s[1]) for s in samples)} served tokens, against "
        f"the reference in {time.perf_counter() - t:.1f} s")
    for name in sorted(k for k in got if k.endswith("_kv")):
        g = got[name]
        log(f"{name}: {len(g)} distinct tokens; by layer, mean "
            + " ".join(f"{x:.4g}" for x in g.mean(0)) + ", median "
            + " ".join(f"{x:.4g}" for x in np.median(g, 0)) + ", share over "
            "0.02 " + " ".join(f"{x:.3g}" for x in (g > 0.02).mean(0))
            + ", largest " + " ".join(f"{x:.4g}" for x in g.max(0)))
    if control is not None:
        c_ok, c_rows = check.judge(check.summarize(got, prefix="ctrl_"),
                                   limits)
        for row in c_rows:
            lim = ("not compared" if row["limit"] is None
                   else f"{row['limit']:g}")
            log(f"control {row['name']}: {row['value']:.6g} (limit {lim})")
        log(f"control correct: {c_ok}")
    for row in rows:
        lim = "not compared" if row["limit"] is None else f"{row['limit']:g}"
        print(f"check {row['name']}: {row['value']:.6g} (limit {lim})",
              file=sys.stderr, flush=True)
    print(f"check correct: {ok}", file=sys.stderr, flush=True)

    result = {"correct": ok, "attempted": len(att), "failed": failed,
              "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = trace.breakdown(tr)
    result["checks"] = {r["name"]: {"value": r["value"], "limit": r["limit"]}
                        for r in rows}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
