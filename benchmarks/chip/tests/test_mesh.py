"""A tensor-parallel cell with a paged cache, on four host devices.

The configuration ``data/configs/tiny-tp4.json`` (olmo-1b's block at a
size a CPU holds, ``"mesh": {"model": 4}``, a paged int8 cache) goes
through ``run.run_cell`` as a four-chip cell would: ``serving.build`` makes
the mesh, the sharded weights (a layer at a time, each device keeping its
slice) and ``ServeEngine(mesh=...)``, and the check
reads the paged, sharded cache.  Four devices need
``--xla_force_host_platform_device_count`` before JAX starts, so the
readings are made in one subprocess (as ``tests/test_sharding.py`` does)
and each test judges its part of them.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MESH_FAULTS = ("exchange_left_out", "half_batch_left_out", "state_unchanged",
               "token_altered")

SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import contextlib, io, json, sys, traceback, types
import jax
import jax.numpy as jnp
import numpy as np
from benchmarks.chip import peaks, run, serving, traffic, view, weights, workload
from benchmarks.chip.faults import for_config

DATA = sys.argv[1]
peaks.PEAKS["cpu"] = peaks.Peaks(1e12, 1e12, 1e11, 2**34, "test only")
devices = jax.devices()[:4]
out = {}


def section(name):
    def wrap(fn):
        try:
            out[name] = fn()
        except Exception:
            out[name] = {"error": traceback.format_exc()}
    return wrap


def cell(config, chips):
    bench = {"workloads": [{"name": "c", "config": config, "traffic": "tiny",
                            "chips": chips}],
             "end_to_end": [{"name": "setup_s", "unit": "s"},
                            {"name": "output_tok_s", "unit": "tokens/s"}],
             "per_layer": []}
    return workload.resolve("c", bench, root=DATA)


@section("runs")
def _():
    got = {}
    faults = for_config(workload.load_json(DATA, "configs", "tiny-tp4"))
    for name in [None] + sorted(faults):
        args = types.SimpleNamespace(seed=21 if name is None else 22,
                                     seconds=1.0, trace=0, control=0)
        text = io.StringIO()
        with contextlib.redirect_stdout(text), \
                contextlib.redirect_stderr(io.StringIO()):
            rc = run.run_cell(cell("tiny-tp4", 4), args, devices,
                              checked_fault=faults.get(name))
        res = json.loads(text.getvalue().strip().splitlines()[-1])
        got[str(name)] = {"rc": rc, "correct": res["correct"],
                          "checks": res["checks"], "device": res["device"],
                          "attempted": res["attempted"],
                          "failed": res["failed"]}
    return got


@section("weights")
def _():
    cfg = workload.load_json(DATA, "configs", "tiny-tp4")
    mesh = serving.mesh_for(cfg, devices)
    got = {}
    for seed in (3, 2**33 + 5):
        one = weights.Source(cfg, seed).stacked()
        sharded = weights.make(cfg, seed, mesh)
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(one),
                                jax.tree.leaves(sharded)):
            held = sorted({s.data.size / b.size for s in b.addressable_shards})
            got.setdefault(jax.tree_util.keystr(path), []).append(
                {"equal": bool(np.array_equal(np.asarray(a), np.asarray(b))),
                 "held": held,
                 "devices": len({s.device for s in b.addressable_shards})})
    return got


@section("slot_rows")
def _():
    cfg = workload.load_json(DATA, "configs", "tiny-tp4")
    mix = workload.load_json(DATA, "traffic", "tiny")
    reqs = traffic.generate(mix, 5, 1.0, cfg["vocab_size"])
    got = {}
    for layout in ("paged", "contiguous"):
        c = dict(cfg, engine=dict(cfg["engine"], cache_layout=layout))
        engine, sched, _ = serving.build(c, 5, lambda msg: None, devices)
        loop = serving.Loop(sched, 1)
        loop.capture = {r.uid for r in reqs}
        run_ = loop.drive(reqs, serving.clock(), 0.0, drain_s=600.0,
                          backlog=False)
        got[layout] = {
            "tokens": {r.uid: r.tokens for r in run_["requests"]},
            "rows": {u: jax.tree.map(np.asarray, s)
                     for u, s in loop.snapshots.items()},
            "paged": type(sched.cache).__name__}
    lens = {r.uid: len(r.prompt) + r.n_out - 1 for r in reqs}
    unequal = []
    for uid, n in lens.items():
        a, b = got["paged"]["rows"][uid], got["contiguous"]["rows"][uid]
        for name in ("kq", "vq", "v_scale"):
            if a[name].shape != b[name].shape or not np.array_equal(
                    a[name][:, :n], b[name][:, :n]):
                unequal.append((uid, name))
        if not np.array_equal(a["k_scale"], b["k_scale"]):
            unequal.append((uid, "k_scale"))
    return {"requests": len(lens), "unequal": unequal,
            "caches": [got[k]["paged"] for k in ("paged", "contiguous")],
            "shapes": {k: list(v.shape) for k, v in
                       got["paged"]["rows"][reqs[0].uid].items()},
            "same_tokens": got["paged"]["tokens"] == got["contiguous"]["tokens"]}


@section("programs")
def _():
    names = {}
    for config, chips in (("tiny-tp4", 4), ("tiny", 1)):
        cfg = workload.load_json(DATA, "configs", config)
        engine, _, _ = serving.build(cfg, 1, lambda msg: None,
                                     devices[:chips])
        dc = engine.dispatch_closures(batch=cfg["n_slots"])
        if engine.mesh is None:
            decode = engine._decode
        else:
            decode = engine._sharded_decode(engine.decode_chunk, 1)
        for role, fn in (("decode", decode), ("prefill", engine._prefill)):
            text = fn.lower(*dc[role].args).as_text()
            names[f"{config}.{role}"] = text.split("@", 1)[1].split()[0]
    return {"found": names,
            "expected": {"tiny-tp4.decode": view.MESH_DECODE,
                         "tiny-tp4.prefill": view.PREFILL,
                         "tiny.decode": view.DECODE,
                         "tiny.prefill": view.PREFILL}}


print(json.dumps(out))
'''


@pytest.fixture(scope="module")
def readings():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([REPO, os.path.join(REPO, "src")]))
    got = subprocess.run([sys.executable, "-c", SCRIPT, DATA], cwd=REPO,
                         env=env, capture_output=True, text=True, timeout=900)
    assert got.returncode == 0, got.stdout[-2000:] + got.stderr[-4000:]
    return json.loads(got.stdout.strip().splitlines()[-1])


def part(readings, name):
    got = readings[name]
    assert "error" not in got, got.get("error")
    return got


def test_a_sound_tp4_paged_run_is_correct(readings):
    res = part(readings, "runs")["None"]
    assert res["rc"] == 0 and res["correct"] is True
    assert res["device"]["count"] == 4
    assert res["attempted"] > 0 and res["failed"] == 0
    with open(os.path.join(DATA, "configs", "tiny-tp4.json")) as f:
        limits = json.load(f)["check"]["limits"]
    for name, limit in limits.items():
        assert res["checks"][name]["limit"] == limit
        assert res["checks"][name]["value"] <= limit


@pytest.mark.parametrize("fault", MESH_FAULTS)
def test_a_fault_in_the_sharded_timed_path_is_not_correct(readings, fault):
    res = part(readings, "runs")[fault]
    assert res["rc"] == 0 and res["correct"] is False


def test_sharded_weights_equal_one_devices_bit_for_bit(readings):
    # one device's tree made a layer at a time (weights.Source), as the
    # mesh makes it: a step summed over one layer may differ in the last
    # bits from the stacked tree's on a TPU
    got = part(readings, "weights")
    assert got and all(r["equal"] for rs in got.values() for r in rs), got


def test_each_device_holds_a_quarter_of_each_sharded_leaf(readings):
    got = part(readings, "weights")
    sharded = [k for k in got if k.endswith("['w']")]
    # the 7 projections of the layer stack, the embedding and the head
    assert len(sharded) == 9
    for leaf in sharded:
        for r in got[leaf]:
            assert r["held"] == [0.25] and r["devices"] == 4, (leaf, r)
    for leaf in set(got) - set(sharded):           # steps and norm scales
        assert all(r["held"] == [1.0] for r in got[leaf]), leaf


def test_paged_slot_rows_equal_the_contiguous_ones(readings):
    got = part(readings, "slot_rows")
    assert got["caches"] == ["PagedServeCache", "ServeCache"]
    assert got["requests"] == 24 and got["same_tokens"]
    assert got["unequal"] == []
    assert got["shapes"] == {"kq": [1, 128, 4, 32], "k_scale": [1, 4, 32],
                             "vq": [1, 128, 4, 32], "v_scale": [1, 128, 4]}


def test_the_trace_names_view_expects_are_the_programs_names(readings):
    got = part(readings, "programs")
    assert got["found"] == got["expected"]
