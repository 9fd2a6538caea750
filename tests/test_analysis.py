"""Static serving-contract analyzer: every detector must (a) pass on the
clean engines and (b) flag its motivating bug class when re-introduced.

The injection tests are the point of the suite (ISSUE: "regression tests
that re-introduce each bug class and assert the analyzer flags it"): a
detector that never fires is indistinguishable from no detector, so each
check here traces a program carrying the historical bug — a baked params
constant (PR 4), a full-dtype KV round-trip (PR 1/PR 3), a third psum
(DESIGN.md §3), an unrolled deep stack (PR 6), a retrace leak (PR 8) —
and asserts the violation surfaces, then that report.gate() turns it
into a loud CI failure.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.analysis import (contracts, deadcode, harness, jaxpr_checks,
                            lint_rules, report)
from repro.kernels import ops as kops
from repro.launch.mesh import make_mesh
from repro.serve.engine import DispatchClosure


@pytest.fixture(scope="module")
def quantized_engine():
    return harness.build_engine("quantized")


@pytest.fixture(scope="module")
def spec_chunked_engine():
    return harness.build_engine("spec_chunked")


@pytest.fixture(scope="module")
def sharded_engine():
    return harness.build_engine("sharded")


# ----------------------------------------------------- jaxpr walkers
def test_iter_eqns_recurses_into_scan():
    def fn(xs):
        def body(c, x):
            return c + x * 2.0, c
        return jax.lax.scan(body, jnp.float32(0.0), xs)

    closed = jax.make_jaxpr(fn)(jnp.ones((4,), jnp.float32))
    # the mul/add live INSIDE the scan body: a non-recursive walk sees
    # only the scan eqn itself
    assert len(closed.jaxpr.eqns) < jaxpr_checks.count_eqns(closed)
    assert jaxpr_checks.count_primitive(closed, "scan") == 1


def test_count_primitive_counts_static_structure():
    mesh = make_mesh((1,), ("model",))

    def fn(x):
        def body(c, _):
            return jax.lax.psum(c, "model"), None
        out, _ = jax.lax.scan(body, x, None, length=5)
        return out

    # check_vma=False matches the engine's shard_map mode — with vma
    # checking on, psum lowers as a different primitive ("psum2")
    sm = jax.shard_map(fn, mesh=mesh, in_specs=(P(),), out_specs=P(),
                       check_vma=False)
    closed = jax.make_jaxpr(sm)(jnp.float32(1.0))
    # one psum in the scan BODY counts once — program structure, not
    # executed collectives (5 iterations still == 1 static psum)
    assert jaxpr_checks.count_primitive(closed, "psum") == 1


# ------------------------------------------- baked consts (PR 4 class)
def test_baked_const_detector_flags_captured_params():
    # the bug class: jitting a closure over the checkpoint bakes it as a
    # trace-time constant instead of an argument
    w = np.ones((64, 64), np.float32)          # 4096 elems >= threshold

    def leaky(x):
        return x @ jnp.asarray(w)

    closed = jax.make_jaxpr(leaky)(jnp.ones((1, 64), jnp.float32))
    flagged = jaxpr_checks.find_baked_consts(closed, min_elems=2048)
    assert flagged, "captured 64x64 weight must be detected"
    assert flagged[0].kind == "const" and flagged[0].size == 64 * 64


def test_baked_const_detector_ignores_small_tables():
    def fn(x):
        return x + jnp.asarray(np.arange(8, dtype=np.float32))

    closed = jax.make_jaxpr(fn)(jnp.ones((8,), jnp.float32))
    assert jaxpr_checks.find_baked_consts(closed, min_elems=2048) == []


def test_engine_dispatches_bake_no_consts(quantized_engine,
                                          spec_chunked_engine):
    for eng in (quantized_engine, spec_chunked_engine):
        res = contracts.check_baked_consts(eng)
        assert res.ok, res.violations


# ------------------------------------------- dtype flow (PR 1/3 class)
def _cache_shapes(eng):
    cfg = eng.cfg
    return (1, eng.max_seq, cfg.n_kv_heads, cfg.head_dim)


def test_dtype_flow_flags_full_cache_dequant(quantized_engine):
    # the bug class: dequantizing the whole quantized cache to a
    # full-dtype HBM tensor before attention (the bf16 round-trip that
    # broke greedy parity) — an S_max-sized float OUTPUT in the trace
    b, s_max, hkv, d = _cache_shapes(quantized_engine)
    min_elems = b * s_max * hkv * d

    def leaky(codes, scale):
        full = codes.astype(jnp.float32) * scale      # (B,S_max,Hkv,D)
        return jnp.sum(full)

    closed = jax.make_jaxpr(leaky)(
        jnp.zeros((b, s_max, hkv, d), jnp.int8), jnp.float32(0.1))
    recs = jaxpr_checks.find_float_intermediates(
        closed, min_elems=min_elems, require_axis=s_max)
    assert recs, "full-cache dequant output must be detected"
    assert any(s_max in r.shape for r in recs)


def test_dtype_flow_ignores_weight_sized_dequant(quantized_engine):
    # int8 packed weights legitimately dequantize as one [K, N] float
    # per dispatch — no S_max axis, so the cache check must not alias
    b, s_max, hkv, d = _cache_shapes(quantized_engine)
    min_elems = b * s_max * hkv * d

    def weights(codes, scale):
        return codes.astype(jnp.float32) * scale       # [K, N]

    closed = jax.make_jaxpr(weights)(
        jnp.zeros((128, 128), jnp.int8), jnp.float32(0.1))
    assert jaxpr_checks.find_float_intermediates(
        closed, min_elems=min_elems, require_axis=s_max) == []


def test_quantized_decode_never_materializes_cache(quantized_engine,
                                                   spec_chunked_engine):
    for eng in (quantized_engine, spec_chunked_engine):
        res = contracts.check_dtype_flow(eng)
        assert res.ok, res.violations
        assert res.details["decode"]["flagged"] == 0


def test_dtype_flow_traces_as_deployed(quantized_engine):
    # the contract only holds for the DEPLOYED (Pallas) program: the CPU
    # ref oracle legitimately dequantizes the full cache, so tracing
    # without the deployed_backend override must flag it — proof the
    # forced-tpu resolution is load-bearing, not decorative
    eng = quantized_engine
    b, s_max, hkv, d = _cache_shapes(eng)
    closures = eng.dispatch_closures()
    closed = closures["decode"].trace()                # ref path (CPU)
    recs = jaxpr_checks.find_float_intermediates(
        closed, min_elems=b * s_max * hkv * d, require_axis=s_max)
    assert recs, "CPU ref decode dequantizes the cache — must be visible"


# ----------------------------------- decode cache rides in the carry
@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_decode_scans_carry_the_cache(quantized_engine, backend):
    """No scan of the decode program takes a cache buffer, or one layer
    of one, as its xs or hands one back as its ys: the token scan and
    every bucket's layer scan carry the cache, so a step writes its new
    rows in place and slices no layer out (the deployed Pallas read and
    the CPU ref read alike)."""
    eng = quantized_engine
    closure = eng.dispatch_closures()["decode"]
    shapes = set()
    for leaf in jax.tree.leaves(closure.args[2]):
        if eng.max_seq in leaf.shape:
            shapes |= {leaf.shape, leaf.shape[1:]}
    assert shapes
    with kops.deployed_backend(backend):
        closed = closure.trace()
    scans = [e for e in jaxpr_checks.iter_eqns(closed)
             if e.primitive.name == "scan"]
    # the token scan and one layer scan per bucket, at least
    assert len(scans) >= 1 + len(eng._cache_plan)
    for e in scans:
        n_in = e.params["num_consts"] + e.params["num_carry"]
        xs = {v.aval.shape for v in e.invars[n_in:]}
        ys = {v.aval.shape for v in e.outvars[e.params["num_carry"]:]}
        assert not shapes & (xs | ys), (shapes & (xs | ys))


# ------------------------------------------- collectives (DESIGN §3)
def test_sharded_decode_has_exactly_two_psums(sharded_engine):
    res = contracts.check_collectives(sharded_engine)
    assert res.ok, res.violations
    assert res.details["psums"] == 2 * sharded_engine.n_scan_bodies()


def test_sharded_paged_decode_has_exactly_two_psums():
    """Paged+mesh composition (PR 10): paging changes how K/V rows are
    ADDRESSED, never what is reduced — the sharded PAGED decode traces
    the same two psums per block body as contiguous."""
    eng = harness.build_engine("sharded_paged")
    assert eng.cache_layout == "paged" and eng.mesh is not None
    res = contracts.check_collectives(eng)
    assert res.ok, res.violations
    assert res.details["psums"] == 2 * eng.n_scan_bodies()


class _ThreePsumEngine:
    """Stub with the check_collectives surface: a decode whose block body
    all-reduces a THIRD time (the re-replicated-norm bug class)."""
    mesh = object()                     # "not None" is all the check reads

    def n_scan_bodies(self):
        return 1

    def dispatch_closures(self):
        mesh = make_mesh((1,), ("model",))

        def decode(x):
            h = jax.lax.psum(x * 2.0, "model")         # attn out-proj
            h = jax.lax.psum(h + 1.0, "model")         # ffn down-proj
            return jax.lax.psum(h * 0.5, "model")      # the regression

        sm = jax.shard_map(decode, mesh=mesh, in_specs=(P(),),
                           out_specs=P(), check_vma=False)
        return {"decode": DispatchClosure("decode", sm,
                                          (jnp.float32(1.0),))}


def test_collectives_flags_third_psum():
    res = contracts.check_collectives(_ThreePsumEngine())
    assert not res.ok
    assert "3 psums" in res.violations[0]
    assert "expects 2" in res.violations[0]


# ------------------------------------------- program size (PR 6 class)
def test_program_size_flat_passes():
    res = contracts.check_program_size({8: 1000, 32: 1010, 80: 1020},
                                       lower_s_deep=2.0)
    assert res.ok, res.violations


def test_program_size_flags_unrolled_growth():
    # the bug class: an unrolled sub-path reappearing makes eqn count
    # O(depth) again — 80/8 = 10x growth, far past the 1.05 budget
    res = contracts.check_program_size({8: 1000, 80: 10000})
    assert not res.ok
    assert "grows" in res.violations[0]


def test_program_size_flags_lower_budget():
    res = contracts.check_program_size({8: 1000, 80: 1010},
                                       lower_s_deep=45.0,
                                       lower_budget_s=30.0)
    assert not res.ok
    assert "trace+lower" in res.violations[0]


def test_unrolled_layout_grows_where_bucketed_stays_flat():
    # the real measurement the contract runs on: compile_bench's shared
    # count_eqns over the unrolled vs bucketed decode step
    # depths past bucket saturation (the 4-level policy yields 4 buckets
    # at depth >= 8): bucketed eqn count must be flat from 8 to 16 while
    # unrolled doubles
    from benchmarks import compile_bench
    out = compile_bench.run(depths=(8, 16), layouts=("bucketed", "unrolled"))
    eqns_b = {d: out[f"bucketed@{d}"]["jaxpr_eqns"] for d in (8, 16)}
    eqns_u = {d: out[f"unrolled@{d}"]["jaxpr_eqns"] for d in (8, 16)}
    assert contracts.check_program_size(eqns_b).ok
    res = contracts.check_program_size(eqns_u)
    assert not res.ok, f"unrolled depth growth must be flagged: {eqns_u}"


# ------------------------------------------------ retrace (PR 8 class)
def test_retrace_clean_workloads_pass():
    audits = harness.run_retrace_workloads()
    res = contracts.check_retrace(audits)
    assert res.ok, res.violations
    # the audit is evidence, not a vacuous pass: dispatches actually ran
    assert audits["quantized"]["sizes"]["decode"] >= 1
    assert audits["spec_chunked"]["sizes"]["fused"] >= 1


def test_retrace_flags_leak():
    # the bug class: a shape-keyed argument feeding new trace keys per
    # call — the audit reports traces above the documented budget
    audits = {"wl": {"sizes": {"decode": 9}, "budget": {"decode": 3},
                     "over": {"decode": {"traces": 9, "budget": 3}}}}
    res = contracts.check_retrace(audits)
    assert not res.ok
    assert "traced 9x" in res.violations[0]
    assert "budget 3" in res.violations[0]


def test_dispatch_budget_counts_staging_structure(spec_chunked_engine):
    # verify (bare layers) and fused-prefill (staging attached) are
    # distinct trace keys at the SAME width — the budget must count the
    # (width, staging) pair, not widths alone
    budget = spec_chunked_engine.dispatch_budget(harness.PROMPT_BUCKET)
    assert budget["fused"] == 2


# ------------------------------------------------------ lint: raw keys
def _lint(tmp_path, name, src):
    (tmp_path / name).write_text(src)
    return lint_rules.check_raw_keys(tmp_path)


def test_raw_key_flagged(tmp_path):
    out = _lint(tmp_path, "sched.py",
                "import jax\nk = jax.random.PRNGKey(0)\n")
    assert len(out) == 1 and out[0].rule == "RK001"
    assert "sampling" in out[0].message


def test_raw_key_from_import_flagged(tmp_path):
    out = _lint(tmp_path, "sched.py",
                "from jax.random import PRNGKey\nk = PRNGKey(0)\n")
    assert len(out) == 1


def test_raw_key_justified_marker_allowed(tmp_path):
    out = _lint(tmp_path, "sched.py",
                "import jax\nk = jax.random.PRNGKey(0)"
                "  # analysis: allow-raw-key -- seeding the test oracle\n")
    assert out == []


def test_raw_key_bare_marker_is_violation(tmp_path):
    out = _lint(tmp_path, "sched.py",
                "import jax\nk = jax.random.PRNGKey(0)"
                "  # analysis: allow-raw-key\n")
    assert len(out) == 1
    assert "justification" in out[0].message


def test_raw_key_sampling_exempt(tmp_path):
    out = _lint(tmp_path, "sampling.py",
                "import jax\nk = jax.random.PRNGKey(0)\n")
    assert out == []


def test_serve_layer_is_clean():
    from pathlib import Path
    serve_dir = Path(contracts.__file__).parents[1] / "serve"
    assert lint_rules.check_raw_keys(serve_dir) == []


# -------------------------------------------------- dead-code sweep
def _mini_repo(tmp_path, allow_text=None):
    pkg = tmp_path / "src" / "repro"
    pkg.mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "used.py").write_text("def alive():\n    return 1\n")
    (pkg / "dead.py").write_text("def nobody_calls_me():\n    return 2\n")
    (tmp_path / "scripts").mkdir()
    (tmp_path / "scripts" / "main.py").write_text(
        "from repro.used import alive\nalive()\n")
    allow = tmp_path / "allow.txt"
    allow.write_text(allow_text if allow_text is not None else "")
    return tmp_path, allow


def test_deadcode_flags_unreferenced_module(tmp_path):
    root, allow = _mini_repo(tmp_path)
    res = deadcode.sweep(root, allowlist_path=allow)
    assert any("repro.dead" in v for v in res["violations"])
    assert not any("repro.used" in v for v in res["violations"])


def test_deadcode_allowlist_needs_justification(tmp_path):
    root, allow = _mini_repo(tmp_path, "repro.dead:\n")
    res = deadcode.sweep(root, allowlist_path=allow)
    assert any("no" in v and "justification" in v
               for v in res["violations"])


def test_deadcode_justified_entry_allowlisted(tmp_path):
    root, allow = _mini_repo(
        tmp_path, "repro.dead: roadmap scaffolding, lands next PR\n")
    res = deadcode.sweep(root, allowlist_path=allow)
    assert res["violations"] == []
    assert "repro.dead" in res["allowlisted"]


def test_deadcode_stale_entry_reported(tmp_path):
    root, allow = _mini_repo(tmp_path, "repro.used: not actually dead\n")
    res = deadcode.sweep(root, allowlist_path=allow)
    assert "repro.used" in res["stale_allowlist"]


def test_repo_deadcode_clean():
    from pathlib import Path
    repo = Path(contracts.__file__).parents[3]
    res = deadcode.sweep(repo)
    assert res["violations"] == [], res["violations"]
    assert res["stale_allowlist"] == [], res["stale_allowlist"]


# ----------------------------------------------- report + gate (CI leg)
def _clean_report():
    cs = [contracts.ContractResult(n, "PR x", "file", (), {})
          for n in contracts.ALL_CONTRACTS]
    dead = {"violations": [], "allowlisted": [], "stale_allowlist": [],
            "n_definitions": 1}
    return report.build_report(cs, [], dead, meta={"jax": jax.__version__})


def test_gate_passes_clean_report():
    assert report.gate(_clean_report()) == []


def test_gate_fails_on_missing_contract():
    doc = _clean_report()
    del doc["contracts"]["collectives"]
    fails = report.gate(doc)
    assert any("REQUIRED contract 'collectives'" in f for f in fails)


def test_gate_fails_on_missing_section():
    doc = _clean_report()
    del doc["deadcode"]
    assert any("'deadcode' missing" in f for f in report.gate(doc))


def test_gate_fails_on_contract_violation():
    doc = _clean_report()
    doc["contracts"]["dtype_flow"]["ok"] = False
    doc["contracts"]["dtype_flow"]["violations"] = [
        "decode: intermediate float32[1, 64, 4, 32] (8192 elems)"]
    fails = report.gate(doc)
    assert any("contract dtype_flow" in f for f in fails)


def test_gate_fails_on_lint_and_deadcode():
    doc = _clean_report()
    doc["lint"]["raw_key"] = ["serve/x.py:3: [RK001] raw PRNGKey"]
    doc["deadcode"]["violations"] = ["unreferenced: repro.zombie"]
    fails = report.gate(doc)
    assert any("lint raw_key" in f for f in fails)
    assert any("deadcode:" in f for f in fails)


def test_gate_psum_exact_match_vs_baseline():
    doc = _clean_report()
    doc["contracts"]["collectives"]["details"] = {"psums": 3, "expected": 3}
    base = _clean_report()
    base["contracts"]["collectives"]["details"] = {"psums": 2, "expected": 2}
    fails = report.gate(doc, baseline=base)
    assert any("psum count 3 != baseline 2" in f for f in fails)


def test_gate_psum_exact_match_per_engine_kind():
    """Baselines keyed per sharded engine kind ({"sharded": {...},
    "sharded_paged": {...}}) gate each psum count exactly — a paged
    regression fails even when the contiguous count still matches."""
    good = {"sharded": {"psums": 2, "expected": 2},
            "sharded_paged": {"psums": 2, "expected": 2}}
    base = _clean_report()
    base["contracts"]["collectives"]["details"] = good
    doc = _clean_report()
    doc["contracts"]["collectives"]["details"] = {
        "sharded": {"psums": 2, "expected": 2},
        "sharded_paged": {"psums": 3, "expected": 2}}
    fails = report.gate(doc, baseline=base)
    assert any("collectives[sharded_paged]" in f and "psum count 3" in f
               for f in fails), fails
    doc["contracts"]["collectives"]["details"] = good
    assert not report.gate(doc, baseline=base)


def test_gate_eqn_rtol_vs_baseline():
    doc = _clean_report()
    doc["contracts"]["program_size"]["details"] = {
        "eqns_by_depth": {"80": 2000}}
    base = _clean_report()
    base["contracts"]["program_size"]["details"] = {
        "eqns_by_depth": {"80": 1000}}
    fails = report.gate(doc, baseline=base)
    assert any("outside rtol" in f for f in fails)
    # within rtol: no failure
    doc["contracts"]["program_size"]["details"]["eqns_by_depth"]["80"] = 1100
    assert report.gate(doc, baseline=base) == []


def test_report_round_trips_through_json(tmp_path):
    doc = _clean_report()
    p = tmp_path / "ANALYSIS.json"
    report.write_report(doc, p)
    assert report.load(p) == json.loads(json.dumps(doc))


# -------------------------------------------- deployed-backend override
def test_deployed_backend_forces_pallas_resolution():
    assert not kops.on_tpu()
    with kops.deployed_backend("tpu"):
        assert kops.on_tpu()
        with kops.deployed_backend("cpu"):
            assert not kops.on_tpu()
        assert kops.on_tpu()
    assert not kops.on_tpu()
