"""A configuration with expert layers, made, packed and checked a layer at
a time.

``data/configs/tiny-moe.json`` is dbrx-132b's block (GQA attention, a
top-k softmax router over experts with renormalised weights, SwiGLU
experts, LayerNorm with a scale and a bias) at a size a CPU test holds:
3 layers, d 128, 4 / 2 heads, 8 experts, top 2, float32.  It goes
through ``run.run_cell`` past the harness's look for a chip: weights from
``weights.Source`` -> EAGL -> knapsack -> packing a layer at a time ->
``ServeEngine`` -> ``ContinuousBatchingScheduler``, and the check against
``references/moe_gqa.py``.  Its limits are its own, set from its readings
the way PERF.md sets the cells'.
"""
import contextlib
import gc
import hashlib
import io
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import (check, faults, peaks, run, serving, weights,
                             workload)
from benchmarks.chip.references import dense_gqa, moe_gqa

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MOE = workload.load_json(DATA, "configs", "tiny-moe")
TINY = workload.load_json(DATA, "configs", "tiny")
FAULTS = faults.for_config(MOE)


@pytest.fixture(scope="module")
def cell():
    bench = {"workloads": [{"name": "tiny-moe.batch", "config": "tiny-moe",
                            "traffic": "tiny", "chips": 1}],
             "end_to_end": [{"name": "setup_s", "unit": "s"},
                            {"name": "output_tok_s", "unit": "tokens/s"}],
             "per_layer": []}
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(peaks.PEAKS, "cpu",
                   peaks.Peaks(1e12, 1e12, 1e11, 2**34, "test only"))
        yield workload.resolve("tiny-moe.batch", bench, root=DATA)


def run_once(cell, seed, fault=None, control=0):
    out = io.StringIO()
    args = types.SimpleNamespace(seed=seed, seconds=1.0, trace=0,
                                 control=control)
    with contextlib.redirect_stdout(out), \
            contextlib.redirect_stderr(io.StringIO()):
        rc = run.run_cell(cell, args, jax.devices()[:1], checked_fault=fault)
    assert rc == 0
    lines = out.getvalue().strip().splitlines()
    return json.loads(lines[-1]), lines


def test_a_sound_moe_run_is_correct(cell):
    res, lines = run_once(cell, 21)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    for name, limit in cell.cfg["check"]["limits"].items():
        assert res["checks"][name]["limit"] == limit
        assert res["checks"][name]["value"] <= limit
    assert "prefill_rms_mean" in cell.cfg["check"]["limits"]
    bits = [json.loads(line.split("per-layer bits ")[1]) for line in lines
            if line.startswith("policy moe_")]
    assert len(bits) == 3                      # router, gate/up, down
    assert {b for layer in bits[1] + bits[2] for b in layer} == {2, 4}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_in_the_moe_timed_path_is_not_correct(cell, fault):
    assert run_once(cell, 22, FAULTS[fault])[0]["correct"] is False


def test_the_control_fails_where_the_moe_program_passes(cell):
    res, lines = run_once(cell, 23, control=1)
    assert res["correct"] is True
    limits = cell.cfg["check"]["limits"]
    control = {line.split()[1].rstrip(":"): float(line.split()[2])
               for line in lines if line.startswith("control ")
               and line.split()[1].rstrip(":") in limits}
    assert set(control) == set(limits)
    assert any(control[name] > limit for name, limit in limits.items())
    assert "control correct: False" in lines


def test_a_moe_run_holds_one_layer_at_a_time(cell, monkeypatch):
    """Every layer is made while no other layer's weights are alive: for
    EAGL, for packing and for the reference, three times in all."""
    e, d, f = (MOE["num_local_experts"], MOE["hidden_size"],
               MOE["moe_intermediate_size"])
    banks = {(e, d, f), (e, f, d)}
    make_layer = weights._layer_weights
    held = []

    def counted(*args, **kw):
        gc.collect()
        held.append(sum(a.shape in banks for a in jax.live_arrays()))
        return make_layer(*args, **kw)

    def whole(*args, **kw):
        raise AssertionError("the whole tree was made")

    monkeypatch.setattr(weights, "_layer_weights", counted)
    monkeypatch.setattr(weights, "make", whole)
    assert run_once(cell, 24)[0]["correct"] is True
    assert held == [0] * (3 * MOE["num_hidden_layers"])


def equal_trees(a, b):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        assert np.asarray(x).dtype == np.asarray(y).dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_packing_a_layer_at_a_time_equals_pack_params():
    from repro.serve import pack_params
    arch = serving.arch_config(MOE)
    src = weights.Source(MOE, 5)
    mixed, bits = serving.select_policy(arch, src.fetch, MOE["policy"])
    arrays = mixed.as_arrays()
    assert {b for layer in bits["moe_gateup"] for b in layer} == {2, 4}
    by_layer = serving.pack_by_layer(src, arrays, arch, 8)
    whole = pack_params(weights.make(MOE, 5), arrays, arch, cache_bits=8)
    assert by_layer["pat"].sizes == whole["pat"].sizes
    assert len(whole["pat"].sizes) > 1
    equal_trees(by_layer, whole)


@pytest.mark.parametrize("norm", ["rms", "nonparam_ln", "ln"])
def test_the_generators_layer_equals_the_stacked_trees(norm):
    cfg = dict(TINY, norm=norm, num_hidden_layers=3)
    stacked = weights.make(cfg, 2**33 + 5)
    src = weights.Source(cfg, 2**33 + 5)
    for i in range(3):
        layer = jax.tree.map(lambda a, i=i: a[i], stacked["pat"]["p0"])
        equal_trees(src.layer(i), layer)
    equal_trees(src.edges(), {k: v for k, v in stacked.items() if k != "pat"})
    got = src.layer(0)["norm1"]
    assert set(got) == {"nonparam_ln": set(), "rms": {"scale"},
                        "ln": {"scale", "bias"}}[norm]
    if norm == "ln":
        assert not np.asarray(got["bias"]).any()


def test_the_moe_generator_makes_each_expert_as_its_own_projection():
    src = weights.Source(MOE, 9)
    layer = src.layer(1)
    moe = layer["moe"]
    e, d, f = (MOE["num_local_experts"], MOE["hidden_size"],
               MOE["moe_intermediate_size"])
    assert moe["gate"]["w"].shape == (e, d, f)
    assert moe["down"]["w"].shape == (e, f, d)
    assert moe["router"]["w"].shape == (d, e)
    assert moe["gate"]["sw"].shape == moe["gate"]["sa"].shape == (e,)
    # every expert holds the same quantiles, in its own order
    w = np.asarray(moe["up"]["w"]).reshape(e, -1)
    assert all(np.array_equal(np.sort(w[0]), np.sort(x)) for x in w[1:])
    assert not np.array_equal(w[0], w[1])
    step = 2 * np.abs(w).mean(1) / np.sqrt(7.0)
    np.testing.assert_allclose(np.asarray(moe["up"]["sw"]), step, rtol=1e-6)
    r = np.asarray(moe["router"]["w"])
    np.testing.assert_allclose(float(moe["router"]["sw"]),
                               2 * np.abs(r).mean() / np.sqrt(127.0),
                               rtol=1e-6)
    assert src.layer(1) is layer               # held until another is made
    src.layer(2)
    assert src.layer(1) is not layer


class Layers:
    """A ``weights.Source`` stand-in over a whole tree."""

    def __init__(self, params, layer):
        self.params, self.convert = params, layer

    def edges(self):
        return {k: v for k, v in self.params.items() if k != "pat"}

    def layer(self, i):
        return self.convert(jax.tree.map(lambda a: a[i],
                                         self.params["pat"]["p0"]))

    def release(self):
        pass


@pytest.mark.parametrize("rounding", ["f32", "bf16"])
def test_moe_gqa_with_one_expert_equals_dense_gqa(rounding):
    act = getattr(check, rounding)
    cfg = dict(TINY, prompt_bucket=TINY["max_seq"])
    params = weights.make(cfg, 3)

    def one_expert(lp):
        m = lp.pop("mlp")
        lp["moe"] = {k: jax.tree.map(lambda a: a[None], m[k])
                     for k in ("gate", "up", "down")}
        lp["moe"]["router"] = {"w": jnp.ones((cfg["hidden_size"], 1)),
                               "sw": jnp.float32(0.01),
                               "sa": jnp.float32(0.05)}
        return lp

    dense_bits = {"attn_qkv": [4, 2], "attn_wo": [2, 4],
                  "mlp_gateup": [4, 2], "mlp_down": [2, 2]}
    moe_bits = {"attn_qkv": [4, 2], "attn_wo": [2, 4], "moe_router": [8, 8],
                "moe_gateup": [[4], [2]], "moe_down": [[2], [2]]}
    moe_cfg = dict(cfg, num_local_experts=1, num_experts_per_tok=1,
                   moe_intermediate_size=cfg["intermediate_size"])
    fed = np.random.default_rng(0).integers(0, cfg["vocab_size"], 40)
    seq = np.zeros(cfg["max_seq"], np.int32)
    seq[:40] = fed
    want, want_kv = dense_gqa.logits(cfg, params, dense_bits, seq, 30, act, 1)
    [[(got, got_kv)]] = list(moe_gqa.logits_by_layer(
        moe_cfg, Layers(params, one_expert), moe_bits, [(fed.tolist(), 30)],
        [act], 1))
    assert got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for g, w in zip(got_kv, want_kv):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_moe_gqa_clamps_q_k_and_v_where_the_configuration_says():
    params = weights.make(MOE, 4)
    a = jax.tree.map(lambda x: x[0], params["pat"]["p0"]["attn"])
    h = jax.random.normal(jax.random.PRNGKey(0), (16, MOE["hidden_size"]))
    plain = moe_gqa._qkv(MOE, check.f32, h, a, 4.0)
    clip = 0.5 * float(np.abs(np.asarray(plain[0])).max())
    clamped = moe_gqa._qkv(dict(MOE, clip_qkv=clip), check.f32, h, a, 4.0)
    for got, y in zip(clamped, plain):
        assert np.abs(np.asarray(y)).max() > clip
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(jnp.clip(y, -clip, clip)))
    # read from the configuration's ``arch`` object, and absent without it
    assert dict(moe_gqa._frozen(dict(MOE, arch={"clip_qkv": 8.0})))[
        "clip_qkv"] == 8.0
    assert dict(moe_gqa._frozen(MOE))["clip_qkv"] is None


def test_arch_config_reads_the_expert_keys_and_refuses_others():
    from repro.models.common import BlockDef
    arch = serving.arch_config(MOE)
    assert (arch.n_experts, arch.top_k, arch.d_ff) == (8, 2, 256)
    assert arch.pattern == (BlockDef("gqa", "moe"),)
    assert arch.norm == "ln" and arch.capacity_factor == 4.0
    with pytest.raises(ValueError, match="unknown configuration keys"):
        serving.arch_config(dict(MOE, n_shared_experts=2))
    with pytest.raises(ValueError, match="prefix of dense layers"):
        serving.arch_config(dict(MOE, model="deepseek-v3-671b"))
    with pytest.raises(ValueError, match="expert model needs"):
        serving.arch_config({k: v for k, v in MOE.items()
                             if k not in weights.EXPERT_KEYS})
    for config in ("olmo-1b.mixed42.int8kv", "internlm2-1.8b.mixed42.int8kv"):
        dense = workload.load_json(workload.HERE, "configs", config)
        arch = serving.arch_config(dense)
        assert (arch.pattern[0].ffn, arch.n_experts) == ("swiglu", 0)
        assert arch.d_ff == dense["intermediate_size"]


def tree_hash(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode() + str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("config", ["olmo-1b.mixed42.int8kv",
                                    "internlm2-1.8b.mixed42.int8kv"])
def test_the_committed_configurations_trees_are_the_parents(config):
    with open(os.path.join(DATA, "tree_hashes.json")) as f:
        rec = json.load(f)
    cfg = dict(workload.load_json(workload.HERE, "configs", config),
               **rec["cut"])
    assert tree_hash(weights.make(cfg, rec["seed"])) == rec["hashes"][config]
