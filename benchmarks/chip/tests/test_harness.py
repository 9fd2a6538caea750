"""The harness finds everything by name, its traffic is a function of the
seed, and its entry point refuses to run without a chip."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.chip import traffic, workload

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def bench():
    return workload.load_benchmark()


def test_traffic_is_a_function_of_the_seed():
    mix = workload.load_json(workload.HERE, "traffic", "chat")
    a = traffic.generate(mix, 7, 30.0, 50304)
    b = traffic.generate(mix, 7, 30.0, 50304)
    c = traffic.generate(mix, 2**33 + 7, 30.0, 50304)
    assert a == b
    assert [r.prompt for r in a] != [r.prompt for r in c]
    # every seed gets the same sizes and gaps, in another order
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
    assert sorted(r.n_out for r in a) == sorted(r.n_out for r in c)
    assert a[-1].due_s == pytest.approx(c[-1].due_s)
    assert [r.due_s for r in a] != [r.due_s for r in c]


def test_chat_traffic_matches_its_parameters():
    mix = workload.load_json(workload.HERE, "traffic", "chat")
    rate = mix["arrival"]["rate_per_s"]
    reqs = traffic.generate(mix, 11, 200.0, 1000)
    assert len(reqs) == round(rate * 200.0)
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.n_out for r in reqs])
    assert p.min() >= mix["prompt"]["min"] and p.max() <= mix["prompt"]["max"]
    assert o.min() >= mix["output"]["min"] and o.max() <= mix["output"]["max"]
    assert abs(np.median(p) - mix["prompt"]["median"]) <= 2
    assert abs(np.median(o) - mix["output"]["median"]) <= 2
    gaps = np.diff([0.0] + [r.due_s for r in reqs])
    assert gaps.mean() == pytest.approx(1 / rate, rel=0.05)
    # gamma shape k has a coefficient of variation of 1/sqrt(k)
    cv = gaps.std() / gaps.mean()
    assert cv == pytest.approx(mix["arrival"]["shape"] ** -0.5, rel=0.1)
    assert all(0 <= t < 1000 for r in reqs for t in r.prompt)


def test_backlog_traffic_is_due_at_the_start():
    mix = workload.load_json(workload.HERE, "traffic", "longdoc")
    reqs = traffic.generate(mix, 3, 45.0, 92544)
    assert len(reqs) == mix["arrival"]["n_requests"]
    assert {r.due_s for r in reqs} == {0.0}
    p = [len(r.prompt) for r in reqs]
    assert min(p) == mix["prompt"]["min"] and max(p) == mix["prompt"]["max"]
    assert traffic.prompt_widths(mix, 256, 2048) == [1024, 1280, 1536]


def test_every_workload_resolves_by_name(bench):
    names = {c["name"]: c for c in bench["configs"]}
    for w in bench["workloads"]:
        cell = workload.resolve(w["name"], bench)
        assert os.path.samefile(
            os.path.join(workload.REPO, names[w["config"]]["file"]),
            os.path.join(workload.HERE, "configs", f"{w['config']}.json"))
        assert cell.cfg["reduced"] == names[w["config"]]["reduced"]
        assert workload.SETUP in {m["name"] for m in cell.end_to_end}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        moved = {m["name"] for m in cell.end_to_end}
        for m in cell.per_layer:
            assert m["moves"] in moved, (w["name"], m["name"])
        for m in cell.end_to_end + cell.per_layer:
            if m["name"] != workload.SETUP:
                assert callable(workload.reader(m["name"]))
    assert workload.reader_path("decode_mfu.chat").endswith("decode_mfu.py")


def test_a_config_and_a_mix_in_another_directory_are_found(tmp_path):
    src = os.path.join(HERE, "data")
    for kind, name in (("configs", "tiny"), ("traffic", "tiny")):
        (tmp_path / kind).mkdir()
        with open(os.path.join(src, kind, f"{name}.json")) as f:
            body = json.load(f)
        with open(tmp_path / kind / "added.json", "w") as f:
            json.dump(body, f)
    bench = {"workloads": [{"name": "added.cell", "config": "added",
                            "traffic": "added", "chips": 1}],
             "end_to_end": [{"name": "setup_s"},
                            {"name": "tpot_p95_ms",
                             "workloads": ["added.cell"]}],
             "per_layer": [{"name": "decode_mfu.x",
                            "workloads": ["other"]}]}
    cell = workload.resolve("added.cell", bench, root=str(tmp_path))
    assert cell.cfg["hidden_size"] == 128 and cell.mix["check_tokens"] == 60
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "tpot_p95_ms"]
    assert cell.per_layer == []
    with pytest.raises(KeyError):
        workload.resolve("missing", bench, root=str(tmp_path))


def test_entry_point_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(workload.HERE, "run.py"),
         "--workload", "olmo-1b.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=workload.REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


@pytest.mark.parametrize("mix_name,bucket,max_seq,page", [
    ("chat", 256, 2048, 16), ("longdoc", 256, 2048, 16),
    ("tiny", 32, 128, 16)])
def test_page_writes_cover_every_paged_admission_shape(mix_name, bucket,
                                                       max_seq, page):
    root = os.path.join(HERE, "data") if mix_name == "tiny" else workload.HERE
    mix = workload.load_json(root, "traffic", mix_name)

    def shape(p, o):              # (padded width, pages written)
        w = min(-(-p // bucket) * bucket, max_seq)
        return w, min(-(-w // page), -(-(p + o) // page))

    want = {shape(p, o)
            for p in range(mix["prompt"]["min"], mix["prompt"]["max"] + 1)
            for o in range(mix["output"]["min"], mix["output"]["max"] + 1)}
    pairs = traffic.page_writes(mix, bucket, max_seq, page)
    assert sorted(shape(p, o) for p, o in pairs) == sorted(want)
    for p, o in pairs:
        assert mix["prompt"]["min"] <= p <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= o <= mix["output"]["max"]


def test_a_mesh_must_be_the_cells_chips():
    import jax
    from benchmarks.chip import serving
    one = jax.devices()[:1]
    assert serving.mesh_for({"model": "olmo-1b"}, one) is None
    with pytest.raises(ValueError, match="cell's chips"):
        serving.mesh_for({"mesh": {"model": 4}}, one)
    with pytest.raises(ValueError, match="only a 'model' axis"):
        serving.mesh_for({"mesh": {"data": 1}}, one)
    mesh = serving.mesh_for({"mesh": {"model": 1}}, one)
    assert dict(mesh.shape) == {"model": 1}
