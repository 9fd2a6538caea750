"""The comparison that decides ``correct`` fails what it must.

Whole runs of ``run.run_cell`` on the CPU at a size a test holds
(``data/configs/tiny.json``: olmo-1b's block, 2 layers, d 128, float32,
4 slots, a backlog that keeps every slot busy), past the harness's look
for a chip: a sound run is correct; each fault a one-chip serving cell can
have, planted in the timed path, makes it not correct; and the control --
the reference computed in bfloat16 in the program's place -- reads past the
limit that sound runs stay under.  The tiny configuration's limit is its
own, set from its readings the way PERF.md sets the cells'.
"""
import contextlib
import io
import json
import os
import types

import jax
import pytest

from benchmarks.chip import faults, peaks, run
from benchmarks.chip import workload

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
FAULTS = faults.for_config(workload.load_json(DATA, "configs", "tiny"))


@pytest.fixture(scope="module")
def cell():
    bench = {"workloads": [{"name": "tiny.batch", "config": "tiny",
                            "traffic": "tiny", "chips": 1}],
             "end_to_end": [{"name": "setup_s", "unit": "s"},
                            {"name": "output_tok_s", "unit": "tokens/s"}],
             "per_layer": []}
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(peaks.PEAKS, "cpu",
                   peaks.Peaks(1e12, 1e12, 1e11, 2**34, "test only"))
        yield workload.resolve("tiny.batch", bench, root=DATA)


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


def test_a_sound_run_is_correct(cell):
    res, _ = run_once(cell, 21)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    limits = cell.cfg["check"]["limits"]
    assert list(res["checks"]) == ["served_gap_max", "served_gap_mean",
                                   "prefill_rms_max", "prefill_rms_mean",
                                   "prefill_kv_token_mean", "decode_kv_token_mean"]
    for name, limit in limits.items():
        assert res["checks"][name]["limit"] == limit
        assert res["checks"][name]["value"] <= limit


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_in_the_timed_path_is_not_correct(cell, fault):
    assert run_once(cell, 22, FAULTS[fault])[0]["correct"] is False


def test_the_control_fails_where_the_program_passes(cell):
    res, lines = run_once(cell, 23, control=1)
    assert res["correct"] is True
    limits = cell.cfg["check"]["limits"]
    control = {line.split()[1].rstrip(":"): float(line.split()[2])
               for line in lines if line.startswith("control ")
               and line.split()[1].rstrip(":") in limits}
    assert set(control) == set(limits)
    assert any(control[name] > limit for name, limit in limits.items())
    assert "control correct: False" in lines
