"""The trace reduction and the readers built on it, on a recorded trace
whose answers are known.

``data/synthetic_trace.textproto`` is an XSpace laid out as a v5e trace
is (``trace.py``): a ``/device:TPU:0`` plane with ``XLA Modules``, ``XLA
Ops`` (a ``while`` spanning its body's ops) and ``Async XLA Ops`` lines,
and a ``/host:CPU`` thread carrying the harness's ``bench.*`` spans.  Times
in microseconds inside the 1000 us ``bench.window``:

  device ops   quant_matmul 60-100, fusion 100-140, kv_decode_attention
               260-500, quant_matmul 500-800, copy 820-840 (while 250-850
               is a container); one quant_matmul at 1100-1200 is outside
  programs     prefill 50-150, decode 250-850 (another at 1100-1300)
  host spans   admit 0-220, decode_round 220-900, idle 900-1000

``data/synthetic_trace_tp4.textproto`` is the same window on a four-chip
host running the tensor-parallel engine with a paged cache, under the
names a chip trace gave its programs and kernels.  Chip k (0-3):

  device ops   quant_matmul 60-100, paged_kv_decode_attention 260-500,
               psum (all-reduce) 500-(540 - 10k), quant_matmul 600-800,
               copy 820-840 (while 250-(850 - 20k) is a container)
  programs     jit__prefill_impl 50-150, jit_body 250-(850 - 20k)
"""
import os

import pytest

from benchmarks.chip import costs, peaks, trace, view
from benchmarks.chip.metrics import (decode_mfu, decode_step_ms,
                                     device_idle_share, prefill_ms_per_ktok,
                                     prefill_share, quant_matmul_roofline)
from benchmarks.chip.view import View

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def reduced(tmp_path_factory, name):
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, name)) as f:
        xspace = ProfileData.text_proto_to_serialized_xspace(f.read())
    path = tmp_path_factory.mktemp("trace") / "host.xplane.pb"
    path.write_bytes(xspace)
    assert trace.find_xplane(str(path.parent)) == str(path)
    return trace.reduce(str(path))


@pytest.fixture(scope="module")
def tr(tmp_path_factory):
    return reduced(tmp_path_factory, "synthetic_trace.textproto")


@pytest.fixture(scope="module")
def tr4(tmp_path_factory):
    return reduced(tmp_path_factory, "synthetic_trace_tp4.textproto")


def test_window_and_busy_share(tr):
    assert tr.n_chips == 1
    assert tr.window_s == pytest.approx(1000e-6)
    # leaf ops 60-140, 260-800 and 820-840; the while and the async copy
    # do not count, nor the op past the window
    assert tr.busy_s == pytest.approx(640e-6)


def test_kernel_and_program_time_by_name(tr):
    assert tr.kernel_s("quant_matmul") == pytest.approx(340e-6)
    assert tr.kernel_s("kv_decode_attention") == pytest.approx(240e-6)
    assert tr.kernel_s("copy") == pytest.approx(20e-6)
    assert "while" not in tr.op_ns and "copy-start" not in tr.op_ns
    assert tr.program_s("jit__prefill_impl") == pytest.approx(100e-6)
    assert tr.program_s("jit__decode_impl") == pytest.approx(600e-6)
    assert tr.program_runs == {"jit__prefill_impl": 1, "jit__decode_impl": 1}


def test_idle_gaps_are_attributed_to_host_spans(tr):
    gaps = sorted((round(ns / 1e3), span) for span, ns in tr.gaps)
    # 0-60 under admit; 140-260 is 80 us admit vs 40 us decode_round;
    # 800-820 under decode_round; 840-1000 is 60 decode_round vs 100 idle
    assert gaps == [(20, "bench.decode_round"), (60, "bench.admit"),
                    (120, "bench.admit"), (160, "bench.idle")]
    assert trace.idle_by_span(tr) == pytest.approx(
        {"bench.admit": 180e-6, "bench.idle": 160e-6,
         "bench.decode_round": 20e-6})
    bd = trace.breakdown(tr)
    assert bd["device_ops"][0] == ["quant_matmul", pytest.approx(340e-6)]
    assert bd["idle_gaps"][0] == ["bench.idle", pytest.approx(160e-6)]


def test_device_readers(tr):
    pk = peaks.for_kind("TPU v5 lite")
    v = View(run={}, attempted=[], peaks=pk, trace=tr,
             costs=costs.Totals(qmm_least_s=85e-6, decode_steps=12))
    assert device_idle_share.read(v) == pytest.approx(36.0)
    assert prefill_share.read(v) == pytest.approx(100 / 640 * 100)
    assert decode_step_ms.read(v) == pytest.approx(600e-6 / 12 * 1e3)
    assert quant_matmul_roofline.read(v) == pytest.approx(25.0)


def test_readers_find_nothing_without_a_trace():
    v = View(run={}, attempted=[], peaks=peaks.for_kind("TPU v5 lite"))
    for reader in (device_idle_share, prefill_share, decode_step_ms,
                   quant_matmul_roofline):
        assert reader.read(v) is None


def test_op_names():
    assert trace.op_name("%quant_matmul.312 = f32[32,8192]{1,0:T(8,128)} "
                         "custom-call(bf16[32,2048] %r)") == (
        "quant_matmul", "custom-call")
    assert trace.op_name("%while.155 = (s32[]{:T(128)}, s8[1,32]{1,0}) "
                         "while((s32[], s8[1,32]) %t)") == ("while", "while")
    assert trace.program_name("jit__decode_impl(1113120)") == \
        "jit__decode_impl"


def test_a_four_chip_trace_gives_one_chips_time(tr4):
    assert tr4.n_chips == 4
    assert tr4.window_s == pytest.approx(1000e-6)
    # chip k is busy 60-100, 260-(540 - 10k), 600-800 and 820-840
    assert tr4.busy_s == pytest.approx((540 + 530 + 520 + 510) / 4 * 1e-6)
    # each program's and each op's time is the mean over the chips
    assert tr4.program_s(view.MESH_DECODE) == pytest.approx(570e-6)
    assert tr4.program_s(view.PREFILL) == pytest.approx(100e-6)
    assert tr4.kernel_s("paged_kv_decode_attention") == pytest.approx(240e-6)
    assert tr4.kernel_s("psum") == pytest.approx(25e-6)
    assert tr4.kernel_s("quant_matmul") == pytest.approx(240e-6)
    assert "while" not in tr4.op_ns and "copy-start" not in tr4.op_ns
    # runs are chip 0's; the idle gaps are chip 0's
    assert tr4.program_runs == {view.PREFILL: 1, view.MESH_DECODE: 1}
    gaps = sorted((round(ns / 1e3), span) for span, ns in tr4.gaps)
    assert gaps == [(20, "bench.decode_round"), (60, "bench.admit"),
                    (60, "bench.decode_round"), (160, "bench.admit"),
                    (160, "bench.idle")]


def test_the_readers_find_the_mesh_engines_programs(tr4):
    pk = peaks.for_kind("TPU v5 lite")
    c = costs.Totals(qmm_least_s=60e-6, decode_flops=1.0e9,
                     decode_steps=12, prefill_tokens=200)
    v = View(run={}, attempted=[], peaks=pk, trace=tr4, costs=c,
             decode_program=view.MESH_DECODE)
    assert decode_step_ms.read(v) == pytest.approx(570e-6 / 12 * 1e3)
    assert decode_mfu.read(v) == pytest.approx(
        100 * 1.0e9 / (570e-6 * 197e12))
    assert prefill_share.read(v) == pytest.approx(100 / 525 * 100)
    assert prefill_ms_per_ktok.read(v) == pytest.approx(100e-3 / 0.2)
    assert device_idle_share.read(v) == pytest.approx(47.5)
    assert quant_matmul_roofline.read(v) == pytest.approx(25.0)
    # under the one-chip engine's decode name the mesh trace has no decode
    one_chip = View(run={}, attempted=[], peaks=pk, trace=tr4, costs=c)
    assert decode_step_ms.read(one_chip) is None
    assert decode_mfu.read(one_chip) is None
