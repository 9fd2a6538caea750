"""program_spans.py on recorded traces whose answers are worked by hand.

``data/synthetic_trace_spans.textproto`` is ``synthetic_trace.textproto``
(its TPU plane and ``bench.*`` spans; see test_trace_reduce.py) with the
program's ``serve.*`` spans on the same thread.  Times in microseconds
inside the 1000 us window; chip 0 is idle 0-60, 140-260, 800-820 and
840-1000:

  serve.admit  10-100 (r0, tokens 100, padded 256): prefill 20-50,
               cache_write 50-80, first_token 80-95
  serve.admit  100-210 (r1, tokens 200, padded 256): prefill 105-130,
               cache_write 130-190, first_token 190-205
  serve.decode 230-880 (live 3, slots 4, steps 16): prepare 230-250,
               dispatch 250-270, sync 270-805, harvest 805-870
  serve.admit  1100-1150 (r2, tokens 999, padded 1024), past the window
"""
import json
import os

import numpy as np
import pytest

from benchmarks.chip import program_spans as ps
from benchmarks.chip import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _xplane(tmp_path_factory, name):
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, f"{name}.textproto")) as f:
        xspace = ProfileData.text_proto_to_serialized_xspace(f.read())
    path = tmp_path_factory.mktemp(name) / "host.xplane.pb"
    path.write_bytes(xspace)
    return str(path)


@pytest.fixture(scope="module")
def spans_path(tmp_path_factory):
    return _xplane(tmp_path_factory, "synthetic_trace_spans")


@pytest.fixture(scope="module")
def sp(spans_path):
    return ps.reduce(spans_path)


def test_idle_time_is_partitioned_by_innermost_span(sp):
    us = {n: round(v / 1e3, 6) for n, v in sp.idle_ns.items()}
    # 0-10 and 210-230 under no program span: the gaps' harness spans
    # (140-260 is bench.admit's in trace.py); 880-1000 likewise bench.idle
    assert us == {
        "bench.admit": 30, "serve.admit": 15, "serve.admit.prefill": 30,
        "serve.admit.cache_write": 60, "serve.admit.first_token": 15,
        "serve.decode.prepare": 20, "serve.decode.dispatch": 10,
        "serve.decode.sync": 5, "serve.decode.harvest": 45,
        "serve.decode": 10, "bench.idle": 120}
    tr_window = sp.window_ns[1] - sp.window_ns[0]
    assert sum(sp.idle_ns.values()) == pytest.approx(tr_window - 640e3)
    assert list(ps.idle_by_span(sp))[:2] == ["bench.idle",
                                             "serve.admit.cache_write"]


def test_gaps_are_named_by_harness_and_program_span(sp):
    assert sorted((round(ns / 1e3), n) for n, ns in sp.gaps) == [
        (20, "bench.decode_round/serve.decode.harvest"),
        (60, "bench.admit/serve.admit.prefill"),
        (120, "bench.admit/serve.admit.cache_write"),
        (160, "bench.idle/serve.decode.harvest")]
    assert ps.longest_gaps(sp, top=1) == [
        ["bench.idle/serve.decode.harvest", pytest.approx(160e-6)]]


def test_counts_and_stats_of_spans_starting_in_the_window(sp):
    assert sp.counts == {
        "serve.admit": 2, "serve.admit.prefill": 2,
        "serve.admit.cache_write": 2, "serve.admit.first_token": 2,
        "serve.decode": 1, "serve.decode.prepare": 1,
        "serve.decode.dispatch": 1, "serve.decode.sync": 1,
        "serve.decode.harvest": 1}
    assert sp.meta["serve.admit"] == {"tokens": 300, "padded": 512}
    assert sp.meta["serve.decode"] == {"live": 3, "slots": 4, "steps": 16}


def test_readers(sp):
    # idle 360 us, of which bench.admit 30 and bench.idle 120
    assert ps.program_idle_share(sp) == pytest.approx(21.0)
    # serve.admit subtree 15 + 30 + 60 + 15 us over two admissions
    assert ps.admit_idle_ms(sp) == pytest.approx(0.060)
    assert ps.prefill_pad_share(sp) == pytest.approx(100 * 212 / 512)


def test_program_spans_leave_the_harness_readings_alone(spans_path,
                                                        tmp_path_factory):
    old = trace.reduce(_xplane(tmp_path_factory, "synthetic_trace"))
    new = trace.reduce(spans_path)
    assert new == old


def test_readers_find_nothing_without_program_spans(tmp_path_factory):
    sp = ps.reduce(_xplane(tmp_path_factory, "synthetic_trace"))
    assert sp.counts == {} and sp.meta == {}
    assert set(sp.idle_ns) == {"bench.admit", "bench.decode_round",
                               "bench.idle"}
    assert [n for n, _ in sp.gaps] == [n for n, _ in trace.reduce(
        _xplane(tmp_path_factory, "synthetic_trace")).gaps]
    for reader in (ps.program_idle_share, ps.admit_idle_ms,
                   ps.prefill_pad_share):
        assert reader(sp) is None


def test_command_line_prints_the_readings(spans_path, capsys):
    assert ps.main([os.path.dirname(spans_path)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["program_idle_share"] == pytest.approx(21.0)
    assert out["idle_by_span_s"]["serve.admit.cache_write"] == \
        pytest.approx(60e-6)


def test_innermost_pieces_match_a_sweep_over_nested_spans():
    rng = np.random.default_rng(0)

    def nest(lo, hi, depth, out):
        t = lo
        while depth < 4 and t < hi - 2:
            s = int(rng.integers(t, hi - 1))
            e = int(rng.integers(s + 1, hi + 1))
            out.append((f"s{len(out)}", float(s), float(e)))
            nest(s, e, depth + 1, out)
            t = e
        return out

    for _ in range(20):
        spans = nest(0, 200, 0, [])
        want = {}
        for t in range(200):       # each unit instant [t, t + 1)
            cover = [(s, -e, n) for n, s, e in spans
                     if s <= t and t + 1 <= e]
            if cover:
                name = max(cover)[2]
                want[name] = want.get(name, 0) + 1
        pieces = ps._innermost(spans)
        got = ps._overlaps(pieces, [p[0] for p in pieces], 0.0, 200.0)
        assert got == want
