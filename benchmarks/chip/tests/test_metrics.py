"""The arithmetic of the end-to-end metrics, the kernel costs and the peaks
table, on synthetic inputs with answers worked out by hand."""
import dataclasses
import json
import os

import pytest

from benchmarks.chip import costs, e2e, peaks, serving, workload
from benchmarks.chip.metrics import output_tok_s, tpot_p95_ms
from benchmarks.chip.view import View

OLMO = {"hidden_size": 2048, "num_hidden_layers": 16,
        "num_attention_heads": 16, "num_key_value_heads": 16, "head_dim": 128,
        "intermediate_size": 8192, "vocab_size": 50304,
        "engine": {"cache_bits": 8}}


DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def served(uid, due, first, last, n_out, n_done, n_in_window, admit=None):
    return e2e.Served(uid=uid, due=due, n_out=n_out, prompt_len=8,
                      noticed=due, admit_start=admit if admit else due,
                      t_first=first, t_last=last, n_done=n_done,
                      n_in_window=n_in_window)


def run_of(reqs, t_open=100.0, seconds=10.0, t_end=130.0, backlog=False):
    return {"requests": reqs, "rounds": [], "t_open": t_open,
            "t_close": t_open + seconds, "t_end": t_end,
            "closed_at": t_open + seconds, "seconds": seconds,
            "backlog": backlog, "min_waiting": None}


def view_of(run):
    return View(run=run, attempted=serving.attempted(run),
                peaks=peaks.for_kind("TPU v5 lite"))


def test_p95_is_nearest_rank():
    assert e2e.p95(list(range(1, 101))) == 95
    assert e2e.p95([3.0]) == 3.0
    assert e2e.p95(list(range(1, 21))) == 19
    with pytest.raises(ValueError):
        e2e.p95([])


def test_ttft_and_tpot_p95_over_requests():
    # 20 requests due 0.5 s apart; request i waits i * 10 ms for its first
    # token and then streams 11 tokens at (i + 1) ms per token.
    reqs = [served(f"r{i}", 100.0 + i * 0.5, 100.0 + i * 0.5 + i * 0.01,
                   100.0 + i * 0.5 + i * 0.01 + 10 * (i + 1) * 1e-3,
                   n_out=11, n_done=11, n_in_window=11)
            for i in range(20)]
    v = view_of(run_of(reqs))
    # nearest rank: the 19th of 20 sorted values -> i = 18; the wait for
    # the first token does not enter the stream rate
    assert tpot_p95_ms.read(v) == pytest.approx(19.0)


def test_output_tok_s_counts_only_tokens_delivered_in_the_window():
    reqs = [served("a", 100.0, 101.0, 112.0, n_out=50, n_done=50,
                   n_in_window=40),
            served("b", 105.0, 106.0, 109.0, n_out=20, n_done=20,
                   n_in_window=20),
            # due after the close: not attempted, delivered nothing in it
            served("c", 111.0, 112.0, 113.0, n_out=5, n_done=5,
                   n_in_window=0)]
    v = view_of(run_of(reqs))
    assert output_tok_s.read(v) == pytest.approx((40 + 20) / 10.0)
    assert [r.uid for r in v.attempted] == ["a", "b"]


def test_request_unfinished_after_the_drain_counts_as_failed():
    ok = served("ok", 101.0, 101.5, 104.0, n_out=6, n_done=6, n_in_window=6)
    cut = served("cut", 102.0, 102.5, 125.0, n_out=100, n_done=40,
                 n_in_window=30)
    never = e2e.Served(uid="never", due=109.0, n_out=10, prompt_len=8,
                       noticed=109.0)
    run = run_of([ok, cut, never], t_end=130.0)
    att = serving.attempted(run)
    assert [r.uid for r in att if not r.finished] == ["cut", "never"]
    # a request never admitted streams nothing from its due time on: it
    # is charged that wait, not 0
    assert e2e.tpot_ms([cut, never], 130.0) == pytest.approx(
        [27.5 / 99 * 1e3, 21.0 / 9 * 1e3])


def test_backlog_attempts_only_what_was_admitted_by_the_close():
    a = served("a", 100.0, 100.2, 120.0, n_out=9, n_done=5, n_in_window=5,
               admit=100.1)
    b = e2e.Served(uid="b", due=100.0, n_out=9, prompt_len=8)
    run = run_of([a, b], backlog=True)
    assert [r.uid for r in serving.attempted(run)] == ["a"]


def test_quant_matmul_costs_at_olmo_widths():
    # decode, 32 rows through the 2048 x 8192 gate projection at 4 bits
    flops, data = costs.quant_matmul(32, 2048, 8192, 4)
    assert flops == 2 * 32 * 2048 * 8192 == 1_073_741_824
    assert data == (2048 * 8192 // 2 + 8192 * 4 + 32 * 2048 * 2
                    + 32 * 8192 * 2) == 9_076_736
    # at 2 bits the codes are half as many bytes
    assert costs.quant_matmul(32, 2048, 8192, 2)[1] == data - 2048 * 8192 // 4


def test_decode_attention_costs_at_olmo_widths():
    dims = costs.Dims.of(OLMO)
    flops, data = costs.decode_attention([400, 1000], dims)
    assert flops == 4 * 16 * 128 * 1400 == 11_468_800
    per_row = 2 * 16 * 128 * 1 + 16 * 4              # K and V codes, V scale
    fixed = 16 * 128 * 4 + 2 * 16 * 128 * 2           # K scales, q and out
    assert data == 1400 * per_row + 2 * fixed == 5_856_768


def test_token_flops_and_round_totals():
    dims = costs.Dims.of(OLMO)
    layer = 2048 * 2048 * 4 + 3 * 2048 * 8192
    assert dims.proj_params == 16 * layer
    assert costs.token_flops(dims, 100) == (2 * 16 * layer + 2 * 2048 * 50304
                                            + 4 * 16 * 16 * 128 * 100)
    bits = {s: [4] * 16 for s in ("attn_qkv", "attn_wo", "mlp_gateup",
                                  "mlp_down")}
    rounds = [{"kind": "prefill", "tokens": 300},
              {"kind": "decode", "steps": 4, "rows": [[10, 3], [20, 1]]}]
    t = costs.totals(rounds, dims, bits, 197e12, 819e9)
    assert (t.prefill_tokens, t.decode_tokens, t.decode_steps) == (300, 4, 4)
    # rows attend 11, 12, 13 and 21 rows
    assert t.decode_flops == sum(costs.token_flops(dims, c)
                                 for c in (11, 12, 13, 21))
    one = costs.least_time(*costs.decode_attention([11, 21], dims), 197e12,
                           819e9)
    assert t.attn_least_s > 16 * one


def test_least_time_takes_the_binding_roof():
    assert costs.least_time(197e12, 1.0, 197e12, 819e9) == pytest.approx(1.0)
    assert costs.least_time(1.0, 819e9, 197e12, 819e9) == pytest.approx(1.0)


def test_peaks_raise_on_an_unknown_device_kind():
    assert peaks.for_kind("TPU v5 lite").hbm_bytes == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        peaks.for_kind("TPU v4")


def recorded_rounds():
    with open(os.path.join(DATA, "rounds_tiny.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("config", ["olmo-1b.mixed42.int8kv",
                                    "internlm2-1.8b.mixed42.int8kv"])
def test_costs_without_a_mesh_equal_the_recorded_totals(config):
    rec = recorded_rounds()
    case = rec["cases"][config]
    dims = costs.Dims.of(workload.load_json(workload.HERE, "configs", config))
    assert dims.shards == 1
    got = costs.totals(rec["rounds"], dims, case["bits"], 197e12, 819e9)
    assert dataclasses.asdict(got) == case["totals"]


def test_one_chips_share_times_four_less_the_replicated_part_is_the_whole():
    whole = costs.Dims.of(OLMO)
    chip = costs.Dims.of(dict(OLMO, mesh={"model": 4}))
    assert chip.shards == 4 and (chip.hq_chip, chip.hkv_chip) == (4, 4)
    m = 32
    for (_, k, n), (_, k4, n4) in zip(whole.projections(),
                                       chip.projections()):
        f1, b1 = costs.quant_matmul(m, k, n, 4)
        f4, b4 = costs.quant_matmul(m, k4, n4, 4)
        assert 4 * f4 == f1
        if k4 == k:         # column: N split, the whole input read
            assert n4 * 4 == n
            replicated = m * k * costs.BF16
        else:               # row: K split, scales and partial output whole
            assert (k4 * 4, n4) == (k, n)
            replicated = n * costs.F32 + m * n * costs.BF16
        assert 4 * b4 - 3 * replicated == b1
    assert 4 * chip.proj_params == whole.proj_params
    fa, ba = costs.decode_attention([400, 1000], whole)
    fa4, ba4 = costs.decode_attention([400, 1000], chip)
    assert (4 * fa4, 4 * ba4) == (fa, ba)
    head = 2 * 2048 * 50304             # the LM head, on every chip
    assert 4 * costs.token_flops(chip, 100) - 3 * head == \
        costs.token_flops(whole, 100)
    rec = recorded_rounds()
    bits = rec["cases"]["olmo-1b.mixed42.int8kv"]["bits"]
    t1 = costs.totals(rec["rounds"], whole, bits, 197e12, 819e9)
    t4 = costs.totals(rec["rounds"], chip, bits, 197e12, 819e9)
    assert 4 * t4.decode_flops - 3 * head * t4.decode_tokens == \
        pytest.approx(t1.decode_flops, rel=1e-12)
    assert (t4.decode_tokens, t4.decode_steps, t4.prefill_tokens) == (
        t1.decode_tokens, t1.decode_steps, t1.prefill_tokens)
    assert t4.attn_least_s == pytest.approx(t1.attn_least_s / 4, rel=1e-12)


DBRX = {"hidden_size": 6144, "num_hidden_layers": 4,
        "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
        "num_local_experts": 16, "num_experts_per_tok": 4,
        "moe_intermediate_size": 10752, "vocab_size": 100352,
        "engine": {"cache_bits": 8}}


def test_an_expert_layers_costs_follow_the_stated_rule():
    dims = costs.Dims.of(DBRX)
    d, f, e, k = 6144, 10752, 16, 4
    q, kv = 48 * 128, 8 * 128
    assert dims.projections() == [
        ("attn_qkv", d, q), ("attn_qkv", d, kv), ("attn_qkv", d, kv),
        ("attn_wo", q, d), ("moe_router", d, e)]
    # every token passes through q, k, v, o, the router and k experts
    layer = d * q + 2 * d * kv + q * d + d * e + k * 3 * d * f
    assert dims.proj_params == 4 * layer
    assert costs.token_flops(dims, 100) == (2 * 4 * layer + 2 * d * 100352
                                            + 4 * 4 * 48 * 128 * 100)
    gateup, down = [4] * 10 + [2] * 6, [2] * 16
    # one token reaches k of E experts; 32 tokens nearly all of them
    for m, reach in ((1, k / e), (32, 1 - (1 - k / e) ** 32)):
        work = costs.expert_matmuls(m, dims, gateup, down)
        codes_gu = sum(d * f * b / 8 + f * 4 for b in gateup)
        codes_dn = sum(f * d * b / 8 + d * 4 for b in down)
        rows = m * k * (d + f) * 2
        assert work == [(2 * m * k * d * f, reach * codes_gu + rows),
                        (2 * m * k * d * f, reach * codes_gu + rows),
                        (2 * m * k * f * d, reach * codes_dn + rows)]
        assert sum(fl for fl, _ in work) == 2 * m * k * 3 * d * f
    assert reach == pytest.approx(16 * (1 - 0.75 ** 32) / 16)
    bits = {"attn_qkv": [4] * 4, "attn_wo": [4] * 4, "moe_router": [8] * 4,
            "moe_gateup": [gateup] * 4, "moe_down": [down] * 4}
    rounds = [{"kind": "decode", "steps": 2, "rows": [[10, 2], [20, 1]]}]
    t = costs.totals(rounds, dims, bits, 197e12, 819e9)
    want = 0.0
    for m in (2, 1):                     # two rows, then one
        for _, kk, n in dims.projections():
            b = 8 if n == e else 4
            want += 4 * costs.least_time(*costs.quant_matmul(m, kk, n, b),
                                         197e12, 819e9)
        want += 4 * sum(costs.least_time(*w, 197e12, 819e9) for w in
                        costs.expert_matmuls(m, dims, gateup, down))
    assert t.qmm_least_s == pytest.approx(want, rel=1e-12)
    assert t.decode_flops == sum(costs.token_flops(dims, c)
                                 for c in (11, 12, 21))
