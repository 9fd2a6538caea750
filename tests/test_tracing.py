"""The scheduler's ``serve.*`` spans (serve/tracing.py), read back from a
profiler trace.

Each test serves a few requests on the tiny model twice with one engine:
once with no profiler session, for the reference completions, and once
under ``jax.profiler.start_trace``.  The trace is read with
``ProfileData`` and its ``serve.*`` host events are nested by
containment: the test checks each parent's children and their order, the
metadata, and that tracing changed no completion.
"""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import transformer as tf
from repro.parallel.context import local_context
from repro.serve import (ContinuousBatchingScheduler, DraftSpec, EngineSpec,
                         Request, ServeEngine, quantize_for_serving, tracing)

BUCKET = 16
N_SLOTS = 2
DECODE = ["serve.decode.prepare", "serve.decode.dispatch",
          "serve.decode.sync", "serve.decode.harvest"]
WHOLE = ["serve.admit.prefill", "serve.admit.cache_write",
         "serve.admit.first_token"]


@pytest.fixture(scope="module")
def setup():
    cfg = configs.get_config("olmo-1b").smoke()
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    policy = tf.build_policy(cfg)
    pa = jax.tree.map(jnp.asarray, policy.as_arrays())
    qparams = quantize_for_serving(params, policy.as_arrays(), cfg)
    return cfg, pa, qparams


def _engine(setup, **kw):
    cfg, pa, qparams = setup
    return ServeEngine(cfg=cfg, params=qparams, policy_arrays=pa,
                       ctx=local_context(), max_seq=64,
                       spec=EngineSpec(**kw))


def _requests(cfg):
    """A 16-token prompt P, a 23-token prompt, P plus 7 tokens and P
    again: on a paged cache a miss, a miss, a page-aligned prefix hit and
    an identical-prompt hit."""
    rng = np.random.default_rng(3)
    p = rng.integers(0, cfg.vocab, 16).tolist()
    prompts = [p, rng.integers(0, cfg.vocab, 23).tolist(),
               p + rng.integers(0, cfg.vocab, 7).tolist(), p]
    return [Request(uid=f"r{i}", prompt=pr, max_new_tokens=n)
            for i, (pr, n) in enumerate(zip(prompts, (6, 4, 9, 3)))]


def _serve(engine, reqs):
    sched = ContinuousBatchingScheduler(engine, n_slots=N_SLOTS,
                                        prompt_bucket=BUCKET)
    for r in reqs:
        sched.submit(r)
    return {u: c.tokens for u, c in sched.run().items()}


def _span_forest(path):
    """The trace's ``serve.*`` host events as nested dicts (name, start,
    end, stats, children), nested by containment."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    events = [(ev.start_ns, ev.end_ns, ev.name, dict(ev.stats))
              for plane in data.planes if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith("serve.")]
    roots, stack = [], []
    for s, e, name, stats in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        node = {"name": name, "start": s, "end": e, "stats": stats,
                "children": []}
        while stack and stack[-1]["end"] <= s:
            stack.pop()
        if stack:
            assert e <= stack[-1]["end"], (name, stack[-1]["name"])
            stack[-1]["children"].append(node)
        else:
            roots.append(node)
        stack.append(node)
    return roots


def _traced(engine, reqs, tmp_path):
    """Serve ``reqs`` untraced, then traced: (reference completions,
    traced completions, serve.* span forest)."""
    want = _serve(engine, reqs)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        got = _serve(engine, reqs)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    return want, got, _span_forest(path)


def _names(nodes):
    return [n["name"] for n in nodes]


def _check_decode(rounds, decode_chunk=16):
    assert rounds
    for r in rounds:
        assert _names(r["children"]) == DECODE
        st = r["stats"]
        assert st["slots"] == N_SLOTS and 1 <= st["live"] <= N_SLOTS
        steps = st["steps"]
        assert steps <= decode_chunk and steps & (steps - 1) == 0


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_whole_prompt_admission_and_decode_spans(setup, tmp_path, layout):
    cfg = setup[0]
    reqs = _requests(cfg)
    want, got, roots = _traced(
        _engine(setup, cache_layout=layout, page_size=BUCKET), reqs,
        tmp_path)
    assert got == want
    admits = [r for r in roots if r["name"] == "serve.admit"]
    assert set(_names(roots)) == {"serve.admit", "serve.decode"}
    assert [a["stats"]["uid"] for a in admits] == [r.uid for r in reqs]
    if layout == "contiguous":
        counts = [(16, 16), (23, 32), (23, 32), (16, 16)]
        children = [WHOLE] * 4
    else:
        # miss, miss, page-aligned prefix hit (the 7-token suffix), and
        # an identical-prompt hit that runs no prefill
        counts = [(16, 16), (23, 32), (7, 16), (0, 0)]
        plan = ["serve.admit.plan"]
        children = [plan + WHOLE] * 3 + [plan + WHOLE[1:]]
    assert [(a["stats"]["tokens"], a["stats"]["padded"])
            for a in admits] == counts
    assert [_names(a["children"]) for a in admits] == children
    _check_decode([r for r in roots if r["name"] == "serve.decode"])


@pytest.mark.parametrize("layout,inplace", [("contiguous", 1),
                                            ("paged", 0)])
def test_decode_spans_count_in_place_dispatches(setup, tmp_path, layout,
                                                inplace):
    """``serve.decode``'s ``inplace`` field: 1 on every dispatch over a
    contiguous int8 cache, whose rows the layer scan writes in place; 0
    over paged pools, which are sliced per layer and written back.  So
    the field sums to the count of dispatches that took the in-place
    path."""
    cfg = setup[0]
    want, got, roots = _traced(
        _engine(setup, cache="quantized", cache_bits=8, cache_layout=layout,
                page_size=BUCKET), _requests(cfg), tmp_path)
    assert got == want
    rounds = [r for r in roots if r["name"] == "serve.decode"]
    _check_decode(rounds)
    assert sum(r["stats"]["inplace"] for r in rounds) \
        == inplace * len(rounds)


def test_paged_chunked_prefill_spans(setup, tmp_path):
    cfg = setup[0]
    reqs = _requests(cfg)[:2]
    want, got, roots = _traced(
        _engine(setup, cache_layout="paged", page_size=BUCKET,
                prefill_chunk=8), reqs, tmp_path)
    assert got == want
    admits = [r for r in roots if r["name"] == "serve.admit"]
    # a chunked claim maps pages only: its prompt runs in serve.fused
    assert [a["stats"] for a in admits] == [
        {"uid": r.uid, "tokens": 0, "padded": 0} for r in reqs]
    assert [_names(a["children"]) for a in admits] == [
        ["serve.admit.plan", "serve.admit.cache_write"]] * 2
    fused = [r for r in roots if r["name"] == "serve.fused"]
    # the 23-token prompt takes three 8-token chunks
    assert len(fused) >= 3
    for f in fused:
        assert _names(f["children"]) == ["serve.fused.dispatch",
                                         "serve.fused.sync"]
        assert f["stats"]["width"] == 8
        assert 1 <= f["stats"]["live"] <= N_SLOTS
    assert _names(roots)[:2] == ["serve.admit", "serve.admit"]
    _check_decode([r for r in roots if r["name"] == "serve.decode"])


def test_ngram_speculation_spans(setup, tmp_path):
    cfg = setup[0]
    reqs = _requests(cfg)
    want, got, roots = _traced(
        _engine(setup, draft=DraftSpec(kind="ngram", k=3)), reqs, tmp_path)
    assert got == want
    assert set(_names(roots)) == {"serve.admit", "serve.spec"}
    for a in (r for r in roots if r["name"] == "serve.admit"):
        assert _names(a["children"]) == WHOLE
    for s in (r for r in roots if r["name"] == "serve.spec"):
        assert _names(s["children"]) == ["serve.spec.dispatch",
                                         "serve.spec.sync"]
        assert s["stats"]["width"] == 4
        assert 1 <= s["stats"]["live"] <= N_SLOTS


def test_span_metadata_survives_separator_characters(tmp_path):
    """The profiler packs metadata into the event's name: a ``,``, ``=``
    or ``#`` in a value would split it and lose the fields after it."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tracing.span("serve.admit", uid="a,b=c#d", tokens=3) as sp:
            sp.set_metadata(padded=16)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    (root,) = _span_forest(path)
    assert root["stats"] == {"uid": "a_b_c_d", "tokens": 3, "padded": 16}
