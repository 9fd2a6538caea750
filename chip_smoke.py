#!/usr/bin/env python3
"""Bring-up check: olmo-1b's packed mixed-4/2 serving path on a TPU.

    python chip_smoke.py              # one chip: the whole main path
    python chip_smoke.py --chips 4    # tensor-parallel serving only

One chip: seeded random weights at olmo-1b's published widths
(configs/olmo_1b.py: d 2048, 16 layers, 16 MHA heads x 128, d_ff 8192,
vocab 50304, bf16) -> EAGL gains through the Pallas histogram -> a mixed
4/2 policy from the knapsack at 70% of the all-4-bit cost -> packed
weights -> 16 requests of unequal length through
``ContinuousBatchingScheduler`` (8 slots, max_seq 2048, int8 quantized
KV cache).  Every check is fatal:

  * each Pallas kernel of the path against its ``kernels/ref.py`` oracle
    at the served shapes;
  * the engine's prefill logits and its first decode steps through the
    quantized cache against an f32 full-context forward of the same
    fake-quant weights (``tf.apply(mode="train")``, no kernels, no cache);
  * the tokens the scheduler served score high under that forward;
  * the compiled decode program holds the Pallas kernels
    (``tpu_custom_call``);
  * every request finishes.

``--chips 4`` runs only the tensor-parallel phase: the same model with
an int8 paged cache served over a 4-device ("model",) mesh and on one
device in the same process; both engines' prefill logits and served
tokens are held to the f32 forward, and their tokens are compared.

Lines tagged ``[set-up]`` give each phase's compile and run seconds;
they are set-up costs, not performance metrics.  The last line is one
JSON object, ``{"ok": true, "device": {...}}``; a failed check raises
before it and the exit code is non-zero.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

BUDGET_FRAC = 0.7
CACHE_BITS = 8
QMM_SHAPES = ((2048, 2048), (2048, 8192), (8192, 2048))


@dataclasses.dataclass(frozen=True)
class Run:
    """Traffic and cache geometry of one smoke run."""
    n_slots: int = 8
    max_seq: int = 2048
    n_requests: int = 16
    max_prompt: int = 1024
    new_tokens: int = 32
    prompt_bucket: int = 512     # two prefill widths (512, 1024) to compile
    page_size: int = 16
    n_checked: int = 4           # requests whose logits meet the reference
    check_steps: int = 4         # decode steps checked after the prefill
    seed: int = 0

    def pad_width(self, n: int) -> int:
        """The scheduler's padded prefill width for an n-token prompt."""
        return min(-(-n // self.prompt_bucket) * self.prompt_bucket,
                   self.max_seq)

    @property
    def ref_width(self) -> int:
        """The reference forward's one padded width: a prompt and every
        token served after it."""
        return self.max_prompt + self.new_tokens


# --- tolerances -----------------------------------------------------------
# quant_matmul vs ref.quant_matmul_w4/w2: both take the same bf16 products
# into an f32 accumulator and scale once at the end; only the summation
# order differs (f32 rounding, ~1e-6 of the output's magnitude).  Measured
# relative to max |ref|.
QMM_RTOL = 1e-4
# decode attention vs ref.kv_cache_attention / paged_kv_cache_attention:
# online softmax over row blocks vs one softmax, all in f32; outputs are
# convex combinations of V rows of magnitude ~1.  The same bound as the
# interpret-mode parity tests (tests/test_kv_quant.py).
ATTN_ATOL = 2e-3
# Served logits vs the f32 full-context forward, in units of the f32
# logits' standard deviation over the vocabulary.  Every projection
# fake-quantizes its input onto a 4- or 2-bit grid (steps ~0.76 at random
# init), so any rounding difference that moves an activation across a grid
# boundary moves it by a whole step.  bf16 compute does that often enough
# that a plain bf16 forward of the same weights, with no kernels and no
# cache, is itself 0.5-0.7 std rms (max ~4 std) from the f32 one at full
# width (CPU, 2-8 layers).  So the check measures that bf16 forward on the
# chip too, and the engine may sit at most RMS_SLACK times as far plus
# RMS_FLOOR (the int8 cache and the kernels' summation order), and never
# past RMS_CEIL: two unrelated logit vectors sit ~1.4 std apart.  Max|err|
# and top-1 agreement are printed, not gated: the bf16 forward itself
# agrees with f32 on the argmax at 0-50% of positions.
RMS_SLACK = 1.25
RMS_FLOOR = 0.1
RMS_CEIL = 1.0
# Served tokens, scored under the f32 forward teacher-forced on the
# engine's own history: z = (reference logit of the token - mean) / std
# over the vocabulary.  The reference's own argmax scores z_max (~4.3 over
# 50304 logits); a greedy engine whose logits sit e std rms from the
# reference picks tokens scoring about z_max / sqrt(1 + e^2) (0.8-0.9 z_max
# at the 0.5-0.7 std above); tokens unrelated to the reference score 0.
# The mean must reach half of z_max.
Z_FRAC = 0.5

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CheckFailed(AssertionError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise CheckFailed(msg)


class Phases:
    """Per-phase wall seconds, split into XLA compile time (JAX's own
    backend-compile events; tracing and lowering stay in the rest)."""

    def __init__(self, jax):
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, *args, **kwargs):
        if event == COMPILE_EVENT:
            self.compile_s += duration

    @contextlib.contextmanager
    def __call__(self, name: str):
        c0, t0 = self.compile_s, time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        comp = self.compile_s - c0
        print(f"[set-up] {name}: compile {comp:.1f} s, run {wall - comp:.1f} s",
              flush=True)


# ----------------------------------------------------------------- phases
def init_model(cfg, seed: int):
    import jax
    from repro.models import transformer as tf
    params = jax.jit(tf.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed))
    return jax.block_until_ready(params)


def select_policy(cfg, params):
    """EAGL gains (the Pallas histogram on the chip) -> knapsack mixed 4/2
    policy at BUDGET_FRAC of the all-4-bit cost."""
    from repro.core import knapsack
    from repro.core.metrics import eagl
    from repro.models import transformer as tf
    policy = tf.build_policy(cfg)
    gains = eagl.eagl_gains(
        policy, lambda u, t: tf.fetch_unit_tensor(params, u, t), impl="auto")
    mixed = policy.apply_selection(
        knapsack.select_for_budget(policy, gains, BUDGET_FRAC).take)
    bits = {slot: [int(b) for b in arr]
            for slot, arr in mixed.as_arrays()["pat0"].items()}
    for slot, per_layer in bits.items():
        print(f"policy {slot}: per-layer bits {per_layer}")
    used = {b for per_layer in bits.values() for b in per_layer}
    check(used == {2, 4}, f"policy is not mixed 4/2: bits used {used}")
    return mixed


def pack(cfg, params, mixed):
    from repro.serve import pack_params
    return pack_params(params, mixed.as_arrays(), cfg, cache_bits=CACHE_BITS)


def new_engine(cfg, pparams, mixed, run: Run, **spec_kw):
    import jax
    import jax.numpy as jnp
    from repro.parallel.context import local_context
    from repro.serve import EngineSpec, ServeEngine
    return ServeEngine(cfg=cfg, params=pparams,
                       policy_arrays=jax.tree.map(jnp.asarray,
                                                  mixed.as_arrays()),
                       ctx=local_context(), max_seq=run.max_seq,
                       spec=EngineSpec(weights="packed", cache="quantized",
                                       cache_bits=CACHE_BITS, **spec_kw))


def make_requests(cfg, run: Run):
    """Seeded prompts of unequal length, the first at run.max_prompt."""
    import numpy as np
    from repro.serve import Request
    rng = np.random.default_rng(run.seed)
    lens = rng.integers(16, run.max_prompt + 1, run.n_requests)
    lens[0] = run.max_prompt
    return [Request(uid=f"r{i:02d}",
                    prompt=rng.integers(0, cfg.vocab, int(n)).tolist(),
                    max_new_tokens=run.new_tokens)
            for i, n in enumerate(lens)]


def serve(engine, requests, run: Run):
    """Every request through the continuous-batching scheduler; each must
    finish with its full token budget."""
    from repro.serve import ContinuousBatchingScheduler
    sched = ContinuousBatchingScheduler(engine, n_slots=run.n_slots,
                                        prompt_bucket=run.prompt_bucket)
    for r in requests:
        sched.submit(r)
    done = sched.run()
    check(sorted(done) == sorted(r.uid for r in requests),
          f"served {len(done)} of {len(requests)} requests")
    for r in requests:
        c = done[r.uid]
        check(c.finish_reason in ("eos", "length")
              and len(c.tokens) == r.max_new_tokens,
              f"{r.uid}: finish_reason {c.finish_reason!r}, "
              f"{len(c.tokens)} tokens")
    return sched, done


def check_kernels(run: Run, qmm_shapes=QMM_SHAPES, heads: int = 16,
                  head_dim: int = 128, impl: str = "auto") -> None:
    """Each kernel of the served path against its kernels/ref.py oracle
    at the served shapes.  The oracles run at 'highest' matmul precision,
    so they are the accurate side of the comparison."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import kv_quant, ops, ref
    keys = iter(jax.random.split(jax.random.PRNGKey(run.seed), 32))
    highest = jax.default_matmul_precision("highest")
    m = run.n_slots

    for bits in (4, 2):
        lim = 2 ** (bits - 1)
        pack_w = ref.pack_w4 if bits == 4 else ref.pack_w2
        oracle = ref.quant_matmul_w4 if bits == 4 else ref.quant_matmul_w2
        for k, n in qmm_shapes:
            x = jax.random.normal(next(keys), (m, k), jnp.bfloat16)
            wp = pack_w(jax.random.randint(next(keys), (k, n), -lim, lim))
            scale = jax.random.uniform(next(keys), (n,), jnp.float32,
                                       0.01, 0.1)
            got = ops.quant_matmul(x, wp, scale, bits, impl=impl)
            with highest:
                want = oracle(x, wp, scale)
            err = float(jnp.abs(got - want).max() / jnp.abs(want).max())
            print(f"kernel quant_matmul w{bits} M={m} K={k} N={n}: "
                  f"max|err|/max|ref| {err:.3e} (tol {QMM_RTOL:g})")
            check(err <= QMM_RTOL, f"quant_matmul w{bits} {k}x{n}: {err}")

    b, s, page = run.n_slots, run.max_seq, run.page_size
    n_pages = s // page
    for bits in (8, 4):
        kv = jax.random.normal(next(keys), (2, b, s, heads, head_dim))
        lengths = jax.random.randint(next(keys), (b,), 1, s + 1)
        qc = kv_quant.quantize_prefill({"k": kv[0], "v": kv[1]}, lengths,
                                       bits)
        q = jax.random.normal(next(keys), (b, heads, head_dim))
        pos = lengths - 1
        got = ops.kv_cache_attention(q, qc["kq"], qc["k_scale"], qc["vq"],
                                     qc["v_scale"], pos, bits, impl=impl)
        with highest:
            want = ref.kv_cache_attention(q, qc["kq"], qc["k_scale"],
                                          qc["vq"], qc["v_scale"], pos, bits)
        err = float(jnp.abs(got - want).max())
        print(f"kernel kv_decode_attention int{bits} B={b} S={s} H={heads} "
              f"D={head_dim}: max|err| {err:.3e} (tol {ATTN_ATOL:g})")
        check(err <= ATTN_ATOL, f"kv_decode_attention int{bits}: {err}")

        # the same rows scattered over a shuffled physical page pool
        perm = jax.random.permutation(next(keys), b * n_pages)
        tbl = perm.reshape(b, n_pages).astype(jnp.int32)

        def pool(x):
            pages = x.reshape((b * n_pages, page) + x.shape[2:])
            return jnp.zeros_like(pages).at[perm].set(pages)

        kqp, vqp, vsp = (pool(qc[name]) for name in ("kq", "vq", "v_scale"))
        got = ops.paged_kv_cache_attention(q, kqp, qc["k_scale"], vqp, vsp,
                                           tbl, pos, bits, impl=impl)
        with highest:
            want = ref.paged_kv_cache_attention(q, kqp, qc["k_scale"], vqp,
                                                vsp, tbl, pos, bits)
        err = float(jnp.abs(got - want).max())
        print(f"kernel paged_kv_decode_attention int{bits} page={page}: "
              f"max|err| {err:.3e} (tol {ATTN_ATOL:g})")
        check(err <= ATTN_ATOL,
              f"paged_kv_decode_attention int{bits}: {err}")


@functools.lru_cache(maxsize=None)
def _reference_fn(cfg, dtype):
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as tf
    from repro.parallel.context import local_context
    cfg_ref = cfg.replace(param_dtype=dtype, compute_dtype=dtype,
                          cache_dtype=dtype)

    def fwd(p, policy_arrays, toks):
        p = jax.tree.map(lambda a: a.astype(dtype)
                         if jnp.issubdtype(a.dtype, jnp.floating) else a, p)
        logits, _, _ = tf.apply(p, policy_arrays, {"tokens": toks}, cfg_ref,
                                local_context(), mode="train")
        return logits

    return jax.jit(fwd)


def reference_logits(cfg, params, policy_arrays, tokens, dtype):
    """Full-context forward of the fake-quant weights in ``dtype``: train
    mode, no kernels, no cache; f32 runs at 'highest' matmul precision."""
    import jax
    import jax.numpy as jnp
    precision = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        return _reference_fn(cfg, dtype)(params, policy_arrays, tokens)


def prefill_logits(engine, prompt, run: Run):
    """The engine's last-position logits for one prompt, padded as the
    scheduler pads it; also returns the prefill cache rows."""
    import jax.numpy as jnp
    import numpy as np
    n = len(prompt)
    toks = np.zeros((1, run.pad_width(n)), np.int32)
    toks[0, :n] = prompt
    last, pre = engine.prefill(jnp.asarray(toks), jnp.asarray([n], jnp.int32))
    return last[0], pre


def engine_logits(engine, prompt, fed, run: Run):
    """The engine's prefill logits for ``prompt``, then one decode step
    through its quantized cache per token of ``fed``: (len(fed)+1, V)."""
    import jax.numpy as jnp
    from repro.serve import kv_cache
    last, pre = prefill_logits(engine, prompt, run)
    lengths = jnp.asarray([len(prompt)], jnp.int32)
    cache = kv_cache.splice_prefill(engine.new_cache(1), pre, lengths)
    out = [last]
    for t in fed:
        layers, _, logits = engine.verify_step(
            cache, jnp.asarray([[t]], jnp.int32))
        cache = engine.commit_verified(cache, layers,
                                       jnp.ones((1,), jnp.int32))
        out.append(logits[0, 0])
    return jnp.stack(out)


def logit_error(name, got, want) -> float:
    """Print max and rms |got - want| in units of want's std over the
    vocabulary, and argmax agreement; returns the rms.  got/want:
    (..., V)."""
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    std = float(want.std(axis=-1).mean())
    err = float(np.abs(got - want).max()) / std
    rms = float(np.sqrt(np.mean((got - want) ** 2))) / std
    top1 = float((got.argmax(-1) == want.argmax(-1)).mean())
    print(f"{name}: max|err| {err:.3f} std, rms err {rms:.3f} std, top-1 "
          f"agreement {top1:.3f} over {got[..., 0].size} positions "
          f"(reference logit std {std:.4f})")
    return rms


def check_against_reference(engine, cfg, params, requests, run: Run):
    """Teacher-forced: the engine prefills each prompt, then decodes
    run.check_steps seeded tokens through its quantized cache; the
    references score prompt + those tokens in one forward each."""
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(run.seed + 1)
    steps = run.check_steps
    got, ref32, ref16 = [], [], []
    for r in requests:
        n = len(r.prompt)
        fed = rng.integers(0, cfg.vocab, steps).tolist()
        got.append(np.asarray(engine_logits(engine, r.prompt, fed, run),
                              np.float32))
        toks = np.zeros((1, run.ref_width), np.int32)
        toks[0, :n + steps] = list(r.prompt) + fed
        for dtype, out in ((jnp.float32, ref32), (jnp.bfloat16, ref16)):
            lg = reference_logits(cfg, params, engine.policy_arrays,
                                  jnp.asarray(toks), dtype)
            out.append(np.asarray(lg[0, n - 1:n + steps], np.float32))
    got, ref32, ref16 = np.stack(got), np.stack(ref32), np.stack(ref16)
    for name, sl in (("prefill", np.s_[:, 0]),
                     (f"decode steps 1-{steps} (int{CACHE_BITS} cache)",
                      np.s_[:, 1:])):
        base = logit_error(f"{name} logits, bf16 forward vs f32 forward",
                           ref16[sl], ref32[sl])
        rms = logit_error(f"{name} logits, engine vs f32 forward",
                          got[sl], ref32[sl])
        bound = min(RMS_SLACK * base + RMS_FLOOR, RMS_CEIL)
        print(f"{name} logits: engine rms err {rms:.3f} std, bound "
              f"{bound:.3f} std")
        check(rms <= bound, f"{name} logits: rms err {rms:.3f} std past "
              f"the bound {bound:.3f}")


def check_served_tokens(name, cfg, params, policy_arrays, requests, done,
                        run: Run):
    """Score every token the scheduler served under the f32 forward,
    teacher-forced on the prompt and the engine's own earlier tokens (see
    Z_FRAC).  Returns the reference's logits at each last prompt position,
    (len(requests), V)."""
    import jax.numpy as jnp
    import numpy as np
    z_tok, z_max, prefill = [], [], []
    for r in requests:
        n, gen = len(r.prompt), list(done[r.uid].tokens)
        toks = np.zeros((1, run.ref_width), np.int32)
        toks[0, :n + len(gen) - 1] = list(r.prompt) + gen[:-1]
        lg = np.asarray(reference_logits(cfg, params, policy_arrays,
                                         jnp.asarray(toks), jnp.float32)
                        [0, n - 1:n - 1 + len(gen)], np.float64)
        z = (lg - lg.mean(-1, keepdims=True)) / lg.std(-1, keepdims=True)
        z_tok.append(z[np.arange(len(gen)), gen])
        z_max.append(z.max(-1))
        prefill.append(lg[0])
    z_tok, z_max = np.concatenate(z_tok), np.concatenate(z_max)
    bound = Z_FRAC * float(z_max.mean())
    print(f"{name}: {z_tok.size} served tokens score {z_tok.mean():.3f} std "
          f"(min {z_tok.min():.3f}) under the f32 forward, whose own argmax "
          f"scores {z_max.mean():.3f}; bound {bound:.3f}")
    check(z_tok.mean() >= bound, f"{name}: served tokens score "
          f"{z_tok.mean():.3f} std, below the bound {bound:.3f}")
    return np.stack(prefill)


def check_decode_program(engine, run: Run) -> None:
    """The compiled scanned-decode program calls the Pallas kernels."""
    import jax
    c = engine.dispatch_closures(batch=run.n_slots)["decode"]
    hlo = jax.jit(c.fn, static_argnums=c.static_argnums).lower(
        *c.args).compile().as_text()
    n = hlo.count("tpu_custom_call")
    print(f"compiled decode program: {n} tpu_custom_call sites")
    check(n > 0, "decode program holds no Pallas kernel")


def run_one_chip(cfg, run: Run, phase) -> None:
    from repro.serve import bf16_resident_weight_bytes
    with phase("init params"):
        params = init_model(cfg, run.seed)
    with phase("EAGL + knapsack"):
        mixed = select_policy(cfg, params)
    with phase("pack"):
        pparams = pack(cfg, params, mixed)
    engine = new_engine(cfg, pparams, mixed, run)
    with phase("kernels vs kernels/ref.py"):
        check_kernels(run)
    requests = make_requests(cfg, run)
    with phase(f"serve {len(requests)} requests"):
        sched, done = serve(engine, requests, run)
    print(f"served {len(done)} requests through {run.n_slots} slots: "
          f"prompt lengths {[len(r.prompt) for r in requests]}, "
          f"{run.new_tokens} new tokens each, finish reasons "
          f"{sorted({c.finish_reason for c in done.values()})}")
    rep = engine.residency(sched.cache)
    print(f"resident bytes (measured): packed weights "
          f"{rep['resident_weight_bytes']:,} (bf16 params "
          f"{bf16_resident_weight_bytes(params):,}), int{CACHE_BITS} KV "
          f"cache {rep['resident_kv_bytes']:,} for {run.n_slots} slots x "
          f"{run.max_seq}")
    del sched
    with phase("decode program HLO"):
        check_decode_program(engine, run)
    with phase("logits vs f32 reference"):
        check_against_reference(engine, cfg, params,
                                requests[:run.n_checked], run)
    with phase("served tokens vs f32 reference"):
        check_served_tokens(
            f"scheduler (scanned decode, int{CACHE_BITS} cache)", cfg,
            params, engine.policy_arrays, requests[:run.n_checked], done,
            run)


def check_spread(name, tree, n_devices: int) -> None:
    """The tree's sharded leaves put one slice, not a copy, on each of the
    mesh's devices."""
    import jax
    leaves = jax.tree.leaves(tree)
    sharded = [x for x in leaves if not x.sharding.is_fully_replicated]
    total = sum(x.nbytes for x in sharded)
    per: dict = {}
    for leaf in sharded:
        for s in leaf.addressable_shards:
            per[s.device.id] = per.get(s.device.id, 0) + s.data.nbytes
    print(f"{name}: {len(sharded)} of {len(leaves)} leaves sharded, "
          f"{total:,} bytes, per device {per}")
    check(len(per) == n_devices,
          f"{name} sit on {len(per)} devices, not {n_devices}")
    check(max(per.values()) * n_devices <= total,
          f"{name}: a device holds more than a 1/{n_devices} slice")


def run_four_chips(cfg, run: Run, phase) -> None:
    """Tensor-parallel paged serving over a 4-device mesh vs the same
    engine on one device."""
    import jax
    import numpy as np
    from repro.launch.mesh import make_mesh
    check(len(jax.devices()) >= 4,
          f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    with phase("init params"):
        params = init_model(cfg, run.seed)
    with phase("EAGL + knapsack"):
        mixed = select_policy(cfg, params)
    with phase("pack"):
        pparams = pack(cfg, params, mixed)
    paged = dict(cache_layout="paged", page_size=run.page_size)
    solo = new_engine(cfg, pparams, mixed, run, **paged)
    mesh = make_mesh((4,), ("model",), devices=jax.devices()[:4])
    with phase("shard params over the mesh"):
        tp = new_engine(cfg, pparams, mixed, run, mesh=mesh, **paged)
    requests = make_requests(cfg, run)
    with phase("serve, one device"):
        _, want = serve(solo, requests, run)
    with phase("serve, 4-device mesh"):
        sched, got = serve(tp, requests, run)

    check_spread("mesh engine params", tp.params, 4)
    check_spread("mesh engine page pools", sched.cache.layers, 4)
    del sched

    with phase("prefill logits, one device vs mesh"):
        a = np.stack([np.asarray(prefill_logits(solo, r.prompt, run)[0])
                      for r in requests])
        b = np.stack([np.asarray(prefill_logits(tp, r.prompt, run)[0])
                      for r in requests])
    logit_error("prefill logits, mesh vs one device", b, a)
    # Both engines against the f32 forward: the mesh may sit no further
    # from it than the one-device engine does, within the one-chip slack.
    # Greedy tokens are scored, not matched: the two engines round
    # differently, and one parted token gives them different histories.
    checked = requests[:run.n_checked]
    with phase("served tokens vs f32 reference"):
        ref = check_served_tokens("one device", cfg, params,
                                  solo.policy_arrays, checked, want, run)
        check_served_tokens("4-device mesh", cfg, params, tp.policy_arrays,
                            checked, got, run)
    base = logit_error("prefill logits, one device vs f32 forward",
                       a[:len(checked)], ref)
    rms = logit_error("prefill logits, mesh vs f32 forward",
                      b[:len(checked)], ref)
    bound = min(RMS_SLACK * base + RMS_FLOOR, RMS_CEIL)
    print(f"prefill logits: mesh rms err {rms:.3f} std, bound {bound:.3f} "
          f"std")
    check(rms <= bound, f"mesh prefill logits: rms err {rms:.3f} std past "
          f"the bound {bound:.3f}")
    n_same = 0
    for r, x, y in zip(requests, a, b):
        t_solo, t_tp = want[r.uid].tokens, got[r.uid].tokens
        check(t_solo[0] == int(x.argmax()) and t_tp[0] == int(y.argmax()),
              f"{r.uid}: first served token is not its prefill argmax")
        first = next((i for i, (t, u) in enumerate(zip(t_solo, t_tp))
                      if t != u), None)
        n_same += first is None
        where = ("identical throughout" if first is None
                 else f"part at token {first}")
        print(f"{r.uid}: greedy tokens {where}")
    print(f"greedy tokens, mesh vs one device: {n_same} of {len(requests)} "
          f"requests identical over {run.new_tokens} tokens")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the tensor-parallel phase")
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    sys.path.insert(0, src)
    try:
        import repro
        from repro import configs
        from repro.launch import compile_cache
    except ImportError as e:
        repro = None
        why = str(e)
    else:
        why = f"it was imported from {os.path.dirname(repro.__file__)}"
    if repro is None or not repro.__file__.startswith(src + os.sep):
        print(f"chip_smoke: needs the repro package under {src} ({why}); "
              f"run it from a checkout of the repo", file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform!r} devices", file=sys.stderr)
        return 2
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"device: {device['kind']} x {device['count']} "
          f"({device['platform']})")
    print(f"compilation cache: {compile_cache.configure()}")

    cfg = configs.get_config("olmo-1b")
    print(f"model {cfg.name}: d_model {cfg.d_model}, {cfg.n_layers} "
          f"layers, {cfg.n_heads} heads x {cfg.head_dim} (kv "
          f"{cfg.n_kv_heads}), d_ff {cfg.d_ff}, vocab {cfg.vocab}, "
          f"compute {jax.numpy.dtype(cfg.compute_dtype).name}")
    phase = Phases(jax)
    if args.chips == 4:
        run_four_chips(cfg, Run(), phase)
    else:
        run_one_chip(cfg, Run(), phase)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
