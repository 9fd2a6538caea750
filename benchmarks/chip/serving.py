"""The system under test, built and driven the way a deployment runs it.

``build`` turns the benchmark's weights into the served model through the
program's own pipeline: EAGL gains (the Pallas histogram), the knapsack at
the configuration's budget, ``pack_params``, and a ``ServeEngine`` behind a
``ContinuousBatchingScheduler``.  A configuration that states
``"mesh": {"model": n}`` is served tensor-parallel over the cell's ``n``
chips: the weights are made sharded and the engine gets the mesh.  One
with expert layers never holds its whole bf16 tree: EAGL reads each
layer as it is made, and packing packs a layer at a time.

``drive`` is the open-loop client.  The scheduler only drains a queue it
was given, so the loop here plays its ``run()`` one round at a time and
submits each request when it falls due: admit into every free slot (one
request per admission, each a prefill and a host sync on its first
token), then one scanned decode round for all slots.  Each call into the
scheduler is a host span (``bench.admit``, ``bench.decode_round``,
``bench.arrivals``, ``bench.idle``) so a trace can say what the host was
doing while the device sat idle.
"""
from __future__ import annotations

import collections
import functools
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from benchmarks.chip import e2e, traffic, weights

clock = time.perf_counter


# Keys a configuration file may hold.  Anything else -- shared experts, a
# prefix of dense layers, another attention -- is a block the harness does
# not describe, and is refused.
KNOWN_KEYS = frozenset((
    "model", "source", "reduced", "assumed", "hidden_size",
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "head_dim", "intermediate_size", "vocab_size", "max_position_embeddings",
    "rope_theta", "norm", "norm_eps", "tie_word_embeddings", "hidden_act",
    "dtype", "head_act_step", "reference", "policy", "engine", "n_slots",
    "max_seq", "prompt_bucket", "check", "mesh", "arch")
    + weights.EXPERT_KEYS)


def arch_config(cfg: dict):
    """The program's ArchConfig for a configuration file: the named model
    with every size the file states.  Expert keys (``num_local_experts``,
    the experts held here; ``num_experts_per_tok``;
    ``moe_intermediate_size``) make the block GQA + MoE; an ``arch`` object
    is handed to ``ArchConfig.replace`` as it stands."""
    from repro import configs
    from repro.models.common import BlockDef
    unknown = sorted(set(cfg) - KNOWN_KEYS)
    if unknown:
        raise ValueError(
            f"{cfg['model']}: unknown configuration keys {unknown}: a "
            "configuration file describes one repeated GQA block with a "
            "SwiGLU MLP or expert layers, with no prefix of dense layers "
            "and no shared experts")
    base = configs.get_config(cfg["model"])
    if len(base.pattern) != 1 or base.prefix:
        raise ValueError(f"{cfg['model']}: only one repeated block is "
                         "described by a configuration file, not a prefix "
                         "of dense layers")
    dt = jnp.dtype(cfg["dtype"])
    kw = dict(
        d_model=cfg["hidden_size"], n_repeats=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        vocab=cfg["vocab_size"],
        norm=cfg["norm"], rope_base=float(cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=dt, compute_dtype=dt)
    if weights.has_experts(cfg):
        kw.update(n_experts=cfg["num_local_experts"],
                  top_k=cfg["num_experts_per_tok"],
                  d_ff=cfg["moe_intermediate_size"],
                  pattern=(BlockDef("gqa", "moe"),))
    elif base.pattern[0].ffn == "moe":
        raise ValueError(f"{cfg['model']}: an expert model needs "
                         f"{list(weights.EXPERT_KEYS)}")
    else:
        kw["d_ff"] = cfg["intermediate_size"]
    return base.replace(**kw).replace(**cfg.get("arch", {}))


def mesh_for(cfg: dict, devices):
    """The configuration's tensor-parallel mesh over the cell's
    ``devices``, or None when it states none."""
    if "mesh" not in cfg:
        return None
    from repro.launch.mesh import make_mesh
    if set(cfg["mesh"]) != {"model"}:
        raise ValueError(f"mesh {cfg['mesh']}: only a 'model' axis is "
                         "served")
    n = int(cfg["mesh"]["model"])
    if devices is None or n != len(devices):
        raise ValueError(f"mesh 'model' {n} needs the cell's chips to be "
                         f"{n}, not {0 if devices is None else len(devices)}")
    return make_mesh((n,), ("model",), devices=devices)


def select_policy(arch, fetch, pol: dict, device=None):
    """EAGL gains -> knapsack at the budget; returns the mixed policy and
    its bits per layer for each projection group (per layer, a list over
    the experts of an expert group).  ``fetch(unit, path)`` gives a unit's
    tensor and LSQ step; ``device``: where each tensor is brought whole for
    its histogram (a Pallas kernel is not partitioned over a mesh); None
    leaves the tensors where they are."""
    from repro.core import knapsack
    from repro.core.metrics import eagl
    from repro.models import transformer as tf
    if pol["metric"] != "eagl":
        raise ValueError(f"unknown policy metric {pol['metric']!r}")
    policy = tf.build_policy(arch, b_hi=pol["b_hi"], b_lo=pol["b_lo"])

    def placed(u, t):
        got = fetch(u, t)
        return got if device is None else jax.device_put(got, device)

    gains = eagl.eagl_gains(policy, placed, impl="auto")
    mixed = policy.apply_selection(
        knapsack.select_for_budget(policy, gains, pol["budget_frac"]).take)
    bits = {slot: arr.astype(int).tolist()
            for slot, arr in mixed.as_arrays()["pat0"].items()}
    return mixed, bits


def pack_by_layer(src: weights.Source, policy_arrays, arch, cache_bits):
    """``pack_params``' bucketed layout, made a layer at a time: each layer
    of ``src`` packed as ``pack_params`` packs it and written into its
    bucket's stack, so no more than one layer's weights are held."""
    from repro.core.policy import bucket_plan
    from repro.models import transformer as tf
    from repro.models.layout import LayerBuckets
    from repro.serve import packing
    slot_of = tf._slot_index(arch)
    out = packing.pack_params(src.edges(), policy_arrays, arch,
                              cache_bits=cache_bits)
    plan = bucket_plan(policy_arrays, cache_bits, n_layers=arch.n_repeats)
    buckets, start = [], 0
    for size in plan.sizes:
        stack = None
        for j in range(size):
            layer = packing._walk({"p0": src.layer(start + j)}, ("pat",),
                                  start + j, slot_of, policy_arrays)
            src.release()
            if stack is None:
                stack = jax.tree.map(
                    lambda x: jnp.zeros((size,) + x.shape, x.dtype), layer)
            stack = weights.put(stack, layer, j)
            del layer
        buckets.append(jax.block_until_ready(stack))
        start += size
    out["pat"] = LayerBuckets(tuple(buckets), plan.sizes)
    return out


def build(cfg: dict, seed: int, log, devices=None):
    """Weights from ``seed`` -> policy -> packed engine and scheduler, on
    the cell's ``devices`` (a mesh over them when the configuration states
    one).  The bf16 weights are dropped before returning: only what a
    deployment holds stays resident.  With expert layers the weights are
    made a layer at a time (``weights.Source``), once for EAGL and once for
    packing, and never whole."""
    from repro.models import transformer as tf
    from repro.parallel.context import local_context
    from repro.serve import (ContinuousBatchingScheduler, EngineSpec,
                             ServeEngine, pack_params)
    arch = arch_config(cfg)
    mesh = mesh_for(cfg, devices)
    eng_cfg = cfg["engine"]
    experts = weights.has_experts(cfg)
    if experts and mesh is not None:
        raise ValueError("expert layers are served on one chip: the "
                         "program's packed expert banks have no mesh form")
    if experts:
        src = weights.Source(cfg, seed)
        fetch = src.fetch
    else:
        t = clock()
        params = weights.make(cfg, seed, mesh)
        log(f"[set-up] weights {clock() - t:.2f} s")
        fetch = functools.partial(tf.fetch_unit_tensor, params)
    t = clock()
    mixed, bits = select_policy(arch, fetch, cfg["policy"],
                                None if mesh is None else devices[0])
    log(f"[set-up] EAGL + knapsack {clock() - t:.2f} s")
    for slot, per_layer in bits.items():
        log(f"policy {slot}: per-layer bits {per_layer}")
    t = clock()
    if experts:
        src.release()
        made = src.seconds
        packed = pack_by_layer(src, mixed.as_arrays(), arch,
                               eng_cfg["cache_bits"])
        log(f"[set-up] pack {clock() - t:.2f} s")
        log(f"[set-up] of these, making the weights a layer at a time: "
            f"{made:.2f} s in EAGL, {src.seconds - made:.2f} s in packing")
    else:
        packed = jax.block_until_ready(pack_params(
            params, mixed.as_arrays(), arch,
            cache_bits=eng_cfg["cache_bits"]))
        log(f"[set-up] pack {clock() - t:.2f} s")
        del params
    engine = ServeEngine(
        cfg=arch, params=packed,
        policy_arrays=jax.tree.map(jnp.asarray, mixed.as_arrays()),
        ctx=local_context(), max_seq=cfg["max_seq"],
        spec=EngineSpec(**eng_cfg, mesh=mesh))
    sched = ContinuousBatchingScheduler(engine, n_slots=cfg["n_slots"],
                                        prompt_bucket=cfg["prompt_bucket"])
    return engine, sched, bits


def n_steps_next(sched) -> int:
    """The scan length the scheduler's next decode round will run: the
    decode chunk, or the power of two covering the longest remaining
    budget (``ContinuousBatchingScheduler._decode_harvest``)."""
    remaining = max(s.req.max_new_tokens - len(s.emitted)
                    for s in sched.slots if s is not None)
    tail = 1
    while tail < remaining:
        tail *= 2
    return min(sched.engine.decode_chunk, tail)


CACHE_LEAVES = ("kq", "k_scale", "vq", "v_scale")
PAGED = ("kq", "vq", "v_scale")     # pooled by page as pkq, pvq, pv_scale


@functools.partial(jax.jit, static_argnums=(2, 4))
def slot_rows(layers, slot, n_layers: int, tbl=None, n_rows: int = 0
              ) -> dict:
    """One slot's int8 cache codes and scales in the first ``n_layers``
    layers: kq, vq (n, S, kv heads, head dim), k_scale (n, kv heads, head
    dim), v_scale (n, S, kv heads).  A paged cache is read through the
    slot's page ids in ``tbl``, the block table as it stood while the slot
    held the request, into the same shapes (``n_rows`` = S); an unmapped
    page reads as zeros."""
    out = {}
    for name in CACHE_LEAVES:
        paged = tbl is not None and name in PAGED
        leaf_name = "p" + name if paged else name
        runs = [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(
            layers) if jax.tree_util.keystr(path).endswith(f"['{leaf_name}']")]
        if paged:
            rows = [_pages(pool, tbl[slot], n_rows) for pool in runs]
        else:
            rows = [r[:, slot] for r in runs]
        out[name] = jnp.concatenate(rows)[:n_layers]
    return out


def _pages(pool, ids, n_rows: int):
    """Stacked pool (L, pages, page, ...) read at page ``ids`` in order:
    (L, n_rows, ...)."""
    ids = jnp.where(ids < 0, pool.shape[1], ids)       # -1: unmapped
    got = jnp.take(pool, ids, axis=1, mode="fill", fill_value=0)
    rows = got.reshape((got.shape[0], -1) + got.shape[3:])
    return rows[:, :n_rows]


class Loop:
    """One scheduler driven open-loop; ``drive`` may be called again (the
    warm-up runs through the same code before the window).

    ``capture``: uids whose slot's cache rows in the first
    ``capture_layers`` layers are copied out (``snapshots``) when the
    request finishes, before the slot is admitted again -- on the device,
    with no host sync.  A paged slot is read through the block table as it
    stood before the decode round whose harvest freed its pages."""

    def __init__(self, sched, capture_layers: int = 0):
        self.sched = sched
        self.n_slots = sched.n_slots
        self.max_seq = sched.engine.max_seq
        self.n_drives = 0
        self.capture_layers = capture_layers
        self.capture: set = set()
        self.snapshots: Dict[str, dict] = {}
        self._logits = None
        prefill = sched.engine.prefill

        def keep_logits(*args, **kwargs):
            # the prefill's last-position logits stay on the device until
            # the check reads them; holding them costs no sync
            out = prefill(*args, **kwargs)
            self._logits = out[0]
            return out

        sched.engine.prefill = keep_logits

    def drive(self, reqs: Sequence[traffic.Request], t_open: float,
              seconds: float, drain_s: float, backlog: bool,
              hooks: Sequence = ()) -> dict:
        """Serve ``reqs`` (due times relative to ``t_open``) for ``seconds``
        of window, then drain: an open loop follows every request due in
        the window to completion for at most ``drain_s``; a backlog stops
        at the close.  ``hooks``: (seconds after ``t_open``, fn) pairs, each
        called once between rounds when its time has come (at the latest
        at the close); the close moves back by the time each took.
        Returns the run record."""
        from repro.serve import Request
        sched = self.sched
        if any(s is not None for s in sched.slots):
            raise RuntimeError("drive() needs every slot free; the last "
                               "drive left requests in flight")
        self.n_drives += 1
        tag = f"d{self.n_drives}:"        # scheduler uids unique per drive
        t_close = t_open + seconds
        recs = {r.uid: e2e.Served(uid=r.uid, due=t_open + r.due_s,
                                  n_out=r.n_out, prompt_len=len(r.prompt))
                for r in reqs}
        pending = collections.deque(r for r in reqs if r.due_s <= seconds)
        waiting: collections.deque = collections.deque()
        in_slot: Dict[int, str] = {}
        rounds: List[dict] = []
        min_waiting: Optional[int] = None
        closed_at = None
        hooks = sorted(hooks, key=lambda h: h[0])
        while True:
            now = clock()
            while hooks and (now >= t_open + hooks[0][0] or now >= t_close):
                hooks.pop(0)[1]()
                # the window serves for ``seconds`` whatever a hook took
                # (writing out a trace takes longer than the traced span)
                t_close += clock() - now
                now = clock()
            if closed_at is None and now >= t_close:
                closed_at = now
                if backlog:
                    for uid in in_slot.values():
                        recs[uid].cut = True
                    break
            if closed_at is not None and (
                    (not pending and not waiting and not in_slot)
                    or now >= t_close + drain_s):
                break
            with jax.profiler.TraceAnnotation("bench.arrivals"):
                while pending and recs[pending[0].uid].due <= now:
                    r = pending.popleft()
                    recs[r.uid].noticed = now
                    waiting.append(r)
            free = [j for j in range(self.n_slots) if sched.slots[j] is None]
            for j in free:
                if not waiting:
                    break
                r = waiting.popleft()
                rec = recs[r.uid]
                rec.admit_start = t0 = clock()
                sched.submit(Request(uid=tag + r.uid, prompt=r.prompt,
                                     max_new_tokens=r.n_out))
                with jax.profiler.TraceAnnotation("bench.admit"):
                    sched._admit()
                t1 = clock()
                rec.t_first = rec.t_last = t1
                rec.logits, self._logits = self._logits, None
                rec.n_done = 1
                rec.n_in_window = int(t1 <= t_close)
                rounds.append({"kind": "prefill", "tokens": rec.prompt_len,
                               "t0": t0, "t1": t1})
                if sched.slots[j] is not None:
                    in_slot[j] = r.uid
                else:
                    rec.tokens = list(sched.completed[tag + r.uid].tokens)
            if in_slot:
                if closed_at is None:
                    min_waiting = (len(waiting) if min_waiting is None
                                   else min(min_waiting, len(waiting)))
                before = {j: len(sched.slots[j].emitted) for j in in_slot}
                ctx0 = {j: recs[u].prompt_len + before[j] - 1
                        for j, u in in_slot.items()}
                steps = n_steps_next(sched)
                tbl = getattr(sched.cache, "block_tbl", None)
                t0 = clock()
                with jax.profiler.TraceAnnotation("bench.decode_round"):
                    sched._decode_harvest()
                t1 = clock()
                rows = []
                for j, uid in list(in_slot.items()):
                    rec = recs[uid]
                    done = sched.completed.get(tag + uid)
                    total = (len(done.tokens) if done is not None
                             else len(sched.slots[j].emitted))
                    delta = total - before[j]
                    rows.append([ctx0[j], delta])
                    rec.n_done += delta
                    rec.t_last = t1
                    if t1 <= t_close:
                        rec.n_in_window += delta
                    if done is not None:
                        rec.tokens = list(done.tokens)
                        del in_slot[j]
                        if uid in self.capture:
                            self.snapshots[uid] = slot_rows(
                                sched.cache.layers, j, self.capture_layers,
                                tbl, self.max_seq)
                rounds.append({"kind": "decode", "rows": rows,
                               "steps": steps, "t0": t0, "t1": t1})
            elif not waiting:
                nxt = recs[pending[0].uid].due if pending else t_close
                with jax.profiler.TraceAnnotation("bench.idle"):
                    while clock() < min(nxt, t_close) and not (
                            closed_at is not None and not pending):
                        time.sleep(0.0005)
        t_end = clock()
        return {"requests": list(recs.values()), "rounds": rounds,
                "t_open": t_open, "t_close": t_close, "t_end": t_end,
                "closed_at": closed_at, "seconds": seconds,
                "backlog": backlog, "min_waiting": min_waiting}


def attempted(run: dict) -> List[e2e.Served]:
    """Requests due in the window; for a backlog, those admitted by the
    close."""
    reqs = [r for r in run["requests"] if r.due <= run["t_close"]]
    if run["backlog"]:
        reqs = [r for r in reqs if r.admit_start is not None
                and r.admit_start <= run["t_close"]]
    return reqs


def warm_up(loop: Loop, mix: dict, cfg: dict, seed: int) -> None:
    """Run every shape this cell's traffic uses once, through ``drive``:
    a prefill at each padded prompt width the mix can produce, each decode
    scan length (the powers of two up to the chunk; a round's length is
    set by the longest remaining budget, so one request at a time), an
    admission into every slot and, with a paged cache, an admission for
    each number of pages one can write (``traffic.page_writes``)."""
    bucket, max_seq = cfg["prompt_bucket"], cfg["max_seq"]
    widths = traffic.prompt_widths(mix, bucket, max_seq)
    chunk = cfg["engine"]["decode_chunk"]
    tails = [2 ** i for i in range(chunk.bit_length()) if 2 ** i <= chunk]
    rng = traffic.rng_for(seed, 3)
    shortest = int(mix["prompt"]["min"])

    def req(i, plen, n_out):
        return traffic.Request(
            uid=f"warm{i:04d}",
            prompt=rng.integers(0, cfg["vocab_size"], plen).tolist(),
            n_out=min(n_out, max_seq - plen), due_s=0.0)

    phases = [[req(i, min(w, int(mix["prompt"]["max"])), 2)
               for i, w in enumerate(widths)]]
    phases += [[req(100 + t, shortest, 1 + t)] for t in tails]
    phases.append([req(200 + j, shortest, 2) for j in range(loop.n_slots)])
    if cfg["engine"].get("cache_layout") == "paged":
        pairs = traffic.page_writes(mix, bucket, max_seq,
                                    loop.sched.engine.page_size)
        phases.append([req(300 + i, p, o) for i, (p, o) in enumerate(pairs)])
    loop.capture.add("warm0200")
    for reqs in phases:
        loop.drive(reqs, clock(), 0.0, drain_s=3600.0, backlog=False)
    jax.block_until_ready((loop.sched.cache.lengths, loop.snapshots))
    loop.capture.clear()
    loop.snapshots.clear()
