"""Engines + workloads the analyzer checks — smoke-sized, CPU-cheap.

One place (shared by scripts/analyze.py and tests/test_analysis.py)
builds the serving configurations the contracts run against, so the
analyzer and its regression tests cannot drift apart.  Three engines
cover the four dispatch shapes the ISSUE names:

  * ``quantized``      — packed weights + int8 contiguous cache:
                         ``prefill`` and scanned ``decode``.
  * ``spec_chunked``   — same, plus an n-gram draft (k=3) and
                         ``prefill_chunk=4``: the ``spec_verify`` and
                         ``fused_prefill_decode`` widths.
  * ``sharded``        — packed + int8 under a 1-device "model" mesh:
                         the shard_map'd decode the collective-count
                         contract walks (the psum structure is identical
                         at any shard count; a 1-device mesh traces it
                         on any host).
  * ``sharded_paged``  — the same mesh with ``cache_layout="paged"``:
                         the sharded PAGED decode dispatch (page pools
                         sharded on the KV-head axis, block table
                         replicated).  Traced so the collective /
                         baked-consts / dtype contracts cover the
                         paged+mesh composition, not just contiguous.

The retrace workloads drive real schedulers (mixed prompt lengths,
staggered admission, tail chunks, speculation) and read back
``dispatch_audit()`` — the one dynamic step in an otherwise static pass.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro import configs
from repro.launch.mesh import make_mesh
from repro.models import transformer as tf
from repro.parallel.context import local_context
from repro.serve import pack_params
from repro.serve.config import DraftSpec, EngineSpec
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import ContinuousBatchingScheduler, Request

ENGINE_KINDS = ("quantized", "spec_chunked", "sharded", "sharded_paged")
MAX_SEQ = 64
DECODE_CHUNK = 4
PREFILL_CHUNK = 4
DRAFT_K = 3
PROMPT_BUCKET = 16
PAGE_SIZE = 8


def _packed_setup():
    cfg = configs.get_config("olmo-1b").smoke()
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    arr = tf.build_policy(cfg).as_arrays()
    packed = pack_params(params, arr, cfg, cache_bits=8)
    return cfg, packed, arr


def build_engine(kind: str) -> ServeEngine:
    cfg, packed, arr = _packed_setup()
    base = dict(weights="packed", cache="quantized", cache_bits=8,
                decode_chunk=DECODE_CHUNK)
    if kind == "quantized":
        spec = EngineSpec(**base)
    elif kind == "spec_chunked":
        spec = EngineSpec(**base, prefill_chunk=PREFILL_CHUNK,
                          draft=DraftSpec(kind="ngram", k=DRAFT_K))
    elif kind == "sharded":
        mesh = make_mesh((1,), ("model",))
        spec = EngineSpec(**base, mesh=mesh)
    elif kind == "sharded_paged":
        mesh = make_mesh((1,), ("model",))
        spec = EngineSpec(**base, mesh=mesh, cache_layout="paged",
                          page_size=PAGE_SIZE)
    else:
        raise ValueError(f"unknown engine kind {kind!r}; "
                         f"one of {ENGINE_KINDS}")
    return ServeEngine(cfg=cfg, params=packed, policy_arrays=arr,
                       ctx=local_context(), max_seq=MAX_SEQ, spec=spec)


def _requests(n: int = 6) -> list:
    """Mixed prompt lengths and budgets: short and long prompts (bucket
    boundaries on both sides), token budgets that force tail chunks, and
    more requests than slots so admission staggers."""
    out = []
    for i in range(n):
        p_len = (3, 9, 17, 5, 21, 12)[i % 6]
        budget = (5, 7, 11, 4, 9, 6)[i % 6]
        out.append(Request(uid=f"r{i}",
                           prompt=[(7 * i + j) % 512 for j in range(p_len)],
                           max_new_tokens=budget))
    return out


def run_retrace_workloads() -> Dict[str, dict]:
    """Drive each scheduler-facing engine through a mixed workload and
    return workload name -> ``dispatch_audit()``."""
    audits = {}
    for kind in ("quantized", "spec_chunked"):
        eng = build_engine(kind)
        sched = ContinuousBatchingScheduler(eng, n_slots=3,
                                            prompt_bucket=PROMPT_BUCKET)
        for req in _requests():
            sched.submit(req)
        sched.run()
        # a second wave over the SAME engine: warm jit caches must be
        # reused, not re-traced (the audit would catch per-wave leaks)
        for req in _requests(3):
            sched.submit(Request(uid=req.uid + "b", prompt=req.prompt,
                                 max_new_tokens=req.max_new_tokens))
        sched.run()
        audits[kind] = sched.dispatch_audit()
    # solo generate on a fresh engine: chunk + exact tail geometry
    eng = build_engine("quantized")
    eng.generate(jnp.zeros((2, 8), jnp.int32), n_new=DECODE_CHUNK + 2)
    sizes, budget = eng.jit_cache_sizes(), eng.dispatch_budget(PROMPT_BUCKET)
    audits["generate_tail"] = {
        "sizes": sizes, "budget": budget,
        "over": {k: {"traces": v, "budget": budget[k]}
                 for k, v in sizes.items()
                 if k in budget and v > budget[k]}}
    return audits
