"""Serving subsystem: quantized weights, quantized KV cache, scheduling.

  engine.py     jitted prefill + scanned-chunk decode (ServeEngine);
                ``mesh=`` serves tensor-parallel (shard_map, two psums
                per block, bit-exact with single-device — DESIGN.md §3)
  packing.py    offline packed-weight pass (uint8 codes) + shard-aware
                repack (no nibble byte straddles a shard)
  kv_cache.py   preallocated (B, S_max) cache with valid-length tracking;
                full-dtype or quantized (int8 / packed-int4 + scales);
                shards along the KV-head axis under a mesh
  paging.py     block/page-table cache layout (cache_layout="paged"):
                fixed-size page pools + refcounted prefix sharing with
                admission-time copy-on-write — per-token actual
                residency instead of per-slot worst case, decode
                bit-exact with the contiguous layout
  residency.py  the ONE resident/roofline byte accounting (weights + KV,
                totals and per-device shares)
  sampling.py   greedy / temperature / top-k; keys fold (admission nonce,
                per-request token index) — scheduler-invariant
  scheduler.py  continuous batching: slot admission, per-request stop/evict
  tracing.py    ``serve.*`` host spans on the profiler's clock (a
                ``jax.profiler`` session is the only switch)
  config.py     EngineSpec / DraftSpec: the typed, validated serving spec
                (``ServeEngine(..., spec=EngineSpec(...))`` is the
                primary constructor; flat kwargs are deprecated)
  spec.py       self-speculative decoding: knapsack-frontier (or n-gram)
                draft proposes k tokens, the target verifies them in one
                multi-token dispatch — greedy spec == non-spec
                token-for-token (lossless)

The public serving surface is what this module exports: ``ServeEngine``,
``EngineSpec``/``DraftSpec``, ``Request``/``Completion``/``serve_all``,
and ``pack_params`` — examples and benches import from here, not from
submodule paths.
"""
from repro.serve import paging, residency, tracing
from repro.serve.config import DraftSpec, EngineSpec
from repro.serve.engine import ServeEngine, quantize_for_serving
from repro.serve.spec import SpecDecoder
from repro.serve.kv_cache import (QuantizedServeCache, ServeCache,
                                  init_cache, splice_prefill)
from repro.serve.paging import (PageAllocator, PagedServeCache,
                                PrefixRegistry)
from repro.serve.packing import (bf16_resident_weight_bytes, pack_params,
                                 params_are_packed, resident_weight_bytes)
from repro.serve.sampling import GREEDY, SamplerConfig, sample
from repro.serve.scheduler import (Completion, ContinuousBatchingScheduler,
                                   Request, serve_all)

__all__ = [
    "ServeEngine", "EngineSpec", "DraftSpec", "SpecDecoder",
    "quantize_for_serving",
    "pack_params", "params_are_packed", "resident_weight_bytes",
    "bf16_resident_weight_bytes", "residency", "tracing",
    "ServeCache", "QuantizedServeCache", "init_cache", "splice_prefill",
    "paging", "PagedServeCache", "PageAllocator", "PrefixRegistry",
    "SamplerConfig", "GREEDY", "sample",
    "Request", "Completion", "ContinuousBatchingScheduler", "serve_all",
]
