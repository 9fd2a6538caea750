"""Continuous batching: fixed-slot admission, per-request stop & eviction.

Production serving never waits for a whole batch to finish: requests are
admitted into fixed batch SLOTS as they arrive, decode advances all slots
together, and a slot is freed the moment its request stops (EOS or token
budget).  This scheduler implements that at chunk granularity —
iteration-level scheduling where one iteration is the engine's scanned
decode chunk:

  admit   — pop pending requests into free slots; each request is
            prefilled alone (its prompt padded to a small bucket so jit
            caches stay warm) and its cache written into the shared
            (B, S_max) buffers along the batch axis (kv_cache.write_slot).
            Unequal prompt lengths are the normal case: every slot keeps
            its own valid length and decode position.
  decode  — one scanned chunk for ALL slots in a single dispatch; inactive
            slots decode garbage that is masked from the cache (their
            write position is pinned out of range) and discarded here.
  harvest — per-request stop conditions: EOS token or max_new_tokens.
            Finished slots are evicted; their rows become
            garbage-until-overwritten, which the admission/decode masking
            already guarantees is never read.

The whole loop is host-side control over jitted batch steps — no
recompilation as requests come and go, because request boundaries only
ever change ARRAY CONTENTS (lengths, active mask, feed tokens), never
shapes.

**Chunked prefill** (``EngineSpec(prefill_chunk=N)``, DESIGN.md §3):
whole-prompt admission runs a request's entire prompt as one prefill
dispatch — every decoding batchmate stalls for the full prompt length
(head-of-line blocking; the p99 inter-token stall under long-prompt
injection is the cost).  With a chunk budget the prompt is consumed N
tokens at a time INSIDE the regular decode cadence: each round becomes
one fused dispatch (engine.fused_step) where prefilling slots are
multi-token rows eating their next prompt chunk and decoding slots are
1-token rows (or k+1-token verify rows under speculation) — so no
running slot ever waits more than one chunk-width dispatch between
tokens.  Quantized caches stage chunk writes at full dtype
(engine.new_staging_cache) and re-quantize the finished prompt with
whole-prompt calibration at completion, keeping chunked admission
token-for-token identical to whole-prompt admission.

A deterministic sim clock ticks in model-step units (a prefill costs its
padded token count, a scanned chunk its step count, a fused dispatch its
token width, and a policy-draft propose its k+1 steps scaled by the
draft's resident-bytes/token roofline share of a target step —
``SpecDecoder.draft_step_cost``); ``latency_report()`` turns the
per-request emission clocks
into p50/p95/p99 TTFT and inter-token stall percentiles —
benchmarks/serve_bench.py gates the chunked-vs-whole stall improvement
on exactly these geometry-deterministic numbers.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import kv_quant as kvq
from repro.models import transformer as tf
from repro.serve import kv_cache, paging, sampling, tracing
from repro.serve import spec as spec_mod
from repro.serve.engine import ServeEngine


@dataclasses.dataclass
class Request:
    uid: str
    prompt: Sequence[int]
    max_new_tokens: int = 32
    eos_id: Optional[int] = None


@dataclasses.dataclass
class Completion:
    uid: str
    prompt_len: int
    tokens: List[int]              # generated tokens (EOS included if hit)
    finish_reason: str             # 'eos' | 'length'


@dataclasses.dataclass
class _Slot:
    req: Request
    emitted: List[int]
    nonce: int                     # admission nonce: folds into every
                                   # sampling key of this request's tokens
    # chunked admission: prompt tokens not yet consumed (empty = decoding)
    pending: List[int] = dataclasses.field(default_factory=list)
    # paged full-miss admissions keep their plan so the prefix registers
    # once the chunked prefill completes (whole-prompt registers inline)
    plan: Optional[paging.AdmitPlan] = None


class ContinuousBatchingScheduler:
    """Drive a ServeEngine with slot-based continuous batching."""

    def __init__(self, engine: ServeEngine, n_slots: int = 4,
                 prompt_bucket: int = 16,
                 key: Optional[jax.Array] = None,
                 share_prefixes: bool = True):
        self.engine = engine
        self.n_slots = n_slots
        self.prompt_bucket = prompt_bucket
        self.key = sampling.base_key() if key is None else key
        self.cache = engine.new_cache(n_slots)
        self._paged = getattr(engine, "cache_layout",
                              "contiguous") == "paged"
        if self._paged:
            # host-side page bookkeeping (serve/paging.py): worst-case
            # pages are claimed at admission, released at eviction; the
            # registry holds recently-seen prefixes alive for sharing
            self.allocator = paging.PageAllocator(
                paging.n_pool_pages(self.cache), engine.page_size)
            self.registry = (paging.PrefixRegistry(self.allocator)
                             if share_prefixes else None)
            self._slot_pages: List[Optional[List[int]]] = [None] * n_slots
            self._batch_axes = None
        else:
            # batch axes come from the ENGINE's cache layout (a quantized
            # cache carries code+scale leaves the default full-dtype
            # template lacks)
            self._batch_axes = engine.cache_batch_axes()
        self.queue: collections.deque = collections.deque()
        self.slots: List[Optional[_Slot]] = [None] * n_slots
        self._tok = np.zeros((n_slots, 1), np.int32)
        self._admit_idx = 0            # next admission nonce (sampling keys
                                       # fold (nonce, per-request token idx))
        self.completed: Dict[str, Completion] = {}
        # chunked prefill (EngineSpec.prefill_chunk): prompts are consumed
        # chunk-at-a-time inside fused dispatches; quantized caches stage
        # the chunk writes at full dtype until whole-prompt finalize
        self._chunked = engine.prefill_chunk is not None
        self.staging = (engine.new_staging_cache(n_slots)
                        if self._chunked else None)
        # deterministic sim clock (model-step units) + per-request emission
        # times — latency_report() derives TTFT / inter-token percentiles
        self.clock = 0
        self._submit_clock: Dict[str, int] = {}
        self._emit_clocks: Dict[str, List[int]] = {}
        # speculative decoding (serve/spec.py): when the engine's spec
        # names a draft, decode rounds go draft-propose -> one verify
        # dispatch -> accept/commit instead of scanned chunks.  Per-slot
        # draft state (scratch cache / history) turns over with the
        # slots, interleaved with admission and eviction.
        self.spec = (spec_mod.SpecDecoder(engine, n_slots,
                                          prompt_bucket=prompt_bucket)
                     if engine.draft is not None else None)

    # ------------------------------------------------------------ frontend
    def submit(self, req: Request) -> None:
        n_prompt = len(req.prompt)
        if n_prompt < 1:
            raise ValueError(f"request {req.uid}: empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError(f"request {req.uid}: max_new_tokens < 1")
        if n_prompt + req.max_new_tokens > self.engine.max_seq:
            raise ValueError(
                f"request {req.uid}: {n_prompt}+{req.max_new_tokens} "
                f"exceeds max_seq {self.engine.max_seq}")
        if self._paged:
            need = kvq.page_count(n_prompt + req.max_new_tokens,
                                  self.engine.page_size)
            if need > self.allocator.n_pages:
                raise ValueError(
                    f"request {req.uid}: needs {need} pages but the pool "
                    f"holds {self.allocator.n_pages} — raise "
                    f"ServeEngine(n_pages=...)")
        self._submit_clock.setdefault(req.uid, self.clock)
        self.queue.append(req)

    def run(self) -> Dict[str, Completion]:
        """Drain the queue; returns uid -> Completion."""
        while self.queue or any(s is not None for s in self.slots):
            self._admit()
            if any(s is not None for s in self.slots):
                if self._chunked and any(s is not None and s.pending
                                         for s in self.slots):
                    self._fused_round()
                elif self.spec is not None:
                    self._spec_round()
                else:
                    self._decode_harvest()
        return self.completed

    # ------------------------------------------------------------ internals
    def _next_nonce(self) -> int:
        """Each admission gets its own nonce: identical prompts admitted
        at different times must not reuse one Gumbel draw, and every
        later sampling key of this request folds the same nonce — so its
        whole trajectory matches engine.generate(..., nonces=[n])
        regardless of slot, batchmates, or chunk geometry.  Chunked
        admission assigns at slot CLAIM, which is the same FIFO order
        whole-prompt admission assigns in — so both admission modes give
        a request the same nonce, hence the same stochastic trajectory."""
        nonce = self._admit_idx
        self._admit_idx += 1
        return nonce

    def _record_emit(self, uid: str, clock: Optional[int] = None) -> None:
        self._emit_clocks.setdefault(uid, []).append(
            self.clock if clock is None else clock)

    def _begin_decode(self, j: int, slot: _Slot, first: int) -> None:
        """A request's prompt is fully in-cache and its first token is
        sampled (key (nonce, 0)): transition the slot to decoding —
        shared by whole-prompt admission, identical-prompt hits, and
        chunked-prefill completion."""
        slot.emitted.append(first)
        self._record_emit(slot.req.uid)
        if self._finish_reason(slot) is not None:
            self._evict(slot, j)        # finished on its very first token
            return
        self.slots[j] = slot
        self._tok[j, 0] = first
        if self.spec is not None:
            self.spec.admit(j, slot.req.prompt, first, uid=slot.req.uid)

    def _admit(self) -> None:
        for j in range(self.n_slots):
            if self.slots[j] is not None or not self.queue:
                continue
            req = self.queue.popleft()
            with tracing.span("serve.admit", uid=req.uid) as sp:
                if self._chunked:
                    sp.set_metadata(tokens=0, padded=0)
                    admitted = self._claim_chunked(j, req)
                else:
                    admitted = self._admit_whole(j, req, sp)
            if not admitted:
                # pool exhausted: defer admission (FIFO preserved)
                # until an eviction returns pages to the free list
                self.queue.appendleft(req)
                return

    def _admit_whole(self, j: int, req: Request,
                     sp: jax.profiler.TraceAnnotation) -> bool:
        """Whole-prompt admission into slot ``j``; False when the page
        pool cannot cover the request.  The admission path records
        ``tokens`` and ``padded`` on ``sp``, its ``serve.admit`` span."""
        if self._paged:
            last = self._admit_paged(j, req, sp)
            if last is None:
                return False
        else:
            last = self._admit_contiguous(j, req, sp)
        nonce = self._next_nonce()
        with tracing.span("serve.admit.first_token"):
            first = self._first_token(last, nonce)
        self._begin_decode(j, _Slot(req=req, emitted=[], nonce=nonce), first)
        return True

    def _first_token(self, last: jax.Array, nonce: int) -> int:
        """Token 0 of a request, drawn with key (nonce, 0) from its
        last-position prompt logits."""
        return int(sampling.sample(
            last, sampling.slot_keys(self.key,
                                     jnp.asarray([nonce], jnp.int32),
                                     jnp.zeros((1,), jnp.int32)),
            self.engine.sampler)[0])

    def _claim_chunked(self, j: int, req: Request) -> bool:
        """Chunked admission claims the SLOT (and, paged, its worst-case
        pages — exactly ``plan_admission``, so allocator state after a
        chunked claim is identical to a whole-prompt admission) but runs
        NO model call: the prompt lands in ``pending`` and is consumed
        chunk-at-a-time by ``_fused_round``.  Returns False when the page
        pool cannot cover the request (caller defers, FIFO preserved).
        An identical-prompt hit still short-circuits to decoding with no
        model call at all (the donor's pages/grids/logits are this
        request's own admission outcome)."""
        n_prompt = len(req.prompt)
        if not self._paged:
            # the slot may be re-used: its valid length restarts at 0 and
            # the chunk writes overwrite the stale rows front-to-back
            with tracing.span("serve.admit.cache_write"):
                self.cache = kv_cache.set_length(self.cache, j, 0)
            self.slots[j] = _Slot(req=req, emitted=[],
                                  nonce=self._next_nonce(),
                                  pending=list(req.prompt))
            return True
        plan = self._plan_pages(j, req)
        if plan is None:
            return False
        nonce = self._next_nonce()
        if plan.suffix_start >= n_prompt and plan.entry is not None:
            # identical-prompt hit: no model call, no chunking to do
            self._admit_identical(j, plan, n_prompt)
            with tracing.span("serve.admit.first_token"):
                first = self._first_token(plan.entry.last_logits[None],
                                          nonce)
            self._begin_decode(j, _Slot(req=req, emitted=[], nonce=nonce),
                               first)
            return True
        # page-aligned prefix hit (full-dtype cache): only the suffix
        # chunks through the model, attending over the shared prefix
        # pages; miss: the whole prompt chunks from position 0 and the
        # prefix registers at completion (slot.plan)
        with tracing.span("serve.admit.cache_write"):
            self.cache = paging.set_length(self.cache, j, plan.suffix_start)
        self.slots[j] = _Slot(
            req=req, emitted=[], nonce=nonce,
            pending=list(req.prompt[plan.suffix_start:]),
            plan=plan if plan.suffix_start == 0 else None)
        return True

    def _bucket_pad(self, n: int, cap: int) -> int:
        """Bucket a prompt/suffix length so jit caches stay warm, never
        past ``cap`` (the written rows must fit the slot window)."""
        return min(-(-n // self.prompt_bucket) * self.prompt_bucket, cap)

    def _admit_contiguous(self, j: int, req: Request,
                          sp: jax.profiler.TraceAnnotation) -> jax.Array:
        n_prompt = len(req.prompt)
        # pad the lone prompt to a bucket so single-request prefill
        # compiles once per bucket, not once per prompt length; never
        # past max_seq (the prefill cache must fit the slot buffers).
        # Recurrent-state configs (mamba/xlstm) prefill at the EXACT
        # length instead: their states have no position masking, so
        # pad tokens would be integrated into the state.
        if self.engine.has_recurrent_state:
            pad = n_prompt
        else:
            pad = self._bucket_pad(n_prompt, self.engine.max_seq)
        sp.set_metadata(tokens=n_prompt, padded=pad)
        toks = np.zeros((1, pad), np.int32)
        toks[0, :n_prompt] = np.asarray(req.prompt, np.int32)
        with tracing.span("serve.admit.prefill"):
            last, pre = self.engine.prefill(
                jnp.asarray(toks), jnp.asarray([n_prompt], jnp.int32))
        with tracing.span("serve.admit.cache_write"):
            self.cache = kv_cache.write_slot(self.cache, pre, j, n_prompt,
                                             self._batch_axes)
        self.clock += pad               # whole-prompt prefill: every other
                                        # slot stalls for the padded prompt
        return last

    def _plan_pages(self, j: int,
                    req: Request) -> Optional[paging.AdmitPlan]:
        """Claim slot ``j``'s worst-case pages (sharing any registered
        prefix) and map them into its table row; None when the pool
        cannot cover the request (caller defers)."""
        with tracing.span("serve.admit.plan"):
            plan = paging.plan_admission(
                self.allocator, self.registry, tuple(req.prompt),
                req.max_new_tokens,
                quantized=self.engine.cache == "quantized")
            if plan is None:
                return None
            self.cache = paging.set_table_rows(self.cache, j, plan.pages)
            self._slot_pages[j] = plan.pages
            if plan.cow_src is not None:
                # copy-on-write of the shared partial tail page, resolved
                # at the moment the first divergent write is known (=
                # admission: this slot's decode will write into that page)
                self.cache = paging.copy_pages(self.cache, plan.cow_src,
                                               plan.fresh[0])
        return plan

    def _admit_identical(self, j: int, plan: paging.AdmitPlan,
                         n_prompt: int) -> None:
        """Identical-prompt hit: the donor's pages, K grids and
        last-position logits ARE what this request's own prefill would
        produce — no model call at all."""
        with tracing.span("serve.admit.cache_write"):
            if plan.entry.k_scales is not None:
                self.cache = paging.set_slot_k_scales(self.cache, j,
                                                      plan.entry.k_scales)
            self.cache = paging.set_length(self.cache, j, n_prompt)

    def _admit_paged(self, j: int, req: Request,
                     sp: jax.profiler.TraceAnnotation
                     ) -> Optional[jax.Array]:
        """Map pages (sharing any registered prefix), prefill only what
        the mapping does not already cover, register the new prefix.
        Returns the last-valid prompt logits, or None when the pool
        cannot cover the request's worst case (caller defers).
        """
        eng = self.engine
        page = eng.page_size
        n_prompt = len(req.prompt)
        plan = self._plan_pages(j, req)
        if plan is None:
            return None
        if plan.suffix_start >= n_prompt and plan.entry is not None:
            sp.set_metadata(tokens=0, padded=0)
            self._admit_identical(j, plan, n_prompt)
            return plan.entry.last_logits[None]
        if plan.suffix_start > 0:
            # page-aligned prefix hit (full-dtype cache): prefill only the
            # unshared suffix, attending over the shared prefix pages
            suffix = list(req.prompt[plan.suffix_start:])
            pad = self._bucket_pad(len(suffix),
                                   eng.max_seq - plan.suffix_start)
            sp.set_metadata(tokens=len(suffix), padded=pad)
            toks = np.zeros((1, pad), np.int32)
            toks[0, :len(suffix)] = np.asarray(suffix, np.int32)
            with tracing.span("serve.admit.prefill"):
                last, suf = eng.prefill_suffix(jnp.asarray(toks),
                                               len(suffix),
                                               plan.suffix_start,
                                               self.cache, j)
            self.clock += pad
            start_page = plan.suffix_start // page
            phys = plan.pages[start_page:
                              start_page + kvq.page_count(pad, page)]
            with tracing.span("serve.admit.cache_write"):
                self.cache = paging.write_slot_pages(self.cache, suf, j,
                                                     len(suffix),
                                                     plan.suffix_start, phys)
                self.cache = paging.set_length(self.cache, j, n_prompt)
            return last
        # miss: full prefill, exactly the contiguous admission math
        pad = self._bucket_pad(n_prompt, eng.max_seq)
        sp.set_metadata(tokens=n_prompt, padded=pad)
        toks = np.zeros((1, pad), np.int32)
        toks[0, :n_prompt] = np.asarray(req.prompt, np.int32)
        with tracing.span("serve.admit.prefill"):
            last, pre = eng.prefill(jnp.asarray(toks),
                                    jnp.asarray([n_prompt], jnp.int32))
        self.clock += pad
        n_write = min(kvq.page_count(pad, page), len(plan.pages))
        with tracing.span("serve.admit.cache_write"):
            self.cache = paging.write_slot_pages(self.cache, pre, j,
                                                 n_prompt, 0,
                                                 plan.pages[:n_write])
            self._register_prefix(j, req, plan, last)
            self.cache = paging.set_length(self.cache, j, n_prompt)
        return last

    def _register_prefix(self, j: int, req: Request, plan: paging.AdmitPlan,
                         last: jax.Array) -> None:
        """After a miss admission, make this prompt's prefix shareable."""
        if self.registry is None:
            return
        eng = self.engine
        page = eng.page_size
        n_prompt = len(req.prompt)
        if eng.cache == "quantized":
            # only an identical full prompt reproduces the per-request K
            # grid, so quantized entries memoize the WHOLE admission:
            # pages (incl. the partial tail), grids, last logits
            self.registry.register(paging.PrefixEntry(
                key=tuple(req.prompt),
                pages=plan.pages[:kvq.page_count(n_prompt, page)],
                n_tokens=n_prompt, full_prompt=True, last_logits=last[0],
                k_scales=paging.get_slot_k_scales(self.cache, j)))
            return
        aligned = (n_prompt // page) * page
        if aligned >= page:
            self.registry.register(paging.PrefixEntry(
                key=tuple(req.prompt[:aligned]),
                pages=plan.pages[:aligned // page], n_tokens=aligned,
                full_prompt=False,
                last_logits=(last[0] if aligned == n_prompt else None)))

    def _decode_harvest(self) -> None:
        active = np.array([s is not None for s in self.slots])
        # tail chunk: when every live slot's remaining budget is short,
        # don't pay full decode_chunk model steps just to discard them.
        # Rounded up to a power of two so the statically-shaped decode scan
        # compiles at most log2(decode_chunk)+1 distinct sizes, not one per
        # remaining-budget value.
        remaining = max(s.req.max_new_tokens - len(s.emitted)
                        for s in self.slots if s is not None)
        tail = 1
        while tail < remaining:
            tail *= 2
        n_steps = min(self.engine.decode_chunk, tail)
        with tracing.span("serve.decode", live=int(active.sum()),
                          slots=self.n_slots, steps=n_steps,
                          inplace=int(tf.decode_writes_in_place(
                              self.cache.layers))):
            self._decode_round(active, n_steps)

    def _decode_round(self, active: np.ndarray, n_steps: int) -> None:
        with tracing.span("serve.decode.prepare"):
            # per-slot sampling-key state: each live slot's admission
            # nonce and its own generated-token count (len(emitted) —
            # token 0 was drawn at admission).  Chunk geometry never
            # enters the keys, so a shorter tail chunk cannot skip key
            # indices (the old scheme folded chunk_idx * decode_chunk and
            # silently broke scheduler-vs-solo parity for everything
            # except greedy).
            nonces = np.array([s.nonce if s is not None else 0
                               for s in self.slots], np.int32)
            t0 = np.array([len(s.emitted) if s is not None else 0
                           for s in self.slots], np.int32)
            feed, live = jnp.asarray(self._tok), jnp.asarray(active)
        with tracing.span("serve.decode.dispatch"):
            self.cache, tok, toks = self.engine.decode_chunk_step(
                self.cache, feed, self.key, nonces=nonces, step0=t0,
                active=live, n_steps=n_steps)
        with tracing.span("serve.decode.sync"):
            toks_np = np.asarray(toks)
        c0 = self.clock                 # scan step i emits at c0 + i + 1
        self.clock += n_steps
        with tracing.span("serve.decode.harvest"):
            for j, slot in enumerate(self.slots):
                if slot is None:
                    continue
                done = False
                for i, t in enumerate(toks_np[j]):
                    slot.emitted.append(int(t))
                    self._record_emit(slot.req.uid, c0 + i + 1)
                    if self._finish_reason(slot) is not None:
                        done = True
                        break
                if done:
                    self._evict(slot, j)
                else:
                    self._tok[j, 0] = slot.emitted[-1]

    def _spec_round(self) -> None:
        """One speculative round for every live slot (serve/spec.py):
        draft k proposals, verify all of them in ONE multi-token target
        dispatch, commit the longest agreeing prefix + 1 bonus token.

        Token-for-token identical to ``_decode_harvest``: every
        committed token is the target's own greedy argmax given the
        committed history (the draft only gates how many commit per
        round), and greedy sampling ignores its key — EngineSpec refuses
        draft= with a stochastic sampler, so skipping the per-token
        ``sampling.request_key`` fold here cannot change output (the
        admission token 0 still draws through its keyed path).  Harvest
        truncates at EOS/budget exactly like the chunk path; both
        truncations evict the slot, so a surviving slot always took its
        full committed count and its host emitted-length stays in sync
        with the device length watermark.
        """
        active = np.array([s is not None for s in self.slots])
        with tracing.span("serve.spec", live=int(active.sum()),
                          width=self.spec.k + 1):
            self._spec_verify(active)

    def _spec_verify(self, active: np.ndarray) -> None:
        d = self.spec.propose(self._tok, active)              # (B, k)
        x = np.concatenate([self._tok, d], axis=1)            # (B, k+1)
        with tracing.span("serve.spec.dispatch"):
            layers, g, _ = self.engine.verify_step(
                self.cache, jnp.asarray(x), active=jnp.asarray(active))
        # one verify dispatch of width k+1 (committed tokens emit as a
        # burst) PLUS the draft's k+1 propose steps priced at the draft's
        # resident-bytes/token roofline share of a target step — 0 for
        # the model-free n-gram draft; a policy draft streams its own
        # bytes per step, which the CPU ref path cannot show (it prices a
        # draft step like a target step), so the sim clock charges the
        # byte ratio instead (SpecDecoder.draft_step_cost)
        self.clock += (self.spec.k + 1) * (
            1.0 + self.spec.draft_step_cost(self.cache))
        with tracing.span("serve.spec.sync"):
            g_np = np.asarray(g)
        accepted = self.spec.accept(d, g_np, active)          # (B,) j
        self.cache = self.engine.commit_verified(
            self.cache, layers, jnp.asarray(accepted),
            active=jnp.asarray(active))
        self.spec.commit(accepted, g_np, active)
        for j, slot in enumerate(self.slots):
            if slot is None:
                continue
            done = False
            for t in g_np[j, :int(accepted[j])]:
                slot.emitted.append(int(t))
                self._record_emit(slot.req.uid)
                if self._finish_reason(slot) is not None:
                    done = True
                    break
            if done:
                self._evict(slot, j)
            else:
                self._tok[j, 0] = slot.emitted[-1]

    def _fused_round(self) -> None:
        """One fused prefill-chunk + decode dispatch (engine.fused_step;
        runs whenever any live slot still holds pending prompt tokens).

        Per-row roles in the SAME batched dispatch: a prefilling slot is
        a multi-token row consuming its next ``prefill_chunk`` prompt
        tokens (no emission until the prompt completes); a decoding slot
        is a 1-token row emitting exactly one sampled token — or, under
        speculation, a k+1-token verify row committing its accepted
        prefix (a spec round and a prefill chunk share the dispatch).
        So a long prompt costs batchmates at most one chunk-width
        dispatch between tokens, never its full length.

        Parity (DESIGN.md §3 chunked-prefill contract): per-token cache
        rows are bitwise the rows whole-prompt prefill writes (full-dtype
        caches write them directly; quantized caches stage at full dtype
        and re-quantize with whole-prompt calibration at completion), the
        completion sample uses key (nonce, 0) on the same last-position
        logits, and decode rows sample key (nonce, t) on the same
        history — token-for-token identical to whole-prompt admission.
        """
        chunk = self.engine.prefill_chunk
        k = self.spec.k if self.spec is not None else 0
        s_w = max(chunk, k + 1) if self.spec is not None else chunk
        active = np.array([s is not None for s in self.slots])
        with tracing.span("serve.fused", live=int(active.sum()), width=s_w):
            self._fused_dispatch(active, k, s_w)

    def _fused_dispatch(self, active: np.ndarray, k: int, s_w: int) -> None:
        eng = self.engine
        chunk = eng.prefill_chunk
        n = self.n_slots
        role = np.array([s is not None and bool(s.pending)
                         for s in self.slots])
        decode_mask = active & ~role
        tokens = np.zeros((n, s_w), np.int32)
        n_valid = np.ones((n,), np.int32)
        t_idx = np.zeros((n,), np.int32)
        take = np.zeros((n,), np.int32)
        nonces = np.array([s.nonce if s is not None else 0
                           for s in self.slots], np.int32)
        d = (self.spec.propose(self._tok, decode_mask)
             if self.spec is not None and decode_mask.any() else None)
        for j, slot in enumerate(self.slots):
            if slot is None:
                continue
            if slot.pending:
                c = min(len(slot.pending), chunk)
                tokens[j, :c] = slot.pending[:c]
                n_valid[j] = take[j] = c
                # t_idx stays 0: a completing prompt samples token 0 with
                # key (nonce, 0), exactly like whole-prompt admission
            else:
                tokens[j, 0] = self._tok[j, 0]
                t_idx[j] = len(slot.emitted)
                if d is not None:
                    tokens[j, 1:k + 1] = d[j]
                    n_valid[j] = k + 1
        with tracing.span("serve.fused.dispatch"):
            layers, staging, sampled, g, logits = eng.fused_step(
                self.cache, jnp.asarray(tokens), n_valid, self.key,
                nonces=nonces, t_idx=t_idx, active=jnp.asarray(active),
                staging=self.staging,
                role=role if self.staging is not None else None)
        with tracing.span("serve.fused.sync"):
            g_np = np.asarray(g)
            sampled_np = np.asarray(sampled)
        if d is not None:
            accepted = self.spec.accept(d, g_np, decode_mask)
            steps = np.where(role, take, accepted).astype(np.int32)
        else:
            accepted = None
            steps = np.where(role, take,
                             active.astype(np.int32)).astype(np.int32)
        self.cache = eng.commit_verified(self.cache, layers,
                                         jnp.asarray(steps),
                                         active=jnp.asarray(active))
        if staging is not None:
            self.staging = staging
        if d is not None:
            self.spec.commit(accepted, g_np, decode_mask)
        self.clock += s_w               # one dispatch of width s_w
        if d is not None:
            # the spec propose ran this round too: its k+1 draft steps
            # are priced at the draft's roofline byte share (0 for n-gram
            # — same rule as _spec_round)
            self.clock += (k + 1) * self.spec.draft_step_cost(self.cache)
        for j, slot in enumerate(self.slots):
            if slot is None:
                continue
            if role[j]:
                del slot.pending[:int(take[j])]
                if slot.pending:
                    continue            # still prefilling next round
                n_prompt = len(slot.req.prompt)
                if self.staging is not None:
                    # quantized: whole-prompt-calibrated re-quantization
                    # of the staged rows (bit-identical to the codes
                    # whole-prompt admission writes)
                    if self._paged:
                        cover = self._slot_pages[j][
                            :kvq.page_count(n_prompt, eng.page_size)]
                        self.cache = paging.finalize_slot_pages(
                            self.cache, self.staging, j, n_prompt, cover)
                    else:
                        self.cache = kv_cache.finalize_slot(
                            self.cache, self.staging, j, n_prompt)
                if self._paged and slot.plan is not None:
                    # full-miss admission registers its prefix now (the
                    # pages/grids/logits are final only at completion)
                    self._register_prefix(
                        j, slot.req, slot.plan,
                        logits[j:j + 1, int(n_valid[j]) - 1])
                    slot.plan = None
                self._begin_decode(j, slot, int(sampled_np[j]))
            elif accepted is not None:
                done = False
                for t in g_np[j, :int(accepted[j])]:
                    slot.emitted.append(int(t))
                    self._record_emit(slot.req.uid)
                    if self._finish_reason(slot) is not None:
                        done = True
                        break
                if done:
                    self._evict(slot, j)
                else:
                    self._tok[j, 0] = slot.emitted[-1]
            else:
                t = int(sampled_np[j])
                slot.emitted.append(t)
                self._record_emit(slot.req.uid)
                if self._finish_reason(slot) is not None:
                    self._evict(slot, j)
                else:
                    self._tok[j, 0] = t

    # ------------------------------------------------------------ telemetry
    def latency_report(self) -> dict:
        """Deterministic step-count latency percentiles (the bench gate).

        The sim clock ticks in MODEL-STEP units: a prefill costs its
        padded token count, a scanned decode chunk one unit per step
        (emissions land at successive steps), a fused/verify dispatch its
        token width (emissions land as a burst at dispatch end), and a
        policy-draft propose its k+1 steps times the draft's roofline
        byte share of a target step (fractional units).  TTFT =
        first-emission clock minus submit clock; inter-token = gaps
        between consecutive emissions of one request, and the p99/max gap
        IS the head-of-line stall a long-prompt admission inflicts on its
        batchmates.  Identical across runs for a fixed workload + chunk
        geometry — no wall-clock noise, so benchmarks/check_bench can
        gate hard on the chunked-vs-whole ratio.
        """
        ttfts, gaps = [], []
        for uid, emits in self._emit_clocks.items():
            ttfts.append(emits[0] - self._submit_clock.get(uid, 0))
            # float, not int: policy-draft rounds tick fractional clock
            # units (draft steps priced by their roofline byte share)
            gaps.extend(float(b - a) for a, b in zip(emits, emits[1:]))

        def pcts(xs):
            if not xs:
                return {"p50": 0.0, "p95": 0.0, "p99": 0.0, "max": 0.0}
            a = np.asarray(xs, np.float64)
            return {"p50": float(np.percentile(a, 50, method="nearest")),
                    "p95": float(np.percentile(a, 95, method="nearest")),
                    "p99": float(np.percentile(a, 99, method="nearest")),
                    "max": float(a.max())}

        return {"unit": "model_steps", "clock": round(float(self.clock), 4),
                "n_requests": len(self._emit_clocks),
                "n_tokens": int(sum(len(v)
                                    for v in self._emit_clocks.values())),
                "ttft": pcts(ttfts), "inter_token": pcts(gaps)}

    def dispatch_audit(self) -> dict:
        """Measured jit-cache entries per serving dispatch vs the
        documented ceiling (``ServeEngine.dispatch_budget`` with THIS
        scheduler's prompt bucket).  ``over`` nonempty means some call
        pattern retraces beyond the written contract — the recompile bug
        class ``repro.analysis`` gates on across workload sweeps."""
        sizes = self.engine.jit_cache_sizes()
        budget = self.engine.dispatch_budget(self.prompt_bucket)
        over = {k: {"traces": v, "budget": budget[k]}
                for k, v in sizes.items() if k in budget and v > budget[k]}
        return {"sizes": sizes, "budget": budget, "over": over}

    def _finish_reason(self, slot: _Slot) -> Optional[str]:
        if not slot.emitted:
            return None                 # still prefilling (chunked)
        if slot.req.eos_id is not None \
                and slot.emitted[-1] == slot.req.eos_id:
            return "eos"
        if len(slot.emitted) >= slot.req.max_new_tokens:
            return "length"
        return None

    def _evict(self, slot: _Slot, j: int) -> None:
        reason = self._finish_reason(slot) or "length"
        self.completed[slot.req.uid] = Completion(
            uid=slot.req.uid, prompt_len=len(slot.req.prompt),
            tokens=list(slot.emitted), finish_reason=reason)
        self.slots[j] = None
        if self.spec is not None:
            self.spec.evict(j)
        if self._paged and self._slot_pages[j] is not None:
            # drop this slot's mappings; pages return to the free list
            # only at refcount 0 (a prefix the registry or another slot
            # still holds stays resident)
            self.allocator.release(self._slot_pages[j])
            self._slot_pages[j] = None
            # and UNMAP the table row: until re-admission this slot keeps
            # decoding as an inactive lane, and with max_seq % page != 0
            # its pinned position is in table range — a stale entry would
            # route the write into a freed (possibly re-allocated) page
            self.cache = paging.set_table_rows(self.cache, j, [])


def serve_all(engine: ServeEngine, requests: Sequence[Request],
              n_slots: int = 4, prompt_bucket: int = 16,
              key: Optional[jax.Array] = None,
              share_prefixes: bool = True) -> Dict[str, Completion]:
    """Convenience one-shot: submit everything, drain, return completions."""
    sched = ContinuousBatchingScheduler(engine, n_slots=n_slots,
                                        prompt_bucket=prompt_bucket, key=key,
                                        share_prefixes=share_prefixes)
    for r in requests:
        sched.submit(r)
    return sched.run()
