"""Faults planted in the timed path, to show that ``correct`` catches them.

Each takes the engine and scheduler ``serving.build`` made and breaks the
decode dispatch underneath the harness: the tokens a chunk emits altered
where they are produced, the cache handed back unchanged (a copy taken
before the dispatch, with its sharding, so a dispatch that donates its
input cache is caught as well), or half the slots left uncomputed (what
all-zero logits give: token 0).  On a mesh the exchange between chips can
also be left out (``MESH_FAULTS``).  Emitting the
token fed in again would be no fault on a model whose greedy answer
repeats one token, as internlm2's seeded weights do.  ``run.py
--fault <name>`` plants one in a whole run on the chip; the tests plant
them at a size the CPU holds.  No benchmark run plants any.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _wrap_decode(engine, post, keep_input=False):
    step = engine.decode_chunk_step

    def broken(cache, tok, key, **kw):
        before = jax.tree.map(jnp.copy, cache) if keep_input else cache
        new, nxt, toks = step(cache, tok, key, **kw)
        return post(before, tok, new, nxt, toks)

    engine.decode_chunk_step = broken


def token_altered(engine, sched):
    vocab = engine.cfg.vocab
    _wrap_decode(engine, lambda c, f, new, nxt, toks:
                 (new, nxt, (toks + 1) % vocab))


def state_unchanged(engine, sched):
    _wrap_decode(engine, lambda c, f, new, nxt, toks: (c, nxt, toks),
                 keep_input=True)


def half_batch_left_out(engine, sched):
    def post(c, fed, new, nxt, toks):
        kept = jnp.arange(toks.shape[0]) < toks.shape[0] // 2
        return new, nxt, jnp.where(kept[:, None], toks, 0)
    _wrap_decode(engine, post)


def exchange_left_out(engine, sched):
    """Every psum of the engine's programs dropped while they are traced:
    each chip keeps its partial sums (a fault only a mesh can have)."""
    def drop(call):
        def broken(*args, **kw):
            psum = jax.lax.psum
            jax.lax.psum = lambda x, *a, **k: x
            try:
                return call(*args, **kw)
            finally:
                jax.lax.psum = psum
        return broken

    engine._prefill = drop(engine._prefill)
    engine.decode_chunk_step = drop(engine.decode_chunk_step)


FAULTS = {f.__name__: f for f in (token_altered, state_unchanged,
                                  half_batch_left_out)}
MESH_FAULTS = {f.__name__: f for f in (exchange_left_out,)}
