"""Serving spans on the profiler's clock.

The scheduler marks the host work around each device dispatch with
``span``, a ``jax.profiler.TraceAnnotation``.  While a profiler session
runs (``jax.profiler.start_trace`` ... ``stop_trace``) every span lands in
that trace on the same clock as the device's programs, with its metadata
as the event's stats; otherwise a span costs about a microsecond and
records nothing.  The session is the only switch: there is no option and
no store of spans or counts in the program.

Spans, nested on the scheduler's thread:

  serve.admit              one per admission attempt; ``uid``, ``tokens``
                           (real tokens prefilled: 0 for an identical-
                           prompt hit and for a chunked claim, whose
                           prompt runs in ``serve.fused``), ``padded``
                           (width the prefill program ran); a paged
                           attempt the page pool defers carries neither
                           count and only its ``.plan`` child
    serve.admit.plan         page plan and table writes (paged only)
    serve.admit.prefill      the prefill program's dispatch
    serve.admit.cache_write  the slot's cache rows, length and prefix entry
    serve.admit.first_token  the first token's sample and its host sync
  serve.decode             one per scanned decode round; ``live`` slots,
                           ``slots``, scan ``steps``, ``inplace`` (1 when
                           the cache's rows are written in place inside
                           the layer scan, 0 when its kind is sliced per
                           layer and written back)
    serve.decode.prepare     sampling-key state and argument uploads
    serve.decode.dispatch    the decode program's dispatch
    serve.decode.sync        the wait for the round's tokens
    serve.decode.harvest     emit, stop and evict
  serve.fused, serve.spec  one per fused prefill-chunk round / speculative
                           round; ``live`` slots, token ``width``
    .dispatch, .sync         the dispatch and the wait for its tokens
"""
from __future__ import annotations

import jax

# the profiler packs metadata into the annotation's name as
# ``name#key=value,...#``: these characters in a value would split it
_SEPARATORS = str.maketrans(",=#", "___")


def span(name: str, **meta: int | str) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` with ``meta`` (ints or short strings) as
    its stats.  Use as a context manager; ``set_metadata`` on it adds
    counts known only once the work inside has begun."""
    return jax.profiler.TraceAnnotation(name, **{
        k: v.translate(_SEPARATORS) if isinstance(v, str) else v
        for k, v in meta.items()})
