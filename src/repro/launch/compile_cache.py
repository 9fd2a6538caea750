"""Persistent compilation cache for the repo's entry points.

Called from the ``main()`` of ``chip_smoke.py`` and ``benchmarks/run.py``
only — never on import and never in tests.  JAX reads
``JAX_COMPILATION_CACHE_DIR`` itself, so when that variable is set no
directory is set here.  Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache``: the path is part of the cache key, so a
directory that moved between runs would never hit.
"""
from __future__ import annotations

import os

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def configure() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
