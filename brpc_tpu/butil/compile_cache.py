"""Persistent XLA compile cache for the entry points.

Every transfer program, collective and serving step is compiled per shape;
a process that starts with no compiled code pays all of them again.  Entry
points (``chip_smoke.py``, ``benchmarks/run.py``, the examples'
``main``s) call :func:`enable` once before their first jit — the
library never does it at import, so embedding applications keep control of
their own cache.

Placement rule: where ``JAX_COMPILATION_CACHE_DIR`` is set jax already uses
that directory and this module sets no path; where it is not, the cache is
``<checkout>/.jax_cache`` (git-ignored) — a fixed path, because the path is
part of what makes a cache findable by the next process.
"""
from __future__ import annotations

import os

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable() -> str:
    """Turn the persistent compile cache on; returns the directory used."""
    import jax
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = _DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # the fabric's programs are many and small (a transfer program
    # compiles in well under jax's default 1 s floor): cache them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir
