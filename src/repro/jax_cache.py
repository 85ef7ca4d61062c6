"""Persistent XLA compilation cache for the repo's entry points.

``chip_smoke.py``, the benchmarks and the examples call
:func:`enable_persistent_cache` once, before their first compile, so a
second process pays no cold compile for programs it has already built.
Importing ``repro`` never turns it on.

The directory is ``$JAX_COMPILATION_CACHE_DIR`` where that is set, and
otherwise the fixed ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
The path is part of the cache key, so it is never built from a temporary
name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_persistent_cache(report=print) -> str:
    """Point jax's compilation cache at ``$JAX_COMPILATION_CACHE_DIR``,
    else ``DEFAULT_CACHE_DIR``; returns the directory."""
    import jax

    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(DEFAULT_CACHE_DIR))
    jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, however small or fast to compile (the defaults
    # skip sub-second compiles, which are most of the planner's programs)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    report(f"# persistent XLA compilation cache: {path}")
    return path
