"""Persistent XLA compilation cache for the program's entry points.

Every cold process compiles every bucketed device program again; with the
cache on, a later process that compiles the same program for the same
device reads it back instead.  The cache directory is part of every entry's
lookup, so it is a fixed path, never a temporary name.

Library import never touches the cache: only entry points call
:func:`enable_compile_cache` (``launch/serve.py``, ``benchmarks/run.py``,
``chip_smoke.py``).
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_DIR", "enable_compile_cache"]

# <checkout>/.jax_cache (git-ignored): src/repro/runtime/ -> checkout root
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
    sets nothing; otherwise the cache goes to :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
