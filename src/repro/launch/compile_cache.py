"""Persistent XLA compile cache for the entry points.

Each entry point's ``main`` calls ``enable_compile_cache()`` first (never
at import), so a second run of the same program reloads its compiled
executables instead of compiling again.  ``JAX_COMPILATION_CACHE_DIR``,
when set, names the directory; otherwise the cache sits at one fixed
path inside the checkout (``.jax_cache``, git-ignored).  The path is
part of the cache's key, so it never depends on a temp name, a PID or
the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
