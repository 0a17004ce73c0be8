"""Where JAX keeps its persistent compilation cache.

A cache entry is keyed on the directory too, so the directory must not
move between runs: a temporary name, a pid or a time would never hit.
Called by entry points that drive the device, never at import.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache(default_dir) -> str:
    """Keep the cache in ``$JAX_COMPILATION_CACHE_DIR`` when it is set (JAX
    reads it itself, and nothing else is set), else in ``default_dir``, a
    fixed path the caller picks inside its checkout.  Returns the directory
    in use."""
    import jax

    placed = os.environ.get(ENV)
    if placed:
        return placed
    path = str(Path(default_dir).resolve())
    jax.config.update("jax_compilation_cache_dir", path)
    return path
