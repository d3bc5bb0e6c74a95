"""Persistent compilation cache for the entry points that run on a chip.

A cold call compiles every kernel and jitted step again; JAX's
persistent cache keeps the compiled programs on disk, keyed among other
things by the cache path, so the path must not move between runs.
"""
from __future__ import annotations

import os
import pathlib

#: Fixed in-checkout cache path (git-ignored).
CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Put JAX's persistent compilation cache at :data:`CACHE_DIR`.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing. Returns the directory it set, else ``None``.
    Call it from an entry point (a script's ``main``), never at import.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
