"""Placement of JAX's persistent compilation cache for entry points.

Called from an entry point's ``main``, never on import: where the caller's
environment sets ``JAX_COMPILATION_CACHE_DIR``, JAX already reads it and
nothing is changed; otherwise the cache goes to one fixed directory inside
the checkout, so a later process on the same checkout finds what an
earlier one compiled (the directory is part of the cache key, so it must
not move between runs)."""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turns the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
