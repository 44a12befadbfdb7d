"""Persistent XLA compilation cache placement for the entry points.

Called by the launchers' ``main`` (and ``chip_smoke.py``), never at
import: a test importing a launcher must not switch a process-wide cache
on. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
nothing is set here; otherwise the cache lives at a FIXED path inside
the checkout (``<checkout>/.jax_cache``, gitignored) — the path is part
of the cache key's reach, so a directory that moved would never hit.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
