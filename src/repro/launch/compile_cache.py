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


_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
           "/jax/compilation_cache/cache_misses": "cache_misses"}
_DURATIONS = {"/jax/core/compile/backend_compile_duration":
              "backend_compile_s",
              "/jax/compilation_cache/cache_retrieval_time_sec":
              "cache_retrieval_s"}
_COUNTS: dict = {}


def compile_counts() -> dict:
    """This process's compiles since the first call, kept up to date by
    ``jax.monitoring`` listeners (registered once): persistent-cache
    hits and misses, and the seconds spent in the backend compiler and
    in reading the cache. The dict is live; copy it to keep a reading.
    """
    if not _COUNTS:
        import jax

        _COUNTS.update(dict.fromkeys(_EVENTS.values(), 0))
        _COUNTS.update(dict.fromkeys(_DURATIONS.values(), 0.0))

        def on_event(event, **_):
            if event in _EVENTS:
                _COUNTS[_EVENTS[event]] += 1

        def on_duration(event, secs, **_):
            if event in _DURATIONS:
                _COUNTS[_DURATIONS[event]] += secs

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
    return _COUNTS
