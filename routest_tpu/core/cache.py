"""Persistent XLA compilation cache.

Every entry point in this framework pays a trace+compile cost on first
call (seconds for the serving buckets and the road solver's
``while_loop`` programs). XLA can persist compiled executables to disk
and reload them across process restarts; this module is the one switch
that turns that on, so server restarts, replicas of one fleet, and the
phases of one chip run skip recompilation.

Placement rule — the directory is part of every entry's key, so it must
be the same path in every process that should share compiles:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already holds that directory
  (it reads the variable itself); nothing here names one.
- unset: ``COMPILE_CACHE_DIR``, one fixed git-ignored path inside the
  checkout.

Either way the two size/time floors are dropped so *everything* is
cached — this framework's programs are small relative to disk, and the
ones worth caching most (the serving buckets, the road solver) are
exactly the ones a floor would skip.
"""

from __future__ import annotations

import os
from typing import Optional

COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Turn on the persistent compilation cache; returns the directory
    in use, or None when the in-checkout default cannot be created (a
    read-only checkout runs uncached rather than not at all)."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    try:
        os.makedirs(COMPILE_CACHE_DIR, exist_ok=True)
    except OSError:
        return None
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR
