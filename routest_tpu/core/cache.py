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

The same switch starts the program's own count of what its compiles
cost: one ``jax.monitoring`` duration listener (:func:`count_compiles`)
keeps ``rtpu_compile_seconds_total{stage}`` and ``rtpu_compiles_total
{stage}`` for the stages of :data:`STAGES`. ``backend`` is what JAX
times round "compile or fetch from the persistent cache", so a hit
raises it too and ``cache_load`` is the part of it spent fetching.
``trace`` holds each second once: JAX reports a jitted function traced
inside another's trace as an event of its own, inside the outer one's
time, and the listener takes the inner seconds off the outer event (per
thread, from the events' own ends and durations); its count is of every
trace event, inner ones too. :func:`compile_seconds` is what a span
reads at its two ends to say ``compile_ms``.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, Optional, Tuple

COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


# jax.monitoring's event → the ``stage`` label
STAGES: Dict[str, str] = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}

_listening = False
_children: Optional[Dict[str, Tuple]] = None    # stage → (seconds, count)
_traces = threading.local()     # .open: [start, seconds] of counted traces


def _on_duration(event: str, duration: float, **_) -> None:
    stage = STAGES.get(event)
    if stage is None or _children is None:
        return
    seconds, count = _children[stage]
    count.inc()
    if stage == "trace":
        # the traces that began after this one did lie inside it
        start, inner = time.time() - duration, 0.0
        stack = getattr(_traces, "open", None)
        if stack is None:
            stack = _traces.open = []
        while stack and stack[-1][0] >= start:
            inner += stack.pop()[1]
        stack.append((start, duration))
        del stack[:-4096]
        duration = max(0.0, duration - inner)
    seconds.inc(duration)


def count_compiles() -> None:
    """Register the listener (once a process) and bind the two families
    in the process registry (again after ``_children`` was set to None:
    a test with a registry of its own)."""
    global _listening, _children
    if _children is None:
        from routest_tpu.obs import get_registry

        reg = get_registry()
        seconds = reg.counter(
            "rtpu_compile_seconds_total",
            "Seconds this process spent making device programs, by stage: "
            "trace (Python to jaxpr, each second once), lower (jaxpr to "
            "StableHLO), backend (XLA compile or persistent-cache fetch), "
            "cache_load (the fetch alone, inside backend).", ("stage",))
        count = reg.counter(
            "rtpu_compiles_total",
            "Events behind rtpu_compile_seconds_total, by stage (trace: "
            "every traced function, nested ones too).", ("stage",))
        _children = {stage: (seconds.labels(stage=stage),
                             count.labels(stage=stage))
                     for stage in STAGES.values()}
    if not _listening:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True


def compile_seconds(stage: str = "backend") -> float:
    """Seconds counted so far for ``stage``; 0.0 where nothing counts."""
    return _children[stage][0].value if _children else 0.0


def enable_compile_cache() -> Optional[str]:
    """Turn on the persistent compilation cache and the count of
    compiles; returns the directory in use, or None when the in-checkout
    default cannot be created (a read-only checkout runs uncached rather
    than not at all)."""
    import jax

    count_compiles()
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
