"""The program's own spans of the window's refit cycles, read where the
program keeps them: in process, in the span buffer of
``routest_tpu.obs.get_tracer()``.

``ContinuousTrainer.run_once`` leaves one ``live.retrain`` root a cycle
with five children (aggregate, upload, steps, apply, save). The set-up
cycle, which compiles, lies before the window's cycles, so the last
``ctx["counts"]["cycles"]`` saved roots are the window's. A program
that writes no such span (an older commit, ``RTPU_OBS_TRACE=0``) gives
``None``: no number, never a wrong one.
"""

from __future__ import annotations

from typing import Dict, List, Optional

ROOT = "live.retrain"
PHASES = ("aggregate", "upload", "steps", "apply", "save")


def window_cycles(ctx: Dict) -> Optional[List[Dict[str, float]]]:
    """Milliseconds of each of the window's cycles, ``{"cycle": …,
    "<phase>": …}``, oldest first; ``None`` where a root or a child is
    missing."""
    n = int(ctx["counts"].get("cycles", 0))
    if n <= 0:
        return None
    from routest_tpu.obs import get_tracer

    spans = get_tracer().buffer.snapshot()
    roots = [s for s in spans if s["name"] == ROOT
             and s["attrs"].get("result") == "saved"][-n:]
    if len(roots) < n:
        return None
    cycles = []
    for root in roots:
        children = {s["name"]: s["duration_ms"] for s in spans
                    if s["parent_id"] == root["span_id"]}
        if any(f"{ROOT}.{p}" not in children for p in PHASES):
            return None
        cycles.append({"cycle": root["duration_ms"],
                       **{p: children[f"{ROOT}.{p}"] for p in PHASES}})
    return cycles


def phase_ms(ctx: Dict, phase: str) -> Optional[float]:
    """Mean duration of one phase over the window's cycles."""
    cycles = window_cycles(ctx)
    if cycles is None:
        return None
    return sum(c[phase] for c in cycles) / len(cycles)


def host_pct(ctx: Dict) -> Optional[float]:
    """Share of the cycles' wall time that is not the train steps."""
    cycles = window_cycles(ctx)
    if cycles is None:
        return None
    whole = sum(c["cycle"] for c in cycles)
    if whole <= 0.0:
        return None
    return 100.0 * (1.0 - sum(c["steps"] for c in cycles) / whole)
