"""The sequence scorer's own account of its steps' device time, read
where the program keeps it: the ``seq.wait.step`` spans a recorded pass
leaves under its ``seq.wait`` (one a timed step, in dispatch order, with
``length_class``, ``real_tokens``, ``padded_tokens`` and ``device_ms``:
the step's completion less the later of the step before's completion
and its own dispatch, from ordered waits on the host's clock) and the
``device_ms`` their sum leaves on the ``seq.score_pass`` root. A
program that writes none of them (an older commit, the tracer off), or
a pass whose steps do not add up to its tokens (spans dropped from the
buffer), gives ``None``: no number, never a wrong one.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from benchmark import trace

ROOT, WAIT, STEP = "seq.score_pass", "seq.wait", "seq.wait.step"
STEP_PROGRAM = "jit__run_step"      # ``RouteScorer._run_step`` in a trace


def window_passes(ctx: Dict) -> Optional[List[Dict]]:
    """``{"pass_ms", "device_ms", "steps": [attributes …]}`` for each of
    the last ``counts["passes"]`` passes, oldest first."""
    n = int(ctx["counts"].get("passes", 0))
    if n <= 0:
        return None
    try:
        from routest_tpu.obs import get_tracer
    except ImportError:
        return None
    spans = get_tracer().buffer.snapshot()
    roots = [s for s in spans if s["name"] == ROOT][-n:]
    if len(roots) < n:
        return None
    root_of = {s["span_id"]: s["parent_id"] for s in spans
               if s["name"] == WAIT}
    steps: Dict[str, List[Dict]] = {r["span_id"]: [] for r in roots}
    for s in spans:
        if s["name"] == STEP and "device_ms" in s["attrs"]:
            root = root_of.get(s["parent_id"])
            if root in steps:
                steps[root].append(s["attrs"])
    out = []
    for r in roots:
        mine = steps[r["span_id"]]
        if (r["attrs"].get("device_ms") is None or not mine
                or sum(a["real_tokens"] for a in mine)
                != r["attrs"].get("real_tokens")):
            return None
        out.append({"pass_ms": r["duration_ms"],
                    "device_ms": r["attrs"]["device_ms"], "steps": mine})
    return out


def by_class(ctx: Dict) -> Optional[Dict[int, Dict[str, float]]]:
    """Per length class over the window's passes: ``device_ms``,
    ``real_tokens``, ``padded_tokens``, ``steps`` and ``us_per_token``
    (device microseconds a REAL token)."""
    passes = window_passes(ctx)
    if passes is None:
        return None
    out: Dict[int, Dict[str, float]] = {}
    for p in passes:
        for a in p["steps"]:
            c = out.setdefault(int(a["length_class"]), {
                "device_ms": 0.0, "real_tokens": 0, "padded_tokens": 0,
                "steps": 0})
            c["device_ms"] += a["device_ms"]
            c["real_tokens"] += a["real_tokens"]
            c["padded_tokens"] += a["padded_tokens"]
            c["steps"] += 1
    for c in out.values():
        if c["real_tokens"] <= 0:
            return None
        c["us_per_token"] = 1e3 * c["device_ms"] / c["real_tokens"]
    return out


def class_us_per_token(ctx: Dict, longest: bool) -> Optional[float]:
    classes = by_class(ctx)
    if not classes:
        return None
    return classes[(max if longest else min)(classes)]["us_per_token"]


def pass_unaccounted_pct(ctx: Dict) -> Optional[float]:
    """Share of the window's passes that no step's device time accounts
    for: 100 × (1 − Σ ``device_ms`` ÷ Σ ``seq.score_pass``)."""
    passes = window_passes(ctx)
    if passes is None:
        return None
    whole = sum(p["pass_ms"] for p in passes)
    if whole <= 0.0:
        return None
    return 100.0 * (1.0 - sum(p["device_ms"] for p in passes) / whole)


def device_gap_pct(ctx: Dict) -> Optional[float]:
    """How far the program's own step times are from the device
    trace's: 100 × |Σ ``device_ms`` − Σ runs of the step programs in
    the traced window| ÷ the latter."""
    passes = window_passes(ctx)
    if passes is None or ctx.get("trace") is None:
        return None
    traced = sum(trace.module_runs(ctx["trace"], ctx["lo"], ctx["hi"],
                                   STEP_PROGRAM))
    if traced <= 0.0:
        return None
    own = sum(p["device_ms"] for p in passes) / 1e3
    return 100.0 * abs(own - traced) / traced
