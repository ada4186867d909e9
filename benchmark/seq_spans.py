"""What the sequence scorer leaves behind in process, read where the
program keeps it: the spans of the window's passes in the span buffer of
``routest_tpu.obs.get_tracer()`` (``seq.score_pass`` roots with
``seq.step`` and ``seq.wait`` children) and its counters in
``routest_tpu.obs.get_registry()``. A program that writes none of them
(an older commit, the tracer off) gives ``None``: no number, never a
wrong one.
"""

from __future__ import annotations

from typing import Dict, Optional

ROOT, WAIT = "seq.score_pass", "seq.wait"


def host_pct(ctx: Dict) -> Optional[float]:
    """Share of the window's passes in which the host was not waiting
    for the device: 100 × (1 − Σ ``seq.wait`` ÷ Σ ``seq.score_pass``)
    over the last ``counts["passes"]`` passes. The steps are dispatched
    without waiting, so a ``seq.step`` span is the host's dispatch and
    the device's time falls into the pass's one ``seq.wait``."""
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
    ids = {r["span_id"] for r in roots}
    waits = [s["duration_ms"] for s in spans
             if s["name"] == WAIT and s["parent_id"] in ids]
    whole = sum(r["duration_ms"] for r in roots)
    if len(waits) < n or whole <= 0.0:
        return None
    return 100.0 * (1.0 - sum(waits) / whole)


def _family(name: str):
    try:
        from routest_tpu.obs import get_registry
    except ImportError:
        return None
    return get_registry().get(name)


def padded_token_pct(ctx: Dict) -> Optional[float]:
    """Padded tokens computed, as a share of all tokens computed, over
    the process's passes (every pass runs the same plan)."""
    family = _family("rtpu_seq_tokens_total")
    if family is None:
        return None
    by_kind = {k[0]: c.value for k, c in family.items()}
    total = by_kind.get("real", 0.0) + by_kind.get("padded", 0.0)
    if total <= 0.0:
        return None
    return 100.0 * by_kind.get("padded", 0.0) / total


def expert_load_max_over_mean(ctx: Dict) -> Optional[float]:
    family = _family("rtpu_seq_expert_load_max_over_mean")
    if family is None or not family.items():
        return None
    return float(family.items()[0][1].value) or None
