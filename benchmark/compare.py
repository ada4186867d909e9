"""The comparison that decides ``correct``: each number compared has a
limit of its own; a run is correct when every number is finite and at
or under its limit. Limits live in the traffic mix's file (``limits``),
set from on-chip readings as PERF.md records."""

from __future__ import annotations

import math
from typing import Dict, List, NamedTuple


class Check(NamedTuple):
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def with_limits(values: Dict[str, float], limits: Dict[str, float]) -> List[Check]:
    """Pair each limited number with its limit; a number that has a
    limit and was not produced reads as infinite (never correct)."""
    return [Check(name, float(values.get(name, math.inf)), float(limit))
            for name, limit in limits.items()]


def verdict(checks: List[Check]) -> bool:
    return bool(checks) and all(c.ok for c in checks)


def as_json(checks: List[Check]) -> Dict:
    return {c.name: {"value": c.value, "limit": c.limit} for c in checks}


def as_lines(checks: List[Check]) -> List[str]:
    return [f"check {c.name}: value {c.value:.6g} limit {c.limit:.6g} "
            f"{'ok' if c.ok else 'FAIL'}" for c in checks]


def norm_gap(got: float, want: float, floor: float) -> float:
    """Gap between two norms, against the reference's norm or a floor
    (the median leaf's), whichever is larger."""
    return abs(got - want) / max(want, floor, 1e-30)
