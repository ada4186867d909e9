"""What set-up spent making device programs, by the program's own
count: ``rtpu_compile_seconds_total{stage}``, which ``routest_tpu/core/
cache.py`` keeps from one ``jax.monitoring`` listener once the compile
cache is switched on (``run.py`` does that before the driver is built).
Read when the reader runs: after the window, before the comparison, so
the reference's programs are not in it; the result's
``compiles.window`` says whether any of it fell into the window. A
commit without the family, or a stage that counted nothing, gives
``None``.
"""

from __future__ import annotations

from typing import Optional

FAMILY = "rtpu_compile_seconds_total"


def stage_seconds(*stages: str) -> Optional[float]:
    """Summed seconds of the named stages; ``None`` unless every one of
    them has counted something."""
    try:
        from routest_tpu.obs import get_registry
    except ImportError:
        return None
    family = get_registry().get(FAMILY)
    if family is None:
        return None
    by_stage = {k[0]: c.value for k, c in family.items()}
    if any(by_stage.get(s, 0.0) <= 0.0 for s in stages):
        return None
    return sum(by_stage[s] for s in stages)
