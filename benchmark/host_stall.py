"""What the host's kernel says it withheld from a pass or a cycle: the
``psi_cpu_ms`` (``/proc/pressure/cpu``, some task of the machine waited
for a CPU) and ``steal_ms`` (``/proc/stat``, the hypervisor ran someone
else) that ``routest_tpu/obs/host.py`` leaves on the program's root
spans, summed over the window's roots. Milliseconds of the MACHINE, not
of the process: a number to set beside a pass that took too long, not a
share of it. ``None`` where a root is missing or carries neither (an
older commit, the tracer off, a machine without either file).
"""

from __future__ import annotations

from typing import Dict, Optional

SOURCES = ("psi_cpu_ms", "steal_ms")


def stall_ms(ctx: Dict, root: str, count: str,
             result: Optional[str] = None) -> Optional[float]:
    """Over the last ``ctx["counts"][count]`` spans named ``root`` (with
    the attribute ``result``, where given)."""
    n = int(ctx["counts"].get(count, 0))
    if n <= 0:
        return None
    try:
        from routest_tpu.obs import get_tracer
    except ImportError:
        return None
    roots = [s["attrs"] for s in get_tracer().buffer.snapshot()
             if s["name"] == root
             and (result is None or s["attrs"].get("result") == result)][-n:]
    if len(roots) < n or any(
            all(a.get(k) is None for k in SOURCES) for a in roots):
        return None
    return sum(a.get(k) or 0.0 for a in roots for k in SOURCES)
