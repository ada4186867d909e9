"""What the host did to a root span: a pair of readings, one at each
end, of the kernel's and the interpreter's own accounts.

A pass or a refit cycle that took seconds too long with the device idle
was starved by something on the host, and the span alone cannot say by
what. :func:`begin` reads, and :func:`end` sets as the span's attributes, the
growth over the span of

- ``psi_cpu_ms`` / ``psi_io_ms`` / ``psi_mem_ms``: ``/proc/pressure/
  {cpu,io,memory}``, the ``some`` line's ``total``: time in which at
  least one runnable task of the MACHINE waited for a CPU, for I/O, for
  memory (not this process alone: a busy neighbour raises it too);
- ``steal_ms`` / ``iowait_ms``: ``/proc/stat``'s aggregate ``cpu``
  line, summed over the CPUs, so either may exceed the span;
- ``nivcsw`` / ``majflt``: ``getrusage(RUSAGE_SELF)``: times a thread
  of this process was taken off a CPU it still wanted, and page faults
  that went to disk;
- ``cpu_ms``: ``time.process_time()``, all threads;
- ``gc_ms``: the collector's pauses, from ``gc.callbacks`` (installed
  by the first :func:`begin`).

Reading a stalled span: ``steal_ms`` says the hypervisor, ``psi_cpu_ms``
with ``nivcsw`` says neighbours on the shared cores, ``psi_mem_ms`` /
``psi_io_ms`` / ``majflt`` / ``iowait_ms`` say paging, ``gc_ms`` the
collector; none of them beside a long span and a small ``cpu_ms`` says
the wait was under JAX. No thread, no sampling: two reads of four small
files (opened once a process) and three calls a span. A source the
machine does not have leaves its attribute out; nothing raises. A
sandboxed kernel may have the file and keep no account in it: the TPU
hosts this repository is measured on have no ``/proc/pressure`` and a
``/proc/stat`` whose ``cpu`` line is all zeros, count no context
switches and tick ``process_time`` in steps of 10 ms, so there the
account is ``cpu_ms`` and ``gc_ms`` beside zeros, and a stalled pass is
read from WHICH span grew (a ``seq.step``: the host's dispatch; a
``seq.wait.step``'s ``device_ms``: the device or the runtime under it).
"""

from __future__ import annotations

import gc
import os
import time
from typing import Dict, Optional, Tuple

try:
    import resource
except ImportError:                 # no such module off POSIX
    resource = None

PRESSURE = {"psi_cpu_ms": "/proc/pressure/cpu",
            "psi_io_ms": "/proc/pressure/io",
            "psi_mem_ms": "/proc/pressure/memory"}
STAT = "/proc/stat"

_gc_s = 0.0             # seconds the collector has run since the hook
_gc_t0: Optional[float] = None
_gc_hooked = False


def _on_gc(phase: str, info: Dict) -> None:
    global _gc_s, _gc_t0
    if phase == "start":
        _gc_t0 = time.perf_counter()
    elif _gc_t0 is not None:
        _gc_s += time.perf_counter() - _gc_t0
        _gc_t0 = None


def parse_pressure(text: str) -> Optional[float]:
    """Milliseconds of the ``some`` line's ``total`` (the file counts
    microseconds); None where the text has no such field."""
    for line in text.splitlines():
        fields = line.split()
        if fields and fields[0] == "some":
            for field in fields[1:]:
                key, _, value = field.partition("=")
                if key == "total":
                    try:
                        return int(value) / 1000.0
                    except ValueError:
                        return None
    return None


def parse_stat(text: str, ticks_per_s: float) -> Optional[Tuple[float,
                                                                float]]:
    """(``steal``, ``iowait``) milliseconds of the aggregate ``cpu``
    line (user nice system idle iowait irq softirq steal …, in clock
    ticks); None where the line is missing or short."""
    for line in text.splitlines():
        fields = line.split()
        if fields and fields[0] == "cpu":
            try:
                iowait, steal = int(fields[5]), int(fields[8])
            except (IndexError, ValueError):
                return None
            return (steal * 1000.0 / ticks_per_s,
                    iowait * 1000.0 / ticks_per_s)
    return None


_fds: Optional[Dict[str, int]] = None    # source → descriptor, kept open


def _open() -> Dict[str, int]:
    """The files this machine has, opened once a process: a reading is
    then one ``pread`` a file (an open and a close cost more than the
    read, tenfold under a sandboxed kernel)."""
    fds = {}
    for name, path in {**PRESSURE, "stat": STAT}.items():
        try:
            fds[name] = os.open(path, os.O_RDONLY)
        except OSError:
            pass
    return fds


def _read(fd: int) -> Optional[str]:
    try:
        return os.pread(fd, 1024, 0).decode("ascii", "replace")
    except OSError:
        return None


def _now() -> Dict[str, float]:
    out = {"cpu_ms": time.process_time() * 1000.0, "gc_ms": _gc_s * 1000.0}
    for name, fd in (_fds or {}).items():
        text = _read(fd)
        if text is None:
            continue
        if name != "stat":
            value = parse_pressure(text)
            if value is not None:
                out[name] = value
            continue
        try:
            both = parse_stat(text, float(os.sysconf("SC_CLK_TCK")))
        except (ValueError, OSError):
            both = None
        if both is not None:
            out["steal_ms"], out["iowait_ms"] = both
    if resource is not None:
        usage = resource.getrusage(resource.RUSAGE_SELF)
        out["nivcsw"], out["majflt"] = usage.ru_nivcsw, usage.ru_majflt
    return out


def begin(span) -> Optional[Dict[str, float]]:
    """The readings at the start of ``span``, for :func:`end`; None, and
    nothing read, where the span is not recorded."""
    global _gc_hooked, _fds
    if not span.sampled:
        return None
    if not _gc_hooked:
        gc.callbacks.append(_on_gc)
        _gc_hooked = True
    if _fds is None:
        _fds = _open()
    return _now()


def end(span, start: Optional[Dict[str, float]]) -> None:
    """Set on ``span`` the growth of each reading both ends could take."""
    if start is None:
        return
    now = _now()
    for name, was in start.items():
        if name in now:
            span.set_attr(name, round(now[name] - was, 3))
