"""Profiler trace → the few numbers the per-layer metrics read.

``capture`` wraps ``jax.profiler`` around a window; ``load`` turns the
xplane it wrote into a :class:`Trace` of plain tuples, and the functions
below reduce that: the union of device-busy intervals, time per
operation, time per compiled program ("module"), and the idle gaps,
each named by the harness's own ``TraceAnnotation`` that was open on the
host while the device sat idle.

On a TPU the device planes are named ``/device:TPU:<n>``; their
``XLA Ops`` line carries one event per executed HLO operation, named by
the instruction's whole text, and their ``XLA Modules`` line one per
executed program, named ``jit_<function>(<hash>)``. An operation event
does not say which program it belongs to: it belongs to the program run
that contains its start. Host threads are lines of ``/host:CPU``; a
``TraceAnnotation`` is an event on its thread's line, on the same clock.
Everything is checked on a small trace recorded on a v5e
(``tests/benchmark/data``).
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Sequence, Tuple

Span = Tuple[str, float, float]          # name, start_ns, duration_ns


class DevicePlane(NamedTuple):
    name: str
    ops: List[Tuple[str, float, float, str]]   # name, start, dur, kind
    modules: List[Span]


class Trace(NamedTuple):
    devices: List[DevicePlane]
    host: List[Span]                     # harness annotations only

    def to_json(self) -> Dict:
        return {"devices": [{"name": d.name, "ops": d.ops,
                             "modules": d.modules} for d in self.devices],
                "host": self.host}

    @classmethod
    def from_json(cls, obj: Dict) -> "Trace":
        return cls([DevicePlane(d["name"],
                                [tuple(o) for o in d["ops"]],
                                [tuple(m) for m in d["modules"]])
                    for d in obj["devices"]],
                   [tuple(s) for s in obj["host"]])


DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


@contextlib.contextmanager
def capture(directory: str):
    """Trace the enclosed window into ``directory`` (host TraceMe's on,
    the Python tracer off: it slows the host that is being measured)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(directory, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """A host span of the harness's own, written into the trace."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no xplane under {directory}")
    return found[-1]


_KIND = re.compile(r"kind=k(\w+)")
_SHAPE = re.compile(r"= \(?(\w+\[[\d,]*\])")


def short_name(text: str) -> str:
    """``%fusion.13 = f32[1070376,64]{…} fusion(…), kind=kCustom, …`` →
    ``fusion.13 f32[1070376,64]``: short enough to print, and the
    output's shape tells the passes over arcs from those over nodes."""
    head = text.split(" = ", 1)[0].lstrip("%")
    shape = _SHAPE.search(text)
    return f"{head} {shape.group(1)}" if shape else head


def op_kind(text: str) -> str:
    """What an operation is, as far as its text tells: ``gather_scatter``
    (the TPU compiler turns gathers, scatters and segment sums into
    custom fusions that take an integer index operand), ``matmul``
    (convolution fusions), else ``other``."""
    body = text.split(" = ", 1)[-1]
    kind = _KIND.search(body)
    head = text.split(" = ", 1)[0]
    if (kind and kind.group(1) == "Custom" and "s32[" in body) or any(
            mark in head for mark in ("gather", "scatter")):
        return "gather_scatter"
    if "convolution" in head or (kind and kind.group(1) == "Output"):
        return "matmul"
    return "other"


def load(path: str, annotations: Iterable[str]) -> Trace:
    """Read an ``.xplane.pb``; keep device operations, device programs
    and the host spans whose names are in ``annotations``."""
    from jax.profiler import ProfileData

    wanted = set(annotations)
    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            tail = plane.name[len(DEVICE_PLANE_PREFIX):]
            if not tail.isdigit():      # e.g. a SparseCore sub-plane
                continue
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(short_name(e.name), float(e.start_ns),
                            float(e.duration_ns), op_kind(e.name))
                           for e in line.events]
                elif line.name == MODULES_LINE:
                    modules = [(e.name, float(e.start_ns),
                                float(e.duration_ns)) for e in line.events]
            devices.append(DevicePlane(plane.name, ops, modules))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events if e.name in wanted)
    host.sort(key=lambda s: s[1])
    return Trace(devices, host)


# ── reductions ───────────────────────────────────────────────────────


def union_ns(intervals: Iterable[Tuple[float, float]]) -> float:
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _clip(spans, lo: float, hi: float):
    for s, d in spans:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield a, b


def window_of(trace: Trace, outer: str) -> Tuple[float, float]:
    """The traced window: from the start of the first ``outer``
    annotation to the end of the last, or the extent of the device
    events where the host line is missing."""
    spans = [(s, s + d) for n, s, d in trace.host if n == outer]
    if not spans:
        spans = [(s, s + d) for dev in trace.devices
                 for _, s, d, _ in dev.ops]
    if not spans:
        raise ValueError("the trace holds no device operation")
    return min(s for s, _ in spans), max(e for _, e in spans)


def busy_seconds(trace: Trace, lo: float, hi: float) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    if not trace.devices:
        return 0.0
    per = [union_ns(_clip(((s, d) for _, s, d, _ in dev.ops), lo, hi))
           for dev in trace.devices]
    return sum(per) / len(per) / 1e9


def ops_in(trace: Trace, lo: float, hi: float, module_prefix: str = ""):
    """(name, seconds, kind) of the first device's operations inside the
    window; with ``module_prefix``, only those that start inside a run
    of a program whose name starts with it. The seconds are an
    operation's own: a ``while`` or a ``conditional`` lies on the same
    line as the operations of its body and spans them, so what its
    children took is taken off it and no time is counted twice."""
    if not trace.devices:
        return
    dev = trace.devices[0]
    runs = sorted((s, s + d) for n, s, d in dev.modules
                  if n.startswith(module_prefix)) if module_prefix else None
    starts = [r[0] for r in runs] if runs else []
    kept = []                      # [name, clipped ns, kind, end]
    for name, s, d, kind in sorted(dev.ops, key=lambda o: (o[1], -o[2])):
        if runs is not None:
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s > runs[i][1]:
                continue
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            kept.append([name, b - a, kind, s, s + d])
    open_ops = []                  # the operations that span this one
    for op in kept:
        while open_ops and not (op[3] < open_ops[-1][4]
                                and op[4] <= open_ops[-1][4]):
            open_ops.pop()
        if open_ops:
            open_ops[-1][5] -= op[1]
        op.append(op[1])           # own ns, less what its children take
        open_ops.append(op)
    for name, _, kind, _, _, own in kept:
        if own > 0.0:
            yield name, own / 1e9, kind


def op_seconds(trace: Trace, lo: float, hi: float,
               module_prefix: str = "") -> Dict[str, float]:
    """Device seconds per operation name."""
    out: Dict[str, float] = {}
    for name, seconds, _ in ops_in(trace, lo, hi, module_prefix):
        out[name] = out.get(name, 0.0) + seconds
    return out


def kind_seconds(trace: Trace, lo: float, hi: float,
                 module_prefix: str = "") -> Dict[str, float]:
    """Device seconds per kind of operation (see :func:`op_kind`)."""
    out: Dict[str, float] = {}
    for _, seconds, kind in ops_in(trace, lo, hi, module_prefix):
        out[kind] = out.get(kind, 0.0) + seconds
    return out


def module_runs(trace: Trace, lo: float, hi: float,
                prefix: str) -> List[float]:
    """Durations (s) of the runs of the programs named ``prefix…`` that
    lie wholly inside the window, on the first device."""
    if not trace.devices:
        return []
    return [d / 1e9 for n, s, d in trace.devices[0].modules
            if n.startswith(prefix) and s >= lo and s + d <= hi]


def idle_gaps(trace: Trace, lo: float, hi: float,
              priority: Sequence[str]) -> Dict[str, float]:
    """Idle seconds of the first device inside the window, by the
    harness annotation open on the host during each gap; where several
    are open, the first of ``priority`` (innermost first) names it."""
    if not trace.devices:
        return {}
    busy = sorted(_clip(((s, d) for _, s, d, _ in trace.devices[0].ops),
                        lo, hi))
    gaps, cursor = [], lo
    for s, e in busy:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    out: Dict[str, float] = {}
    for name in list(priority) + ["unattributed"]:
        out[name] = 0.0
    for g0, g1 in gaps:
        left = [(g0, g1)]
        for name in priority:
            spans = [(s, s + d) for n, s, d in trace.host if n == name]
            nxt = []
            for a, b in left:
                cut = sorted((max(a, s), min(b, e)) for s, e in spans
                             if min(b, e) > max(a, s))
                pos = a
                for s, e in cut:
                    if s > pos:
                        nxt.append((pos, s))
                    out[name] += max(0.0, e - max(pos, s)) / 1e9
                    pos = max(pos, e)
                if b > pos:
                    nxt.append((pos, b))
            left = nxt
        out["unattributed"] += sum(b - a for a, b in left) / 1e9
    return {k: v for k, v in out.items() if v > 0.0}


def idle_pct(ctx: Dict):
    """Share of the traced window in which no operation ran on the
    device (what every ``device_idle_pct.*`` reader returns)."""
    if ctx["window_s"] <= 0.0 or ctx["busy_s"] <= 0.0:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])


def top(items: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(items.items(),
                                      key=lambda kv: -kv[1])[:n]]
