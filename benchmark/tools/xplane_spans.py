"""What ``benchmark.trace.Trace`` does not keep of an xplane, read from
the file itself: the device-idle seconds inside each host span (the
program's own ``live.retrain.*`` among them, which ``Tracer.span``
writes as ``TraceAnnotation``s) and, for the longest device operations
of one program, the named scope (``tf_op``) and source line that the
event metadata holds.

    python3 benchmark/tools/xplane_spans.py trace.xplane.pb \\
        --module jit_step --spans cycle,live.retrain,live.retrain.steps

Prints one JSON object. ``benchmark/tools/trace_dump.py`` keeps a copy
of a traced window's ``.xplane.pb``. Reads the protobuf with
TensorFlow's generated ``xplane_pb2`` (``jax.profiler.ProfileData``
exposes an event's stats, not its metadata's).
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import sys
from typing import Dict, List, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import program_spans, trace  # noqa: E402

DEFAULT_SPANS = ("window", "refill-window", "cycle", program_spans.ROOT,
                 *(f"{program_spans.ROOT}.{p}" for p in program_spans.PHASES))


def read_space(path: str):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def _events(plane, line):
    """(metadata, start_ns, duration_ns) of a line's events."""
    for e in line.events:
        yield (plane.event_metadata[e.metadata_id],
               line.timestamp_ns + e.offset_ps / 1e3, e.duration_ps / 1e3)


def _metadata_stats(plane, metadata) -> Dict[str, str]:
    names = {k: v.name for k, v in plane.stat_metadata.items()}
    out = {}
    for s in metadata.stats:
        kind = s.WhichOneof("value")
        value = getattr(s, kind)
        out[names[s.metadata_id]] = (names.get(value, value)
                                     if kind == "ref_value" else value)
    return out


def first_device(space):
    for plane in space.planes:
        tail = plane.name[len(trace.DEVICE_PLANE_PREFIX):]
        if plane.name.startswith(trace.DEVICE_PLANE_PREFIX) \
                and tail.isdigit():
            return plane
    raise ValueError("the xplane holds no device plane")


def host_spans(space, wanted) -> Dict[str, List[Tuple[float, float]]]:
    out: Dict[str, List[Tuple[float, float]]] = {n: [] for n in wanted}
    for plane in space.planes:
        if plane.name != trace.HOST_PLANE:
            continue
        for line in plane.lines:
            for md, start, dur in _events(plane, line):
                if md.name in out:
                    out[md.name].append((start, start + dur))
    return out


def idle_in_spans(space, wanted) -> Dict[str, Dict[str, float]]:
    """Per span name: how often it ran, its seconds, and the seconds of
    them in which no operation ran on the first device."""
    device = first_device(space)
    busy = [(s, d) for line in device.lines if line.name == trace.OPS_LINE
            for _, s, d in _events(device, line)]
    out = {}
    for name, runs in host_spans(space, wanted).items():
        if not runs:
            continue
        whole = sum(e - s for s, e in runs)
        covered = sum(trace.union_ns(trace._clip(busy, s, e))
                      for s, e in runs)
        out[name] = {"n": len(runs), "seconds": whole / 1e9,
                     "idle_s": (whole - covered) / 1e9}
    return out


def _inside(runs, start: float) -> bool:
    i = bisect.bisect_right(runs, (start, float("inf"))) - 1
    return i >= 0 and start <= runs[i][1]


def top_ops(space, module_prefix: str, n: int = 10) -> List[Dict]:
    """The ``n`` operations of the programs named ``module_prefix…``
    with the most device time, each with its scope and source."""
    device = first_device(space)
    lines = {line.name: line for line in device.lines}
    runs = sorted((s, s + d) for md, s, d in
                  _events(device, lines[trace.MODULES_LINE])
                  if md.name.startswith(module_prefix))
    total: Dict[int, List] = {}
    for md, s, d in _events(device, lines[trace.OPS_LINE]):
        if _inside(runs, s):
            rec = total.setdefault(md.id, [md, 0, 0.0])
            rec[1] += 1
            rec[2] += d
    out = []
    for md, count, ns in sorted(total.values(), key=lambda r: -r[2])[:n]:
        stats = _metadata_stats(device, md)
        out.append({"op": trace.short_name(md.name), "n": count,
                    "seconds": ns / 1e9, "ms_a_run": ns / 1e6 / len(runs),
                    "tf_op": stats.get("tf_op", ""),
                    "source": stats.get("source", ""),
                    "kind": trace.op_kind(md.name)})
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("xplane")
    ap.add_argument("--module", default="jit_step")
    ap.add_argument("--spans", default=",".join(DEFAULT_SPANS))
    ap.add_argument("--top", type=int, default=10)
    args = ap.parse_args()
    space = read_space(args.xplane)
    print(json.dumps({
        "idle_in_spans": idle_in_spans(space, args.spans.split(",")),
        "top_ops": top_ops(space, args.module, args.top)}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
