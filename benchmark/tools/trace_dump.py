"""Trace one window of a cell and write what the trace holds, for a
builder to look at before writing code against it, and to record the
small trace the tests keep:

    python3 benchmark/tools/trace_dump.py --workload od-score --seed 1 \\
        --seconds 2 --out chiprun_out/trace_od [--set n_stops=256 ...]

Writes ``summary.json`` (planes, lines, event counts, the top
operations with their stats), ``trace.json`` (the reduced
``benchmark.trace.Trace``) and a copy of the ``.xplane.pb``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--set", action="append", default=[],
                    help="key=json overriding the config or the mix")
    args = ap.parse_args()

    from benchmark import run as R
    from benchmark import trace

    manifest = R.load_json(R.REPO, "BENCHMARK.json")
    cell, config, mix = R.load_cell(manifest, args.workload)
    R.override(config, mix, args.set)
    R.require_chips(int(cell["chips"]))

    from routest_tpu.core.cache import enable_compile_cache

    enable_compile_cache()
    os.makedirs(args.out, exist_ok=True)
    scratch = tempfile.mkdtemp(prefix="routest-trace-dump-")
    try:
        mod = R.load_module("drivers", mix["driver"])
        driver = mod.Driver(R.Run(args.seed, config, mix, R.REPO, scratch))
        tdir = os.path.join(scratch, "trace")
        with trace.capture(tdir):
            driver.window(args.seconds)
        xplane = trace.find_xplane(tdir)
        shutil.copy(xplane, os.path.join(args.out, "trace.xplane.pb"))

        from jax.profiler import ProfileData

        summary = {"planes": []}
        for plane in ProfileData.from_file(xplane).planes:
            lines = []
            for line in plane.lines:
                events = list(line.events)
                by_name = {}
                for e in events:
                    rec = by_name.setdefault(e.name, [0, 0.0, None])
                    rec[0] += 1
                    rec[1] += e.duration_ns
                    if rec[2] is None:
                        rec[2] = {k: str(v)[:160] for k, v in e.stats}
                top = sorted(by_name.items(), key=lambda kv: -kv[1][1])[:25]
                lines.append({"name": line.name, "events": len(events),
                              "top": [[n, c, d, st] for n, (c, d, st) in top]})
            summary["planes"].append({"name": plane.name, "lines": lines})
        with open(os.path.join(args.out, "summary.json"), "w") as f:
            json.dump(summary, f, indent=1)
        tr = trace.load(xplane, mod.ANNOTATIONS)
        with open(os.path.join(args.out, "trace.json"), "w") as f:
            json.dump(tr.to_json(), f)
        lo, hi = trace.window_of(tr, mod.ANNOTATIONS[-1])
        print(json.dumps({
            "xplane_bytes": os.path.getsize(xplane),
            "window_s": (hi - lo) / 1e9,
            "busy_s": trace.busy_seconds(tr, lo, hi),
            "ops": trace.top(trace.op_seconds(tr, lo, hi)),
            "gaps": trace.top(trace.idle_gaps(tr, lo, hi, mod.ANNOTATIONS)),
            "planes": [[p["name"], [[l["name"], l["events"]]
                                    for l in p["lines"]]]
                       for p in summary["planes"]]}))
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
