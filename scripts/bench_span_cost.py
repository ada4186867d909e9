"""What one ``trace_span`` costs, with and without the bridge that holds
a ``jax.profiler.TraceAnnotation`` open for a recorded span.

``bench_obs_overhead.py``'s method at the scale of one span: identical
child processes that differ only in their mode, each timing the same
empty ``with trace_span(...)`` loop; the parent never touches JAX.

- ``off``       tracer disabled (the shared no-op span);
- ``no_jax``    span recorded in a process that never imported ``jax``
                (the gateway's tier): no annotation is made;
- ``jax``       span recorded with ``jax`` imported, no capture running:
                the annotation is a flag test in the runtime;
- ``capture``   the same inside a running ``jax.profiler`` capture with
                the benchmark's options (host tracer on, Python tracer
                off): every span is also written into the xplane;
- ``capture_py`` the same under ``start_trace``'s defaults, which
                ``obs/profiler.py`` uses: the Python tracer is on too
                and records every call the span machinery makes.

Usage: python scripts/bench_span_cost.py [--spans 200000] [--repeats 5]
Prints one JSON line per mode: nanoseconds a span, best and median of
the repeats.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODES = ("off", "no_jax", "jax", "capture", "capture_py")

# The package's ``__init__`` imports JAX, so the child reaches the
# stdlib-only ``routest_tpu.obs`` through a bare package object, and
# imports JAX itself only in the modes that are about it.
_CHILD = r"""
import json, statistics, sys, tempfile, time, types
mode, n, repeats, pkg_path = sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4]
pkg = types.ModuleType("routest_tpu"); pkg.__path__ = [pkg_path]
sys.modules["routest_tpu"] = pkg
from routest_tpu.obs import Tracer, configure_tracer, trace_span
if mode != "off" and mode != "no_jax":
    import jax
tracer = configure_tracer(Tracer(enabled=mode != "off", sample_rate=1.0))
if mode.startswith("capture"):
    options = jax.profiler.ProfileOptions()
    if mode == "capture":
        options.python_tracer_level = 0
    jax.profiler.start_trace(tempfile.mkdtemp(prefix="span-cost-"),
                             profiler_options=options)
readings = []
for _ in range(repeats + 1):                    # the first warms up
    t0 = time.perf_counter_ns()
    for _ in range(n):
        with trace_span("bench.span"):
            pass
    readings.append((time.perf_counter_ns() - t0) / n)
if mode.startswith("capture"):
    jax.profiler.stop_trace()
readings = readings[1:]
print(json.dumps({"mode": mode, "spans": n, "repeats": repeats,
                  "ns_a_span_best": min(readings),
                  "ns_a_span_median": statistics.median(readings),
                  "jax_imported": "jax" in sys.modules,
                  "recorded": len(tracer.buffer)}))
"""


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--spans", type=int, default=200_000)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--modes", default=",".join(MODES))
    args = ap.parse_args()
    for mode in args.modes.split(","):
        # a capture keeps every event: fewer spans fit its buffer
        n = min(args.spans, 50_000) if mode.startswith("capture") \
            else args.spans
        proc = subprocess.run(
            [sys.executable, "-c", _CHILD, mode, str(n), str(args.repeats),
             os.path.join(REPO, "routest_tpu")],
            capture_output=True, text=True, cwd=REPO, timeout=600)
        if proc.returncode != 0:
            print(proc.stderr[-2000:], file=sys.stderr)
            return proc.returncode
        print(proc.stdout.strip().splitlines()[-1], flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
