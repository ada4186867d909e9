"""Run a cell exactly as ``run.py`` does and keep what the PROGRAM said
about the run beside the result, for a builder to read a stalled pass,
a step's device time by length class or a set-up by stage from:

    python3 benchmark/tools/run_kept.py --workload route-lm-kexaone-mixed \\
        --seed 2147487013 --seconds 30 --trace 0 --out chiprun_out/kept

Prints ``run.py``'s result line as its last line of standard output and
writes ``<out>/<workload>-<seed>-<trace>.json``: the result; the
program's root spans of the whole process with their attributes
(``seq.score_pass``, ``live.retrain``: ``device_ms``, ``compile_ms``,
the host's account ``psi_cpu_ms`` / ``steal_ms`` / ``nivcsw`` / ``gc_ms``
…), every ``seq.step`` that compiled, the per-class table of
``benchmark/seq_steps.by_class`` over the window's passes with the
tokens-weighted mean beside the pass over its real tokens; the compile
counters by stage; the goodput ledger's ``seq_score`` section; and what
one pair of ``obs/host.py`` readings and one recorded span cost on this
host (microseconds, the mean of 2,000). On a commit without the spans
or counters the file holds what there is.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

_T0 = time.perf_counter()
ROOTS = ("seq.score_pass", "live.retrain")


def _costs() -> dict:
    """Microseconds of one begin/end pair and of one recorded span."""
    from routest_tpu.obs import Tracer

    out, n = {}, 2000
    tracer = Tracer(enabled=True, sample_rate=1.0)
    try:
        from routest_tpu.obs import host
    except ImportError:
        host = None
    if host is not None:
        with tracer.span("pair") as s:
            t = time.perf_counter()
            for _ in range(n):
                host.end(s, host.begin(s))
            out["host_pair_us"] = (time.perf_counter() - t) / n * 1e6
    t = time.perf_counter()
    for _ in range(n):
        with tracer.span("cost", a=1, b=2) as s:
            s.set_attr("c", 3.0)
    out["recorded_span_us"] = (time.perf_counter() - t) / n * 1e6
    return out


def kept(result: dict, passes: int) -> dict:
    from routest_tpu.obs import get_registry, get_tracer

    spans = get_tracer().buffer.snapshot()
    out = {"result": result,
           "roots": [{"name": s["name"], "ms": s["duration_ms"],
                      **s["attrs"]} for s in spans if s["name"] in ROOTS],
           "compiled_steps": [{"ms": s["duration_ms"], **s["attrs"]}
                              for s in spans if s["name"] == "seq.step"
                              and "compile_ms" in s["attrs"]]}
    try:
        from benchmark import seq_steps

        ctx = {"counts": {"passes": passes}}
        classes = seq_steps.by_class(ctx)
        if classes:
            found = seq_steps.window_passes(ctx)
            tokens = sum(c["real_tokens"] for c in classes.values())
            out["by_class"] = {str(k): v for k, v in sorted(classes.items())}
            out["us_per_token"] = {
                "weighted_over_classes": 1e3 * sum(
                    c["device_ms"] for c in classes.values()) / tokens,
                "pass_over_its_tokens": 1e3 * sum(
                    p["pass_ms"] for p in found) / tokens}
    except ImportError:
        pass
    for family in ("rtpu_compile_seconds_total", "rtpu_compiles_total"):
        metric = get_registry().get(family)
        if metric is not None:
            out[family] = {k[0]: c.value for k, c in metric.items()}
    try:
        from routest_tpu.obs.efficiency import get_ledger

        out["ledger"] = get_ledger().snapshot()["programs"].get("seq_score")
    except ImportError:
        pass
    out["costs"] = _costs()
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    from benchmark import run as R

    manifest = R.load_json(R.REPO, "BENCHMARK.json")
    cell, config, mix = R.load_cell(manifest, args.workload)
    devices = R.require_chips(int(cell["chips"]))
    result = R.execute(manifest, cell, config, mix, args.seed, args.seconds,
                       bool(args.trace), devices, _T0)
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(
        args.out, f"{args.workload}-{args.seed}-{args.trace}.json")
    with open(path, "w") as f:
        json.dump(kept(result, int(result["operation_s"].get("n", 0))), f,
                  indent=1, default=str)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
