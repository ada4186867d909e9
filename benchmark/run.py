"""The benchmark's one entry.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration in
``benchmark/configs/<config>.json``, its traffic mix in
``benchmark/traffic/<traffic>.json``, the mix's driver in
``benchmark/drivers/<driver>.py`` and, in a traced run, each per-layer
metric's reader in ``benchmark/metrics/<metric>.py``. Prints one JSON
object as the last line of standard output. Needs the chips the cell
asks for: without them it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()       # process start, as near as Python gets

import argparse                  # noqa: E402
import importlib.util            # noqa: E402
import json                      # noqa: E402
import os                        # noqa: E402
import shutil                    # noqa: E402
import sys                       # noqa: E402
import tempfile                  # noqa: E402
from typing import Dict, List, NamedTuple, Optional   # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(REPO, "benchmark")
if REPO not in sys.path:
    sys.path.insert(0, REPO)


class Run(NamedTuple):
    """What a driver is given."""
    seed: int
    config: Dict
    mix: Dict
    repo: str
    scratch: str


class NoChipError(RuntimeError):
    pass


def load_json(*parts: str) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` by file, so that a name may hold
    dots and dashes."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}._{abs(hash(name))}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find_cell(manifest: Dict, name: str) -> Dict:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def load_cell(manifest: Dict, name: str):
    """(cell, configuration, traffic mix) of the cell ``name``."""
    cell = find_cell(manifest, name)
    return (cell, load_json(HERE, "configs", cell["config"] + ".json"),
            load_json(HERE, "traffic", cell["traffic"] + ".json"))


def override(config: Dict, mix: Dict, items: List[str]) -> None:
    """``key=json`` items laid over the configuration or the mix: for
    the tools and a rehearsal at a toy size, never for a run."""
    for item in items:
        key, value = item.split("=", 1)
        (config if key in config else mix)[key] = json.loads(value)


def metrics_of(manifest: Dict, group: str, cell: str,
               reported: Optional[List[str]] = None) -> List[Dict]:
    """The manifest's metrics of ``group`` that this cell reports: those
    that list it under ``workloads``; one without the key belongs to
    every cell (end to end) or to every cell that reports the metric it
    moves (per layer)."""
    out = []
    for m in manifest[group]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif group == "end_to_end" or m["moves"] in (reported or []):
            out.append(m)
    return out


def summary(durations: List[float]) -> Dict:
    """Wall seconds of the window's operations (passes, cycles): not a
    metric, but what a reader wants when a rate reads far off."""
    if not durations:
        return {}
    d = sorted(durations)
    return {"n": len(d), "min": d[0], "median": d[len(d) // 2],
            "max": d[-1], "first": durations[0],
            "slowest_at": durations.index(d[-1])}


def require_chips(n: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChipError(
            f"the benchmark needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < n:
        raise NoChipError(f"the cell needs {n} chips; JAX found "
                          f"{len(devices)}")
    return devices[:n]


class CompileCounter:
    """Counts XLA backend compilations (a hit in the persistent cache
    counts too: inside a window neither may happen)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        from jax import monitoring

        self.n = 0
        monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.n += 1


def device_block(devices) -> Dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def execute(manifest: Dict, cell: Dict, config: Dict, mix: Dict, seed: int,
            seconds: float, traced: bool, devices, t0: float) -> Dict:
    """Everything of a run after the look for a chip: set-up, the
    window, the metrics, then the comparison. Returns the result."""
    from benchmark import compare, trace

    from routest_tpu.core.cache import enable_compile_cache

    enable_compile_cache()
    compiles = CompileCounter()
    to_chip_s = time.perf_counter() - t0
    scratch = tempfile.mkdtemp(prefix="routest-benchmark-")
    try:
        driver_mod = load_module("drivers", mix["driver"])
        driver = driver_mod.Driver(Run(seed, config, mix, REPO, scratch))
        setup_s = time.perf_counter() - t0
        compiled_in_setup = compiles.n
        if traced:
            seconds = min(seconds, float(mix.get("trace_seconds", seconds)))
            trace_dir = os.path.join(scratch, "trace")
            with trace.capture(trace_dir):
                driver.window(seconds)
        else:
            driver.window(seconds)
        values = driver.end_to_end()
        values["setup_s"] = setup_s
        e2e = metrics_of(manifest, "end_to_end", cell["name"])
        device = device_block(devices)
        result = {"correct": False, "attempted": driver.attempted,
                  "failed": driver.failed,
                  "operation_s": summary(driver.durations),
                  "setup_parts_s": {"to_chip": to_chip_s,
                                    "driver": setup_s - to_chip_s},
                  "compiles": {"setup": compiled_in_setup,
                               "window": compiles.n - compiled_in_setup}}
        if traced:
            tr = trace.load(trace.find_xplane(trace_dir),
                            driver_mod.ANNOTATIONS)
            lo, hi = trace.window_of(tr, driver_mod.ANNOTATIONS[-1])
            ctx = {"trace": tr, "lo": lo, "hi": hi,
                   "window_s": (hi - lo) / 1e9,
                   "busy_s": trace.busy_seconds(tr, lo, hi),
                   "counts": driver.counts(), "config": config, "mix": mix,
                   "device_kind": device["kind"], "chips": len(devices)}
            metrics = {}
            for m in metrics_of(manifest, "per_layer", cell["name"],
                                [e["name"] for e in e2e]):
                value = load_module("metrics", m["name"]).read(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": float(value),
                                          "unit": m["unit"]}
            device["busy_s"] = ctx["busy_s"]
            device["window_s"] = ctx["window_s"]
            result["breakdown"] = {
                "device_ops": trace.top(trace.op_seconds(tr, lo, hi)),
                "idle_gaps": trace.top(trace.idle_gaps(
                    tr, lo, hi, driver_mod.ANNOTATIONS))}
        else:
            metrics = {m["name"]: {"value": float(values[m["name"]]),
                                   "unit": m["unit"]} for m in e2e}
        result["metrics"] = metrics
        result["device"] = device
        # the comparison runs last: the window is closed, the peak is
        # read, and the program's state is dropped
        driver.release()
        t_check = time.perf_counter()
        checks = driver.check()
        result["check_s"] = time.perf_counter() - t_check
        result["correct"] = compare.verdict(checks) and driver.failed == 0
        result["checks"] = compare.as_json(checks)
        for line in compare.as_lines(checks):
            print(line, file=sys.stderr)
        return result
    finally:
        shutil.rmtree(scratch, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(REPO, "routest_tpu")):
        print("benchmark: no program to measure beside the benchmark "
              f"(no routest_tpu/ in {REPO})", file=sys.stderr)
        return 4
    manifest = load_json(REPO, "BENCHMARK.json")
    cell, config, mix = load_cell(manifest, args.workload)
    try:
        devices = require_chips(int(cell["chips"]))
    except NoChipError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 4
    result = execute(manifest, cell, config, mix, args.seed, args.seconds,
                     bool(args.trace), devices, _T0)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
