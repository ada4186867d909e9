"""Rollout bench: safe change delivery, measured end to end.

The ISSUE-7 acceptance bar against a REAL fleet (supervisor + serving
worker processes + in-process gateway + rollout controller) under
open-loop loadgen traffic:

- ``hot_swap`` — ≥3 consecutive verified model hot-swaps land on a
  serving replica under load with ZERO client 5xx and the SLO engine
  never paging; then three bad artifacts (corrupt bytes, NaN weights,
  wildly divergent weights) are each REJECTED by the golden-batch gate
  with the old model still serving.
- ``boot_crash`` / ``corrupt_artifact`` / ``slo_regression`` — three
  distinct bad deploys rolled out through the canary state machine,
  each auto-rolled back (crash-loop watch, /api/health verify gate,
  canary-vs-baseline SLO comparison), with blast radius bounded to the
  canary traffic fraction and the rollback decision + offending version
  captured in a flight-recorder bundle (manifest embedded in the
  artifact).
- ``rollout_good`` — a healthy new version canaries, bakes clean, and
  promotes across the fleet with zero client 5xx.

Same host-honesty contract as ``bench_autoscale.py``: a 1-core
container proves the CONTROL machinery (gates, comparisons, rollbacks,
drains), not parallel capacity.

Usage: python scripts/bench_rollout.py [--quick]
       [--scenarios hot_swap boot_crash corrupt_artifact
        slo_regression rollout_good]
       [--out artifacts/rollout.json]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import socket
import sys
import tempfile
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

BASE_MODEL = os.path.join(REPO, "artifacts", "eta_mlp.msgpack")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get_json(base, path, timeout=15.0):
    try:
        with urllib.request.urlopen(f"{base}{path}", timeout=timeout) as r:
            return json.loads(r.read())
    except Exception:
        return {}


def _write_bytes_atomic(path: str, blob: bytes) -> None:
    tmp = f"{path}.tmp{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(blob)
    os.replace(tmp, path)


class ModelForge:
    """Builds the good/bad artifact variants the scenarios deploy,
    from the repo's real trained model."""

    def __init__(self, workdir: str) -> None:
        import jax

        from routest_tpu.train.checkpoint import load_model, save_model

        self._save = save_model
        self._tree_map = jax.tree_util.tree_map
        self.model, self.params = load_model(BASE_MODEL)
        self.workdir = workdir

    def write(self, name: str, fn) -> str:
        path = os.path.join(self.workdir, name)
        self._save(path, self.model, self._tree_map(fn, self.params))
        return path

    def perturbed(self, name: str, scale: float) -> str:
        """A plausible retrain: tiny uniform weight scale."""
        return self.write(name, lambda x: x * (1.0 + scale))

    def nan(self, name: str) -> str:
        import numpy as np

        return self.write(name, lambda x: np.full_like(x, np.nan))

    def divergent(self, name: str) -> str:
        """Corrupted-export proxy: loads, self-checks finite, but the
        golden batch diverges by ~1e6 minutes."""
        return self.write(name, lambda x: x + 1.0e6)

    def corrupt(self, name: str) -> str:
        path = os.path.join(self.workdir, name)
        _write_bytes_atomic(path, b"garbage, not an artifact\n" * 64)
        return path


class SloWatcher:
    """Samples the gateway SLO engine while a scenario runs — the
    'never paged' witness."""

    def __init__(self, gw) -> None:
        self.gw = gw
        self.states = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self) -> None:
        while not self._stop.is_set():
            if self.gw.slo is not None:
                self.gw.slo.tick()
                self.states.append(self.gw.slo.worst_state())
            self._stop.wait(0.5)

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=5)

    def paged(self) -> bool:
        return "page" in self.states


class LoadArm:
    """Open-loop loadgen traffic running beside a scenario: started,
    then stopped once the scenario's control action settles (the
    schedule is sized generously; unsent arrivals are simply not
    offered)."""

    def __init__(self, base: str, rate: float, duration_s: float,
                 seed: int, zipf_s: float, workers: int) -> None:
        from routest_tpu.loadgen import (RateCurve, ZipfODWorkload,
                                         paced_schedule, run_open_loop)

        self._run_open_loop = run_open_loop
        self.offsets = paced_schedule(RateCurve.constant(rate), duration_s)
        self.requests = ZipfODWorkload(
            s=zipf_s, seed=seed).sequence(len(self.offsets))
        self.base = base
        self.workers = workers
        self.stop = threading.Event()
        self.records = []
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self) -> None:
        self.records = self._run_open_loop(
            [self.base], self.offsets, self.requests,
            workers=self.workers, timeout=35.0, stop=self.stop)

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self.stop.set()
        self._thread.join(timeout=60)

    def report(self) -> dict:
        from routest_tpu.loadgen import summarize

        return summarize(self.records, max(
            (r.offset_s for r in self.records), default=0.0) or 1.0,
            len(self.records))


def boot_fleet(args, n: int, recorder_dir: str,
               model_path: str, reload_sec: float = 0.0):
    """→ (supervisor, gateway, base_url). ``n`` real serving workers on
    version ``v1``; they share the one XLA cache (``core/cache.py``) so
    replacement boots are cheap."""
    from routest_tpu.core.config import FleetConfig, RecorderConfig
    from routest_tpu.obs.recorder import FlightRecorder, configure_recorder
    from routest_tpu.serve.fleet.gateway import Gateway
    from routest_tpu.serve.fleet.supervisor import ReplicaSupervisor

    configure_recorder(FlightRecorder(RecorderConfig(
        dir=os.path.join(recorder_dir, "gateway"), min_interval_s=0.0)))
    env = dict(os.environ)
    env.update({
        "ROUTEST_FORCE_CPU": "1",
        "ROUTEST_MESH": "0",
        "ROUTEST_WARM_BUCKETS": "0",
        "ETA_MODEL_PATH": model_path,
        "RTPU_VERSION": "v1",
        "RTPU_RECORDER_DIR": os.path.join(recorder_dir, "workers"),
        "RTPU_RECORDER_MIN_INTERVAL_S": "0",
    })
    if reload_sec > 0:
        env["ROUTEST_RELOAD_SEC"] = str(reload_sec)
    ports = [_free_port() for _ in range(n)]
    sup = ReplicaSupervisor(ports, env=env, cwd=REPO,
                            probe_interval_s=0.5, backoff_base_s=0.2,
                            backoff_cap_s=2.0, version="v1")
    sup.start()
    if not sup.ready(timeout=300):
        sup.drain(timeout=10)
        raise RuntimeError("fleet workers never became ready")
    cfg = FleetConfig(hedge=False, eject_after=3, cooldown_s=1.0,
                      max_inflight=32, queue_depth=64)
    gw = Gateway([("127.0.0.1", p) for p in ports], cfg, supervisor=sup,
                 version="v1")
    httpd = gw.serve("127.0.0.1", 0)
    return sup, gw, f"http://127.0.0.1:{httpd.server_address[1]}"


def shutdown_fleet(sup, gw):
    from routest_tpu.obs.recorder import configure_recorder

    try:
        gw.drain(timeout=5)
    finally:
        sup.drain(timeout=20)
        configure_recorder(None)


def measure_capacity(base: str, seed: int, zipf_s: float,
                     seconds: float) -> float:
    from routest_tpu.loadgen import (KeepAliveClient, ZipfODWorkload,
                                     run_closed_loop, summarize)

    workload = ZipfODWorkload(s=zipf_s, seed=seed)
    client = KeepAliveClient(base, timeout=120.0)
    try:
        for req in workload.sequence(4):
            client.send(req)          # warm the buckets + the cache path
    finally:
        client.close()
    records = run_closed_loop([base], workload.sequence(100_000),
                              workers=16, duration_s=seconds)
    rep = summarize(records, seconds, len(records), loop="closed")
    return max(5.0, rep["achieved_rps"])


def _bundle_manifest(bundle_path):
    if not bundle_path:
        return None
    try:
        with open(os.path.join(bundle_path, "manifest.json")) as f:
            manifest = json.load(f)
        return {"reason": manifest.get("reason"),
                "detail": manifest.get("detail"),
                "counts": manifest.get("counts")}
    except OSError:
        return None


def _swap_counts(base: str) -> dict:
    """rtpu_model_swaps_total by result, summed over replicas (read
    through the gateway's replica-metrics passthrough)."""
    payload = _get_json(base, "/api/metrics?replicas=1", timeout=30.0)
    out = {"accepted": 0, "rejected": 0}
    for rep in (payload.get("replica_metrics") or {}).values():
        fam = (rep.get("registry") or {}).get("rtpu_model_swaps_total")
        for series in (fam or {}).get("series", ()):
            result = series["labels"].get("result")
            if result in out:
                out[result] += int(series["value"])
    return out


# ── scenario: verified hot-swap under load ───────────────────────────

def scenario_hot_swap(args, forge: ModelForge) -> dict:
    recorder_dir = tempfile.mkdtemp(prefix="rollout-pm-")
    live_path = os.path.join(forge.workdir, "live.msgpack")
    shutil.copyfile(BASE_MODEL, live_path)
    sup, gw, base = boot_fleet(args, n=1, recorder_dir=recorder_dir,
                               model_path=live_path,
                               reload_sec=args.reload_sec)
    try:
        capacity = measure_capacity(base, args.seed, args.zipf_s,
                                    args.calibrate_s)
        time.sleep(1.0)
        rate = max(4.0, capacity * 0.4)

        def generation() -> int:
            return int(((_get_json(base, "/api/version").get("model")
                         or {}).get("generation")) or -1)

        gen0 = generation()
        swaps = []
        with SloWatcher(gw) as slo, \
                LoadArm(base, rate, args.load_s, args.seed, args.zipf_s,
                        args.workers) as load:
            time.sleep(2.0)
            # ≥3 good swaps: plausible retrains, each verified against
            # the live model's golden outputs before going live.
            for k in range(1, args.swaps + 1):
                src = forge.perturbed(f"good_{k}.msgpack", 1e-4 * k)
                before = generation()
                shutil.copyfile(src, f"{live_path}.stage")
                os.replace(f"{live_path}.stage", live_path)
                deadline = time.time() + 30
                while time.time() < deadline and generation() <= before:
                    time.sleep(0.2)
                swaps.append({"swap": k,
                              "generation": generation(),
                              "landed": generation() > before})
            # Three bad artifacts: each must be rejected with the old
            # generation still serving.
            rejected = []
            for name, src in (
                    ("corrupt_bytes", forge.corrupt("bad_corrupt.bin")),
                    ("nan_weights", forge.nan("bad_nan.msgpack")),
                    ("divergent_weights",
                     forge.divergent("bad_div.msgpack"))):
                before_gen = generation()
                before_rejected = _swap_counts(base)["rejected"]
                shutil.copyfile(src, f"{live_path}.stage")
                os.replace(f"{live_path}.stage", live_path)
                deadline = time.time() + 20
                now_rejected = before_rejected
                while time.time() < deadline \
                        and now_rejected <= before_rejected:
                    time.sleep(0.3)
                    now_rejected = _swap_counts(base)["rejected"]
                rejected.append({
                    "artifact": name,
                    "rejected": now_rejected > before_rejected,
                    "generation_unchanged": generation() == before_gen,
                })
            time.sleep(1.0)
        report = load.report()
        counts = _swap_counts(base)
        health = _get_json(base, "/api/health")
        model_ok = ((health.get("checks") or {}).get("model")
                    or {}).get("status") == "ok"
        versions = gw.version_skew()
        out = {
            "capacity_rps_1_replica": round(capacity, 1),
            "offered_rps": round(rate, 1),
            "initial_generation": gen0,
            "good_swaps": swaps,
            "bad_artifacts": rejected,
            "swap_counts": counts,
            "load": report,
            "slo": {"states_seen": sorted(set(slo.states)),
                    "paged": slo.paged()},
            "versions": versions,
        }
        out["pass"] = bool(
            len(swaps) >= 3
            and all(s["landed"] for s in swaps)
            and counts["accepted"] >= args.swaps
            and counts["rejected"] >= 3
            and all(r["rejected"] and r["generation_unchanged"]
                    for r in rejected)
            and model_ok
            and report["errors"] == 0
            and not slo.paged())
        return out
    finally:
        shutdown_fleet(sup, gw)
        shutil.rmtree(recorder_dir, ignore_errors=True)


# ── canary rollout scenarios ─────────────────────────────────────────

def _rollout_scenario(args, forge: ModelForge, *, version: str,
                      env: dict, expect_state: str, expect_triggers,
                      chaos_spec: str = "", fraction: float = 0.25,
                      bake_s: float = None, blast_check=None) -> dict:
    from routest_tpu import chaos
    from routest_tpu.core.config import RolloutConfig
    from routest_tpu.serve.fleet.rollout import RolloutController

    recorder_dir = tempfile.mkdtemp(prefix="rollout-pm-")
    live_path = os.path.join(forge.workdir, f"base_{version}.msgpack")
    shutil.copyfile(BASE_MODEL, live_path)
    sup, gw, base = boot_fleet(args, n=2, recorder_dir=recorder_dir,
                               model_path=live_path)
    if chaos_spec:
        chaos.configure(chaos.ChaosEngine(spec=chaos_spec,
                                          seed=args.seed))
    try:
        capacity = measure_capacity(base, args.seed, args.zipf_s,
                                    args.calibrate_s)
        time.sleep(1.0)
        rate = max(4.0, capacity * 0.4)
        ctl = RolloutController(sup, gw, RolloutConfig(
            canary_fraction=fraction, canary_replicas=1,
            bake_s=bake_s if bake_s is not None else args.bake_s,
            tick_s=0.25, max_unavailable=1, min_canary_requests=5,
            max_error_rate=0.05, max_error_ratio=3.0,
            latency_threshold_ms=args.latency_ms,
            max_latency_regression=0.25, crash_restarts=2,
            boot_timeout_s=240.0, health_timeout_s=30.0,
            drain_timeout_s=8.0))
        with SloWatcher(gw) as slo, \
                LoadArm(base, rate, args.load_s * 3, args.seed,
                        args.zipf_s, args.workers) as load:
            time.sleep(2.0)
            assert ctl.start(version, env=env)
            final = ctl.wait(timeout=600)
            time.sleep(2.0)
        report = load.report()
        snap = ctl.snapshot()
        rollback = next((h for h in snap["history"]
                         if h.get("event") == "rollback"), None)
        with gw._lock:
            fleet_versions = sorted({r.version for r in gw.replicas})
            fleet_size = len(gw.replicas)
        out = {
            "capacity_rps_1_replica": round(capacity, 1),
            "offered_rps": round(rate, 1),
            "version": version,
            "final_state": final,
            "fleet_versions": fleet_versions,
            "fleet_size": fleet_size,
            "rollback": rollback,
            "bundle": _bundle_manifest(snap.get("last_bundle")),
            "last_verdict": snap.get("last_verdict"),
            "load": report,
            "slo": {"states_seen": sorted(set(slo.states)),
                    "paged": slo.paged()},
            "history": snap["history"],
        }
        checks = [final == expect_state, fleet_size == 2]
        if expect_state == "rolled_back":
            checks += [
                rollback is not None,
                rollback and rollback.get("trigger") in expect_triggers,
                rollback and rollback.get("offending_version") == version,
                out["bundle"] is not None,
                out["bundle"] and out["bundle"]["reason"]
                == "rollout_rollback",
                fleet_versions == ["v1"],
            ]
        else:
            checks += [fleet_versions == [version],
                       report["errors"] == 0]
        if blast_check is not None:
            blast = blast_check(report)
            out["blast_radius"] = blast
            checks.append(blast["bounded"])
        out["pass"] = bool(all(checks))
        return out
    finally:
        if chaos_spec:
            from routest_tpu import chaos as _chaos

            _chaos.configure(None)
        shutdown_fleet(sup, gw)
        shutil.rmtree(recorder_dir, ignore_errors=True)


def scenario_boot_crash(args, forge: ModelForge) -> dict:
    """The new version's process exits at boot (``replica.boot.<v>``
    chaos, deterministic): the crash-loop watch rolls back before the
    canary ever joins the gateway — client traffic never sees it."""
    return _rollout_scenario(
        args, forge, version="v2-bootcrash",
        env={"RTPU_VERSION": "v2-bootcrash"},
        chaos_spec="replica.boot.v2-bootcrash:error=1.0",
        expect_state="rolled_back",
        expect_triggers={"boot_crash_loop", "boot_timeout"},
        blast_check=lambda rep: {"client_5xx": rep["errors"],
                                 "bounded": rep["errors"] == 0})


def scenario_corrupt_artifact(args, forge: ModelForge) -> dict:
    """The new version points at corrupt model bytes: the worker boots
    (degraded-not-down) but its /api/health model check fails the
    verify gate — rollback before any traffic routes to it."""
    corrupt = forge.corrupt("deploy_corrupt.bin")
    return _rollout_scenario(
        args, forge, version="v3-corrupt",
        env={"RTPU_VERSION": "v3-corrupt", "ETA_MODEL_PATH": corrupt},
        expect_state="rolled_back", expect_triggers={"verify_failed"},
        blast_check=lambda rep: {"client_5xx": rep["errors"],
                                 "bounded": rep["errors"] == 0})


def scenario_slo_regression(args, forge: ModelForge) -> dict:
    """The new version boots healthy but serves with +2.5 s device
    latency (worker-side seeded chaos): only the bake's canary-vs-
    baseline SLO comparison can catch it. Blast radius: the canary
    fraction bounds how much traffic ever saw the slow version — the
    fleet-wide median must stay under the latency threshold."""
    def blast(rep: dict) -> dict:
        lat = rep.get("latency") or {}
        p50 = lat.get("p50_ms")
        return {"p50_ms": p50, "client_5xx": rep["errors"],
                "bounded": bool(p50 is not None
                                and p50 <= args.latency_ms)}

    # Cache off on the bad version: the regression must be visible on
    # every request it serves, not amortized away by the content-
    # addressed cache warming over the Zipf head.
    return _rollout_scenario(
        args, forge, version="v4-slow",
        env={"RTPU_VERSION": "v4-slow",
             "RTPU_CHAOS_SPEC": "device.compute:latency=1.0/2500",
             "RTPU_CHAOS_SEED": str(args.seed),
             "RTPU_FASTLANE_CACHE": "0"},
        expect_state="rolled_back", bake_s=max(args.bake_s * 3, 25.0),
        expect_triggers={"canary_latency", "canary_error_rate",
                         "slo_page"},
        blast_check=blast)


def scenario_rollout_good(args, forge: ModelForge) -> dict:
    """A healthy retrain promotes: canary → clean bake → the whole
    fleet rolls to it, zero client 5xx."""
    v2 = forge.perturbed("deploy_good.msgpack", 2e-4)
    return _rollout_scenario(
        args, forge, version="v2-good",
        env={"RTPU_VERSION": "v2-good", "ETA_MODEL_PATH": v2},
        expect_state="done", expect_triggers=set())


SCENARIOS = {
    "hot_swap": scenario_hot_swap,
    "boot_crash": scenario_boot_crash,
    "corrupt_artifact": scenario_corrupt_artifact,
    "slo_regression": scenario_slo_regression,
    "rollout_good": scenario_rollout_good,
}


def main() -> None:
    from routest_tpu.utils.logging import get_logger

    log = get_logger("routest_tpu.bench_rollout")
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--quick", action="store_true")
    parser.add_argument("--seed", type=int, default=42)
    parser.add_argument("--zipf-s", type=float, default=1.1)
    parser.add_argument("--workers", type=int, default=48,
                        help="open-loop sender threads")
    parser.add_argument("--swaps", type=int, default=3,
                        help="good hot-swaps to land under load")
    parser.add_argument("--latency-ms", type=float, default=1200.0)
    parser.add_argument("--scenarios", nargs="*", default=None,
                        choices=sorted(SCENARIOS))
    parser.add_argument("--out", default=os.path.join(
        REPO, "artifacts", "rollout.json"))
    args = parser.parse_args()
    if args.quick:
        args.calibrate_s = 3.0
        args.load_s = 45.0
        args.bake_s = 8.0
        args.reload_sec = 0.25
    else:
        args.calibrate_s = 5.0
        args.load_s = 75.0
        args.bake_s = 12.0
        args.reload_sec = 0.25

    workdir = tempfile.mkdtemp(prefix="rollout-models-")
    forge = ModelForge(workdir)
    results = {}
    try:
        for name in (args.scenarios or list(SCENARIOS)):
            log.info("rollout_scenario_started", scenario=name)
            t0 = time.time()
            try:
                results[name] = SCENARIOS[name](args, forge)
            except Exception as e:
                results[name] = {"error": f"{type(e).__name__}: {e}",
                                 "pass": False}
                log.error("rollout_scenario_failed", scenario=name,
                          error=f"{type(e).__name__}: {e}")
            results[name]["wall_s"] = round(time.time() - t0, 1)
            log.info("rollout_scenario_finished", scenario=name,
                     ok=results[name].get("pass"),
                     wall_s=results[name]["wall_s"])
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    cores = len(os.sched_getaffinity(0)) if hasattr(os, "sched_getaffinity") \
        else (os.cpu_count() or 1)
    record = {
        "generated_unix": int(time.time()),
        "host": {
            "cpu_count": cores,
            "multi_core": cores > 1,
            "note": None if cores > 1 else
            "1-core container: replicas time-share the core, so these "
            "scenarios prove the change-delivery machinery (verified "
            "swaps, gates, cohort comparison, rollbacks, drains) — "
            "capacity effects bind on multi-core hosts",
        },
        "loadgen": {"zipf_s": args.zipf_s, "seed": args.seed,
                    "workers": args.workers,
                    "open_loop": "latency measured from intended send "
                                 "time (coordinated-omission-correct)"},
        "scenarios": results,
        "all_pass": all(r.get("pass") for r in results.values()),
    }
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2, default=str)
    log.info("rollout_written", path=args.out,
             all_pass=record["all_pass"])
    print(json.dumps({k: (v if k != "scenarios" else {
        n: {kk: vv for kk, vv in s.items()
            if kk in ("pass", "wall_s", "final_state", "rollback",
                      "swap_counts", "blast_radius", "slo", "error")}
        for n, s in v.items()}) for k, v in record.items()},
        indent=2, default=str))


if __name__ == "__main__":
    main()
