"""Autoscale bench: SLO survival through a 10× flash crowd, measured.

The ISSUE-6 acceptance bar, end to end: a REAL fleet (supervisor +
serving worker processes + in-process gateway + autoscaler) is driven
by the open-loop generator (``routest_tpu/loadgen``) through a 10×
flash crowd and a compressed diurnal curve. The artifact must show

- the autoscaler scaling up during the spike and back down after,
- availability/latency SLOs out of ``page`` at the end of each
  scenario (or recovered within the fast window),
- a bounded shed rate (admission control degrades overload into 429s
  while the fleet grows — never a collapse),
- the same seed reproducing the same offered-load schedule, and
- a closed-loop vs open-loop comparison on the same overload exposing
  the coordinated-omission gap in recorded p99.

Rates are CALIBRATED, not hardcoded: a short closed-loop phase
measures one replica's capacity ``C`` on this host, then the flash
crowd offers ``C/8 → 10×`` (guaranteed overload at the spike on any
host) and the diurnal curve crests at ``1.2 C``. The artifact records
``C`` and the host shape; on a 1-core container extra replicas
time-share the core, so the scenario proves the CONTROL LOOP
(decisions, membership, drain, SLO state), not parallel speedup —
``host.note`` says so, same honesty contract as ``bench_fleet.py``.

Usage: python scripts/bench_autoscale.py [--quick] [--seed 42]
       [--scenarios flash_crowd diurnal closed_vs_open]
       [--out artifacts/autoscale.json]
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

MODEL = os.path.join(REPO, "artifacts", "eta_mlp.msgpack")


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


def boot_fleet(args, autoscale: bool, recorder_dir: str,
               queue_depth: int = 32):
    """→ (supervisor, gateway, autoscaler-or-None, base_url). One real
    serving worker to start; the autoscaler grows it. Replicas share
    the one XLA compile cache (``core/cache.py``) so scaled-up workers
    reuse the first boot's compilations."""
    from routest_tpu.core.config import (AutoscaleConfig, FleetConfig,
                                         RecorderConfig)
    from routest_tpu.obs.recorder import FlightRecorder, configure_recorder
    from routest_tpu.serve.fleet.autoscaler import Autoscaler
    from routest_tpu.serve.fleet.gateway import Gateway
    from routest_tpu.serve.fleet.supervisor import ReplicaSupervisor

    configure_recorder(FlightRecorder(RecorderConfig(
        dir=os.path.join(recorder_dir, "gateway"), min_interval_s=0.0)))
    # Cross-replica SSE needs the hermetic TCP broker (same wiring as
    # ``python -m routest_tpu.serve.fleet``): a tracker tick published
    # on a scaled-up replica must reach subscribers held on r0.
    from routest_tpu.serve.netbus import start_broker

    broker, _ = start_broker()
    env = dict(os.environ)
    env.update({
        "REDIS_URL": f"tcp://127.0.0.1:{broker.port}",
        "ROUTEST_FORCE_CPU": "1",
        "ROUTEST_MESH": "0",
        "ROUTEST_WARM_BUCKETS": "0",   # elastic boots: compile lazily
        "ETA_MODEL_PATH": MODEL,
        "RTPU_RECORDER_DIR": os.path.join(recorder_dir, "workers"),
        "RTPU_RECORDER_MIN_INTERVAL_S": "0",
    })
    ports = [_free_port()]
    sup = ReplicaSupervisor(ports, env=env, cwd=REPO,
                            probe_interval_s=0.5, backoff_base_s=0.2,
                            backoff_cap_s=2.0)
    sup._bench_broker = broker     # torn down in shutdown_fleet
    sup.start()
    if not sup.ready(timeout=300):
        sup.drain(timeout=10)
        broker.shutdown()
        raise RuntimeError("initial fleet worker never became ready")
    cfg = FleetConfig(hedge=False, eject_after=3, cooldown_s=1.0,
                      max_inflight=32, queue_depth=queue_depth)
    gw = Gateway([("127.0.0.1", p) for p in ports], cfg, supervisor=sup)
    httpd = gw.serve("127.0.0.1", 0)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    scaler = None
    if autoscale:
        # Constructed but NOT started: the calibration phase saturates
        # the 1-replica fleet on purpose, and a live controller would
        # (correctly!) scale against it — scenarios start the ticker
        # once the measured phase begins, so every decision in the
        # history is attributable to the offered scenario load.
        scaler = Autoscaler(sup, gw, AutoscaleConfig(
            enabled=True, min_replicas=1, max_replicas=args.max_replicas,
            tick_s=0.5, up_queue_frac=0.25, up_outstanding=8.0,
            up_burn=6.0, up_stable_ticks=2, up_step=1, up_cooldown_s=8.0,
            down_outstanding=1.0, down_stable_ticks=10, down_step=1,
            down_cooldown_s=10.0, startup_timeout_s=180.0,
            drain_timeout_s=10.0))
    return sup, gw, scaler, base


def shutdown_fleet(sup, gw, scaler):
    from routest_tpu.obs.recorder import configure_recorder

    try:
        if scaler is not None:
            scaler.stop()
        gw.drain(timeout=5)
    finally:
        sup.drain(timeout=20)
        broker = getattr(sup, "_bench_broker", None)
        if broker is not None:
            broker.shutdown()
        configure_recorder(None)


def warm(base: str, workload) -> None:
    from routest_tpu.loadgen import KeepAliveClient

    client = KeepAliveClient(base, timeout=120.0)
    try:
        for req in workload.sequence(4):
            client.send(req)
    finally:
        client.close()


def measure_capacity(base: str, workload, seconds: float) -> float:
    """Closed-loop ceiling of the current (1-replica) fleet in ok-rps —
    the calibration constant every scenario's rates derive from."""
    from routest_tpu.loadgen import run_closed_loop, summarize

    # 32 workers = the gateway's max_inflight: enough closed-loop
    # concurrency to actually saturate the replica (8 workers measured
    # the CLIENT's concurrency limit, ~40% under the true ceiling).
    records = run_closed_loop([base], workload.sequence(100_000),
                              workers=32, duration_s=seconds)
    rep = summarize(records, seconds, len(records), loop="closed")
    return max(5.0, rep["achieved_rps"])


class FleetWatcher:
    """Samples gateway fleet size + SLO state once a second while a
    scenario runs — the replica-count-vs-load timeline the acceptance
    criteria are judged on."""

    def __init__(self, gw) -> None:
        self.gw = gw
        self.samples = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self) -> None:
        t0 = time.monotonic()
        while not self._stop.is_set():
            with self.gw._lock:
                live = sum(1 for r in self.gw.replicas if not r.draining)
                draining = sum(1 for r in self.gw.replicas if r.draining)
                queued = self.gw._waiters
                inflight = self.gw._inflight
            slo_state = "n/a"
            if self.gw.slo is not None:
                self.gw.slo.tick()
                slo_state = self.gw.slo.worst_state()
            pending = 0
            if self.gw.autoscaler is not None:
                with self.gw.autoscaler._lock:
                    pending = len(self.gw.autoscaler._pending)
            self.samples.append({
                "t": round(time.monotonic() - t0, 1),
                "replicas": live, "draining": draining,
                "pending": pending, "queued": queued,
                "inflight": inflight, "slo": slo_state,
            })
            self._stop.wait(1.0)

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=5)

    def max_replicas(self) -> int:
        return max((s["replicas"] for s in self.samples), default=0)

    def slo_states(self) -> list:
        return [s["slo"] for s in self.samples]


def scenario_flash_crowd(args) -> dict:
    """Base → 10× spike → base, autoscaler on. Pure Zipf predict
    traffic so the PR-4 cache sees realistic key skew (hit-rate delta
    recorded from registry snapshots)."""
    from routest_tpu.loadgen import (RateCurve, ZipfODWorkload, cache_delta,
                                     fetch_metrics, poisson_schedule,
                                     run_open_loop, summarize, timeline)

    recorder_dir = tempfile.mkdtemp(prefix="autoscale-pm-")
    sup, gw, scaler, base = boot_fleet(args, autoscale=True,
                                       recorder_dir=recorder_dir)
    try:
        workload = ZipfODWorkload(s=args.zipf_s, seed=args.seed)
        warm(base, workload)
        capacity = measure_capacity(base, workload, args.calibrate_s)
        time.sleep(1.0)          # calibration queue drains
        scaler.start()           # every decision now belongs to the run
        base_rate = max(2.0, capacity / 8.0)
        spike_rate = base_rate * 10.0          # ≈ 1.25 × capacity
        duration = args.baseline_s + args.spike_s + args.recovery_s
        curve = RateCurve.flash_crowd(base_rate, 10.0, args.baseline_s,
                                      args.spike_s)
        offsets = poisson_schedule(curve, duration, seed=args.seed)
        # Determinism receipt: the identical seed regenerates the
        # identical schedule (array-equal) and request sequence.
        offsets2 = poisson_schedule(curve, duration, seed=args.seed)
        reproducible = (len(offsets) == len(offsets2)
                        and bool((offsets == offsets2).all())
                        and workload.sequence(64)
                        == ZipfODWorkload(s=args.zipf_s,
                                          seed=args.seed).sequence(64))
        requests = workload.sequence(len(offsets))
        metrics_before = fetch_metrics(base, replicas=True)
        run_t0 = time.time()
        with FleetWatcher(gw) as watcher:
            records = run_open_loop([base], offsets, requests,
                                    workers=args.workers, timeout=35.0)
            # Keep watching (and keep the SLO engine ticking) until the
            # fleet is back to min size or the wait budget lapses — the
            # "and back down" half of the acceptance bar.
            settle_deadline = time.monotonic() + args.settle_s
            while time.monotonic() < settle_deadline:
                with gw._lock:
                    live = sum(1 for r in gw.replicas if not r.draining)
                pending = len(scaler._pending)
                if live <= 1 and pending == 0:
                    break
                time.sleep(1.0)
        metrics_after = fetch_metrics(base, replicas=True)
        report = summarize(records, duration, len(offsets))
        spike_lo, spike_hi = args.baseline_s, args.baseline_s + args.spike_s
        ups = [h for h in scaler.snapshot()["history"]
               if h.get("direction") == "up" and "phase" not in h]
        # Attribution: the decision must land in (or just after — the
        # hysteresis ticks) the spike window, not during baseline.
        ups_in_spike = [h for h in ups
                        if spike_lo <= h["t"] - run_t0 <= spike_hi + 10.0]
        downs = [h for h in scaler.snapshot()["history"]
                 if h.get("direction") == "down"
                 and h.get("phase") == "stopped"]
        joins = [h for h in scaler.snapshot()["history"]
                 if h.get("phase") == "joined"]
        slo_states = watcher.slo_states()
        final_fleet = gw.snapshot()["fleet"]
        out = {
            "capacity_rps_1_replica": round(capacity, 1),
            "offered": {"base_rps": round(base_rate, 1),
                        "spike_rps": round(spike_rate, 1),
                        "spike_window_s": [spike_lo, spike_hi],
                        "curve": curve.spec, "seed": args.seed,
                        "arrivals": len(offsets)},
            "schedule_reproducible": reproducible,
            "load": report,
            "load_timeline": timeline(records, bucket_s=2.0),
            "fleet_timeline": watcher.samples,
            "cache": cache_delta(metrics_before, metrics_after),
            "autoscale": {
                "up_decisions": len(ups),
                "up_decisions_in_spike_window": len(ups_in_spike),
                "down_decisions": len(downs),
                "joins": [{k: h[k] for k in ("replica", "boot_s")
                           if k in h} for h in joins],
                "max_replicas_seen": watcher.max_replicas(),
                "final_replicas": final_fleet["replica_count"],
                "history": scaler.snapshot()["history"],
            },
            "slo": {
                "states_seen": sorted(set(slo_states)),
                "final_state": slo_states[-1] if slo_states else "n/a",
                "paged": "page" in slo_states,
                "recovered": (slo_states[-1] != "page"
                              if slo_states else False),
            },
        }
        out["pass"] = bool(
            len(ups_in_spike) >= 1
            and watcher.max_replicas() >= 2
            and len(downs) >= 1
            and out["autoscale"]["final_replicas"] <= 1
            and report["error_rate"] <= args.max_error_rate
            and report["shed_rate"] <= args.max_shed_rate
            and out["slo"]["recovered"]
            and reproducible)
        return out
    finally:
        shutdown_fleet(sup, gw, scaler)
        shutil.rmtree(recorder_dir, ignore_errors=True)


def scenario_diurnal(args) -> dict:
    """One compressed day: mixed Zipf predict + history reads under a
    sinusoid cresting above one replica's capacity, with SSE
    subscribers held open across the whole curve. Pass = fleet size
    tracks the curve (up near the crest, back to min after the trough)
    with ~zero errors."""
    from routest_tpu.loadgen import (MixedWorkload, RateCurve, SseClients,
                                     poisson_schedule, run_open_loop,
                                     summarize, timeline)

    recorder_dir = tempfile.mkdtemp(prefix="autoscale-pm-")
    sup, gw, scaler, base = boot_fleet(args, autoscale=True,
                                       recorder_dir=recorder_dir)
    try:
        workload = MixedWorkload(
            mix={"predict_eta": 0.87, "history": 0.08,
                 "update_tracker": 0.05},
            s=args.zipf_s, seed=args.seed)
        warm(base, workload.od)
        capacity = measure_capacity(base, workload.od, args.calibrate_s)
        time.sleep(1.0)
        scaler.start()
        period = args.diurnal_period_s
        curve = RateCurve.diurnal(base=max(1.0, capacity / 10.0),
                                  peak=capacity * 1.2, period_s=period,
                                  phase_s=0.0)   # trough at t=0
        duration = period + args.settle_s
        offsets = poisson_schedule(curve, period, seed=args.seed + 1)
        requests = workload.sequence(len(offsets))
        with FleetWatcher(gw) as watcher, \
                SseClients(base, n=2,
                           channel=workload.sse_channel) as sse:
            records = run_open_loop([base], offsets, requests,
                                    workers=args.workers, timeout=35.0)
            settle_deadline = time.monotonic() + args.settle_s
            while time.monotonic() < settle_deadline:
                with gw._lock:
                    live = sum(1 for r in gw.replicas if not r.draining)
                if live <= 1 and not scaler._pending:
                    break
                time.sleep(1.0)
            sse_snap = sse.snapshot()
        report = summarize(records, duration, len(offsets))
        hist = scaler.snapshot()["history"]
        ups = [h for h in hist
               if h.get("direction") == "up" and "phase" not in h]
        downs = [h for h in hist if h.get("phase") == "stopped"]
        out = {
            "capacity_rps_1_replica": round(capacity, 1),
            "offered": {"curve": curve.spec, "seed": args.seed + 1,
                        "arrivals": len(offsets)},
            "workload": workload.describe(),
            "sse": sse_snap,
            "load": report,
            "load_timeline": timeline(records, bucket_s=5.0),
            "fleet_timeline": watcher.samples,
            "autoscale": {"up_decisions": len(ups),
                          "down_decisions": len(downs),
                          "max_replicas_seen": watcher.max_replicas(),
                          "final_replicas":
                          gw.snapshot()["fleet"]["replica_count"],
                          "history": hist},
            "slo": {"final_state": watcher.slo_states()[-1]
                    if watcher.samples else "n/a"},
        }
        out["pass"] = bool(
            len(ups) >= 1
            and watcher.max_replicas() >= 2
            and out["autoscale"]["final_replicas"] <= 1
            and report["error_rate"] <= args.max_error_rate
            and out["slo"]["final_state"] != "page"
            and sse_snap["connected"] == sse_snap["requested"]
            and sse_snap["events"] > 0)
        return out
    finally:
        shutdown_fleet(sup, gw, scaler)
        shutil.rmtree(recorder_dir, ignore_errors=True)


def scenario_closed_vs_open(args) -> dict:
    """The coordinated-omission receipt: the SAME overloaded fixed
    1-replica fleet (autoscaler off), measured both ways. The
    closed-loop harness throttles itself to the server's pace, so its
    recorded p99 stays near the service time; the open-loop harness
    charges every request its wait from the INTENDED send and exposes
    the real user-visible tail."""
    from routest_tpu.loadgen import (RateCurve, ZipfODWorkload,
                                     paced_schedule, run_closed_loop,
                                     run_open_loop, summarize)

    recorder_dir = tempfile.mkdtemp(prefix="autoscale-pm-")
    # Deep admission queue: THIS scenario wants the overload to QUEUE
    # (the backlog is what closed-loop accounting hides); the autoscale
    # scenarios keep the shallow production-shaped queue and shed.
    sup, gw, scaler, base = boot_fleet(args, autoscale=False,
                                       recorder_dir=recorder_dir,
                                       queue_depth=512)
    try:
        workload = ZipfODWorkload(s=args.zipf_s, seed=args.seed)
        warm(base, workload)
        capacity = measure_capacity(base, workload, args.calibrate_s)
        over_rate = capacity * 1.5
        dur = args.cvo_s
        # Deterministic pacing: identical offered schedule both runs.
        offsets = paced_schedule(RateCurve.constant(over_rate), dur)
        n = len(offsets)
        closed = summarize(
            run_closed_loop([base], workload.sequence(n), workers=8,
                            duration_s=dur, timeout=35.0),
            dur, n, loop="closed")
        time.sleep(2.0)   # let the queue fully drain between arms
        open_ = summarize(
            run_open_loop([base], offsets, workload.sequence(n),
                          workers=args.workers, timeout=35.0),
            dur, n)
        closed_p99 = (closed.get("latency") or {}).get("p99_ms")
        open_p99 = (open_.get("latency") or {}).get("p99_ms")
        gap = round(open_p99 / closed_p99, 2) \
            if closed_p99 and open_p99 else None
        return {
            "capacity_rps_1_replica": round(capacity, 1),
            "offered_rps": round(over_rate, 1),
            "duration_s": dur,
            "closed_loop": closed,
            "open_loop": open_,
            "coordinated_omission_p99_gap_x": gap,
            "explanation": (
                "identical server, identical offered schedule; the "
                "closed-loop arm self-throttles to the server's pace "
                "(its own achieved rps is the tell) so its p99 hides "
                "the backlog wait that open-loop accounting charges"),
            "pass": bool(gap is not None and gap >= args.min_co_gap),
        }
    finally:
        shutdown_fleet(sup, gw, scaler)
        shutil.rmtree(recorder_dir, ignore_errors=True)


SCENARIOS = {
    "flash_crowd": scenario_flash_crowd,
    "diurnal": scenario_diurnal,
    "closed_vs_open": scenario_closed_vs_open,
}


def main() -> None:
    from routest_tpu.utils.logging import get_logger

    log = get_logger("routest_tpu.bench_autoscale")
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--quick", action="store_true")
    parser.add_argument("--seed", type=int, default=42)
    parser.add_argument("--zipf-s", type=float, default=1.1)
    parser.add_argument("--workers", type=int, default=96,
                        help="open-loop sender threads")
    parser.add_argument("--max-replicas", type=int, default=3)
    parser.add_argument("--scenarios", nargs="*", default=None,
                        choices=sorted(SCENARIOS))
    parser.add_argument("--max-error-rate", type=float, default=0.01)
    parser.add_argument("--max-shed-rate", type=float, default=0.35,
                        help="shed(429) bound during the overload "
                             "scenarios — bounded load-shedding is the "
                             "design, collapse is the failure")
    parser.add_argument("--min-co-gap", type=float, default=2.0,
                        help="open-loop p99 must exceed closed-loop "
                             "p99 by at least this factor on the same "
                             "overload")
    parser.add_argument("--out", default=os.path.join(
        REPO, "artifacts", "autoscale.json"))
    args = parser.parse_args()
    if args.quick:
        args.calibrate_s = 3.0
        args.baseline_s, args.spike_s, args.recovery_s = 8.0, 20.0, 30.0
        args.settle_s = 90.0
        args.diurnal_period_s = 60.0
        args.cvo_s = 8.0
    else:
        args.calibrate_s = 5.0
        args.baseline_s, args.spike_s, args.recovery_s = 15.0, 30.0, 45.0
        args.settle_s = 150.0
        args.diurnal_period_s = 90.0
        args.cvo_s = 12.0

    results = {}
    for name in (args.scenarios or list(SCENARIOS)):
        log.info("autoscale_scenario_started", scenario=name)
        t0 = time.time()
        try:
            results[name] = SCENARIOS[name](args)
        except Exception as e:
            results[name] = {"error": f"{type(e).__name__}: {e}",
                             "pass": False}
            log.error("autoscale_scenario_failed", scenario=name,
                      error=f"{type(e).__name__}: {e}")
        results[name]["wall_s"] = round(time.time() - t0, 1)
        log.info("autoscale_scenario_finished", scenario=name,
                 ok=results[name].get("pass"),
                 wall_s=results[name]["wall_s"])

    cores = len(os.sched_getaffinity(0)) if hasattr(os, "sched_getaffinity") \
        else (os.cpu_count() or 1)
    record = {
        "generated_unix": int(time.time()),
        "host": {
            "cpu_count": cores,
            "multi_core": cores > 1,
            "note": None if cores > 1 else
            "1-core container: scaled-up replicas time-share the core, "
            "so these scenarios prove the control loop (decisions, "
            "membership changes, drains, SLO state) and bounded "
            "shedding — capacity relief from extra replicas binds on "
            "multi-core hosts",
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
        json.dump(record, f, indent=2)
    log.info("autoscale_written", path=args.out,
             all_pass=record["all_pass"])
    print(json.dumps({k: (v if k != "scenarios" else {
        n: {kk: vv for kk, vv in s.items()
            if kk in ("pass", "wall_s", "capacity_rps_1_replica",
                      "coordinated_omission_p99_gap_x", "autoscale",
                      "slo", "error")}
        for n, s in v.items()}) for k, v in record.items()}, indent=2))


if __name__ == "__main__":
    main()
