"""Goodput ledger + efficiency watchdog end to end → artifacts/efficiency.json.

The ISSUE-17 acceptance scenario: real 2-replica fleets (supervisor +
workers + in-process gateway, live traffic where the scenario needs
metric flips) under open-loop load, with the per-replica efficiency
watchdog pinned to the committed battery curves. Two injected
efficiency regressions — each invisible to latency SLOs at this load,
because every request still answers a healthy 200 —

- ``device_slowdown``  — one replica rolled onto
  ``device.compute:latency`` chaos (the device computes 400 ms slower
  per launch; goodput craters while answers stay right);
- ``padding_blowup``   — one replica rolled onto a pathological
  single-bucket config (``RTPU_BATCH_BUCKETS=4096``: every 8-row
  launch pays a 4096-wide batch — designed-in padding waste past the
  threshold)

must each be detected by the watchdog, page the dedicated efficiency
SLO within a bounded window, and produce a flight-recorder bundle
naming the program, replica, and bucket and embedding the
expected-vs-measured curve. The ``clean`` scenario proves the other
half: across ≥1 legitimate metric flip and ≥1 verified model swap the
fleet raises ZERO efficiency pages, every replica's watchdog stays
armed on the backend-matched pin, the new families are visible in the
timeline, and the gateway's fleet rollup counts the goodput. The
``overhead`` scenario isolates the always-on ledger's cost
(``RTPU_EFF=0`` vs on, everything else off) inside the existing ≤5%
p95 observability budget.

Extract and overlay caches are shared across scenarios and runs via
``--cache-dir`` (default ``artifacts/bench_cache/efficiency``); XLA
compiles go to the one compile cache (``core/cache.py``).

Usage: python scripts/bench_efficiency.py [--quick]
       [--out artifacts/efficiency.json] [--cache-dir DIR]
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

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "scripts"))

import bench_probing as bp  # noqa: E402  (the shared fleet harness)

DETECT_BOUND_S = 120.0
# Efficiency knobs for the fleet under test: second-scale ticks and
# windows so sustained regressions page inside the bench's bound, and
# bench-calibrated thresholds — the measured clean/faulty separation
# is ~70× on ratio (clean ≥0.2 vs faulty ~0.001) and ~0.2 absolute on
# waste (clean ≤0.8 under merge, blowup ≥0.99).
EFF_ENV = {
    "RTPU_EFF": "1",
    "RTPU_EFF_WATCHDOG": "1",
    "RTPU_EFF_TICK_S": "1.0",
    "RTPU_EFF_WINDOW_S": "15",
    "RTPU_EFF_MIN_ROWS": "64",
    "RTPU_EFF_AFTER": "3",
    "RTPU_EFF_MIN_RATIO": "0.02",
    "RTPU_EFF_MAX_WASTE": "0.9",
    "RTPU_EFF_FAST_S": "10",
    "RTPU_EFF_SLOW_S": "30",
}
BATCH_ROWS = 8           # full bucket-8 launches: clean waste ≈ 0
OVERHEAD_PCT = 5.0
OVERHEAD_FLOOR_MS = 2.0


def open_loop_batch(base: str, rate: float, duration_s: float,
                    stop=None, salt: int = 0):
    """Open-loop predict_eta_batch load, every row unique (cache-miss
    by construction — cached rows are goodput the device never pays
    for, and this bench measures the device)."""
    from routest_tpu.loadgen.arrivals import RateCurve, paced_schedule
    from routest_tpu.loadgen.engine import run_open_loop
    from routest_tpu.loadgen.workload import PlannedRequest

    offsets = paced_schedule(RateCurve.constant(rate), duration_s)
    requests = [PlannedRequest(
        method="POST", path="/api/predict_eta_batch",
        body={"items": [
            {"summary": {"distance": 3000 + salt + i * BATCH_ROWS + j},
             "weather": "Sunny", "traffic": "Medium", "driver_age": 33,
             "pickup_time": "2026-08-05T18:00:00"}
            for j in range(BATCH_ROWS)]},
        route="predict_eta_batch") for i in range(len(offsets))]
    return run_open_loop([base], offsets, requests, workers=8,
                         timeout=30.0, stop=stop)


def replica_efficiency(port: int) -> dict:
    return bp._fetch(f"http://127.0.0.1:{port}/api/efficiency",
                     timeout=30)


def wait_for_efficiency_page(port: int, bound_s: float) -> dict:
    """Poll one replica's watchdog until the efficiency SLO pages
    (each poll of an armed watchdog also runs a comparison tick)."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < bound_s:
        try:
            wd = replica_efficiency(port).get("watchdog") or {}
        except OSError:
            wd = {}
        if (wd.get("pages") or 0) >= 1:
            return {"paged": True,
                    "detect_s": round(time.monotonic() - t0, 2),
                    "verdicts": wd.get("verdicts"),
                    "last_bundle": wd.get("last_bundle")}
        time.sleep(1.0)
    return {"paged": False, "detect_s": None}


def efficiency_bundles(workers_dir: str):
    """Replica-side flight-recorder bundles for efficiency pages."""
    out = []
    if not os.path.isdir(workers_dir):
        return out
    for name in sorted(os.listdir(workers_dir)):
        if "efficiency" not in name:
            continue
        bundle = os.path.join(workers_dir, name)
        try:
            evidence = json.load(open(
                os.path.join(bundle, "efficiency_evidence.json")))
            manifest = json.load(open(
                os.path.join(bundle, "manifest.json")))
        except (OSError, ValueError):
            continue
        out.append({"name": name, "evidence": evidence,
                    "manifest_reason": manifest.get("reason")})
    return out


def judge_efficiency_bundle(bundles, faulty_label: str,
                            check_prefix: str) -> dict:
    """An efficiency bundle must name the program, replica, and bucket
    and embed the expected-vs-measured curve with the offending bucket
    measured live."""
    for b in bundles:
        ev = b["evidence"]
        if ev.get("replica") != faulty_label:
            continue
        if not str(ev.get("check", "")).startswith(check_prefix):
            continue
        curve = ev.get("expected_vs_measured") or []
        by_bucket = {row.get("bucket"): row for row in curve}
        offending = by_bucket.get(ev.get("bucket"))
        named = (ev.get("program") in ("eta_score", "route_solve",
                                       "dispatch_solve", "dispatch_reopt")
                 and ev.get("bucket") is not None)
        embedded = (bool(curve)
                    and all(r.get("expected_rows_per_s") for r in curve)
                    and offending is not None
                    and offending.get("measured_rows_per_s") is not None)
        if named and embedded:
            return {"ok": True, "bundle": b["name"],
                    "program": ev["program"], "bucket": ev["bucket"],
                    "check": ev["check"],
                    "curve_points": len(curve),
                    "offending_bucket": offending}
    return {"ok": False,
            "bundles_seen": [b["name"] for b in bundles]}


def _timeline_has_efficiency(base: str) -> bool:
    try:
        tl = bp._fetch(f"{base}/api/timeline?family=rtpu_efficiency",
                       timeout=30)
    except OSError:
        return False
    return "rtpu_efficiency_rows_total" in json.dumps(tl)


def fleet_ports(fleet) -> list:
    return list(fleet.ports)


def workers_dir(fleet) -> str:
    return fleet.env["RTPU_RECORDER_DIR"]


# ── scenarios ────────────────────────────────────────────────────────


def scenario_clean(extract, cache_dir, rate, quick) -> dict:
    """Live fleet, ≥1 verified model swap + ≥1 metric flip under load:
    zero efficiency pages, watchdogs armed throughout, families in the
    timeline on both tiers, gateway rollup counting the goodput."""
    work = tempfile.mkdtemp(prefix="efficiency-clean-")
    out: dict = {"scenario": "clean"}
    fleet = bp.Fleet(live=True, extract=extract, cache_dir=cache_dir,
                     work_dir=work)
    load_stop = threading.Event()
    try:
        fleet.start_probe_drivers()

        def _load():
            salt = 0
            while not load_stop.is_set():
                try:
                    open_loop_batch(fleet.base, rate, 10.0,
                                    stop=load_stop, salt=salt)
                except Exception:
                    pass
                salt += 1_000_000

        load_thread = threading.Thread(target=_load, daemon=True)
        load_thread.start()

        # Verified model swap mid-run (within-gate perturbation; both
        # replicas' reload watchers land it through the golden gate).
        import jax

        from routest_tpu.train.checkpoint import load_model, save_model

        model, params = load_model(fleet.model_path)
        close = jax.tree_util.tree_map(lambda x: x * (1.0 + 1e-4),
                                       params)
        save_model(fleet.model_path, model, close)
        st = os.stat(fleet.model_path)
        os.utime(fleet.model_path,
                 ns=(st.st_atime_ns, st.st_mtime_ns + 1_000_000))

        def swaps_accepted() -> int:
            total = 0
            for port in fleet_ports(fleet):
                reg = bp._fetch(f"http://127.0.0.1:{port}/api/metrics",
                                timeout=30).get("registry", {})
                for s in reg.get("rtpu_model_swaps_total",
                                 {}).get("series", ()):
                    if s.get("labels", {}).get("result") == "accepted":
                        total += int(s.get("value", 0))
            return total

        def fleet_epoch() -> int:
            return max(bp._fetch(f"http://127.0.0.1:{p}/api/live",
                                 timeout=30).get("epoch", 0)
                       for p in fleet_ports(fleet))

        epoch0 = fleet_epoch()
        deadline = time.time() + (90 if quick else 180)
        flips = 0
        while time.time() < deadline:
            flips = fleet_epoch() - epoch0
            if flips >= 1 and swaps_accepted() >= 2:
                break
            time.sleep(1.0)
        out["swaps_accepted"] = swaps_accepted()
        out["metric_flips"] = flips
        # A few more watchdog rounds under steady load post-flip.
        time.sleep(6.0)

        per_replica = {}
        for port in fleet_ports(fleet):
            snap = replica_efficiency(port)
            wd = snap.get("watchdog") or {}
            eta = snap["ledger"]["programs"]["eta_score"]
            per_replica[port] = {
                "armed": wd.get("armed"), "status": wd.get("status"),
                "pages": wd.get("pages"), "verdicts": wd.get("verdicts"),
                "eta_rows": eta["rows"], "eta_calls": eta["calls"],
                "waste_fraction": eta["waste_fraction"],
            }
        out["replicas"] = per_replica
        gw_eff = bp._fetch(f"{fleet.base}/api/efficiency", timeout=30)
        out["fleet_rollup"] = gw_eff.get("fleet")
        out["timeline_replica"] = _timeline_has_efficiency(
            f"http://127.0.0.1:{fleet_ports(fleet)[0]}")
        out["timeline_gateway"] = _timeline_has_efficiency(fleet.base)
        bundles = efficiency_bundles(workers_dir(fleet))
        out["efficiency_bundles"] = [b["name"] for b in bundles]

        checks = {
            "metric_flip_ge_1": flips >= 1,
            "verified_swap_ge_1": out["swaps_accepted"] >= 1,
            "watchdogs_armed_and_pinned": all(
                r["armed"] and r["status"] == "pinned"
                for r in per_replica.values()),
            "ledger_counted_device_rows": all(
                r["eta_rows"] > 0 for r in per_replica.values()),
            "zero_efficiency_pages": (
                all((r["pages"] or 0) == 0 for r in per_replica.values())
                and not bundles),
            "all_verdicts_pass": all(
                v == "pass"
                for r in per_replica.values()
                for v in (r["verdicts"] or {}).values()),
            "fleet_rollup_counts_goodput": (
                (gw_eff.get("fleet", {}).get("programs", {})
                 .get("eta_score", {}).get("rows") or 0) > 0
                and not gw_eff.get("fleet", {}).get("degraded")),
            "timeline_family_visible_both_tiers": bool(
                out["timeline_replica"] and out["timeline_gateway"]),
        }
        out["checks"] = checks
        out["pass"] = all(checks.values())
    finally:
        load_stop.set()
        try:
            load_thread.join(timeout=20)
        except (NameError, RuntimeError):
            pass
        fleet.stop()
        shutil.rmtree(work, ignore_errors=True)
    return out


def scenario_fault(name, extract, cache_dir, rate, quick, *,
                   overlay: dict, check_prefix: str) -> dict:
    """Shared fault harness: boot → healthy baseline → roll one replica
    onto the degrading overlay → efficiency page within bound → bundle
    names program/replica/bucket with the curve embedded."""
    work = tempfile.mkdtemp(prefix=f"efficiency-{name}-")
    out: dict = {"scenario": name}
    fleet = bp.Fleet(live=False, extract=extract, cache_dir=cache_dir,
                     work_dir=work)
    load_stop = threading.Event()
    try:
        def _load():
            salt = 0
            while not load_stop.is_set():
                try:
                    open_loop_batch(fleet.base, rate, 10.0,
                                    stop=load_stop, salt=salt)
                except Exception:
                    pass
                salt += 1_000_000

        load_thread = threading.Thread(target=_load, daemon=True)
        load_thread.start()

        # Healthy baseline: both watchdogs armed, no pages, device rows
        # flowing (the evidence floor is met before the fault lands).
        baseline_deadline = time.time() + (45 if quick else 90)
        while time.time() < baseline_deadline:
            snaps = [replica_efficiency(p) for p in fleet_ports(fleet)]
            if all((s.get("watchdog") or {}).get("armed")
                   and s["ledger"]["programs"]["eta_score"]["rows"] >= 64
                   for s in snaps):
                break
            time.sleep(1.0)
        out["baseline"] = {
            p: {"armed": (s.get("watchdog") or {}).get("armed"),
                "pages": (s.get("watchdog") or {}).get("pages"),
                "eta_rows": s["ledger"]["programs"]["eta_score"]["rows"]}
            for p, s in zip(fleet_ports(fleet), snaps)}

        victim = fleet.replica_rids()[0]
        t_fault = time.time()
        faulty_rid = fleet.inject_replacement(victim, dict(overlay),
                                              version=f"v-{name}")
        faulty_port = fleet.ports[-1]
        faulty_label = f"{socket.gethostname()}:{faulty_port}"
        healthy_ports = [p for p in fleet_ports(fleet)
                         if p != faulty_port]
        out.update({"victim": victim, "faulty_rid": faulty_rid,
                    "faulty_port": faulty_port,
                    "faulty_label": faulty_label,
                    "inject_wall_s": round(time.time() - t_fault, 1)})

        page = wait_for_efficiency_page(faulty_port, DETECT_BOUND_S)
        out["page"] = page
        out["detect_bound_s"] = DETECT_BOUND_S

        # The page lands the bundle synchronously; poll briefly for the
        # directory scan to see it.
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            bundles = efficiency_bundles(workers_dir(fleet))
            out["bundle"] = judge_efficiency_bundle(
                bundles, faulty_label, check_prefix)
            if out["bundle"]["ok"]:
                break
            time.sleep(1.0)

        healthy = {p: (replica_efficiency(p).get("watchdog") or {})
                   for p in healthy_ports}
        out["healthy_pages"] = {p: w.get("pages") for p, w in
                                healthy.items()}
        checks = {
            "detected_and_paged": bool(page["paged"]),
            "within_bound": bool(page["paged"]
                                 and page["detect_s"] <= DETECT_BOUND_S),
            "bundle_names_program_replica_bucket": out["bundle"]["ok"],
            "healthy_replica_zero_pages": all(
                (v or 0) == 0 for v in out["healthy_pages"].values()),
        }
        out["checks"] = checks
        out["pass"] = all(checks.values())
    finally:
        load_stop.set()
        try:
            load_thread.join(timeout=20)
        except (NameError, RuntimeError):
            pass
        fleet.stop()
        shutil.rmtree(work, ignore_errors=True)
    return out


def scenario_overhead(quick) -> dict:
    """The always-on ledger's p95 cost, isolated: everything else off,
    ``RTPU_EFF=0`` vs on (watchdog armed, second-scale ticks) — the
    obs-overhead bench's best-of-both-orders protocol against the same
    ≤5% budget with the same 1-core noise floor."""
    import bench_obs_overhead as bo

    out: dict = {"scenario": "overhead"}
    lt = bo._load_load_test()
    threads = 4 if quick else 8
    requests = 20 if quick else 40
    repeats = 2 if quick else 3
    base_off = {"RTPU_OBS_TRACE": "0", "RTPU_RECORDER": "0",
                "RTPU_SLO": "0", "RTPU_TIMELINE": "0",
                "RTPU_TAIL_SAMPLE": "0"}
    modes = (
        ("ledger_off", dict(base_off, RTPU_EFF="0")),
        ("ledger_on", dict(base_off, RTPU_EFF="1",
                           RTPU_EFF_TICK_S="1.0")),
    )
    results: dict = {}
    for order in (modes, tuple(reversed(modes))):
        for mode, env in order:
            r = bo.run_mode(lt, env, threads, requests,
                            batch_size=512, repeats=repeats)
            prev = results.get(mode)
            if prev is not None and \
                    (prev["predict_eta"].get("p95_ms") or 1e9) < \
                    (r["predict_eta"].get("p95_ms") or 1e9):
                r["predict_eta"] = prev["predict_eta"]
            results[mode] = r
    p_off = results["ledger_off"]["predict_eta"].get("p95_ms")
    p_on = results["ledger_on"]["predict_eta"].get("p95_ms")
    overhead_pct = (p_on - p_off) / p_off * 100.0
    ok = (overhead_pct <= OVERHEAD_PCT
          or p_on - p_off <= OVERHEAD_FLOOR_MS)
    out.update({
        "p95_off_ms": p_off, "p95_on_ms": p_on,
        "p95_overhead_pct": round(overhead_pct, 2),
        "budget_pct": OVERHEAD_PCT,
        "noise_floor_ms": OVERHEAD_FLOOR_MS,
        "modes": {m: r.get("predict_eta") for m, r in results.items()},
    })
    out["checks"] = {"ledger_within_p95_budget": bool(ok)}
    out["pass"] = bool(ok)
    return out


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--quick", action="store_true",
                        help="smaller extract + shorter phases (CI)")
    parser.add_argument("--nodes", type=int, default=6000)
    parser.add_argument("--rate", type=float, default=4.0,
                        help="predict_eta_batch requests/s "
                             f"(×{BATCH_ROWS} rows each)")
    parser.add_argument("--cache-dir", default=os.path.join(
        REPO, "artifacts", "bench_cache", "efficiency"))
    parser.add_argument("--out", default=os.path.join(
        REPO, "artifacts", "efficiency.json"))
    parser.add_argument("--scenario", default=None,
                        help="run one scenario (debug)")
    args = parser.parse_args()
    if args.quick:
        args.nodes = min(args.nodes, 4000)

    os.environ.setdefault("ROUTEST_FORCE_CPU", "1")
    import jax

    jax.config.update("jax_platforms", "cpu")
    os.makedirs(args.cache_dir, exist_ok=True)
    os.environ["ROUTEST_HIER_CACHE"] = os.path.join(args.cache_dir,
                                                    "hier")
    from routest_tpu.core.cache import enable_compile_cache

    enable_compile_cache()
    # The fleet inherits the bench's environment: the efficiency knobs
    # reach every replica (and their rollout successors) verbatim.
    os.environ.update(EFF_ENV)

    t0 = time.time()
    print(f"[1/6] extract + overlay cache ({args.nodes:,} nodes)…",
          flush=True)
    extract = bp.build_extract(args.nodes, args.cache_dir)

    scenarios: dict = {}
    plan = [
        ("clean", lambda: scenario_clean(
            extract, args.cache_dir, args.rate, args.quick)),
        ("device_slowdown", lambda: scenario_fault(
            "device_slowdown", extract, args.cache_dir, args.rate,
            args.quick,
            overlay={"RTPU_CHAOS_SPEC": "device.compute:latency=1.0/400",
                     "RTPU_CHAOS_SEED": "7"},
            check_prefix="throughput")),
        ("padding_blowup", lambda: scenario_fault(
            "padding_blowup", extract, args.cache_dir, args.rate,
            args.quick,
            overlay={"RTPU_BATCH_BUCKETS": "4096"},
            check_prefix="padding")),
        ("overhead", lambda: scenario_overhead(args.quick)),
    ]
    for i, (name, run) in enumerate(plan):
        if args.scenario and name != args.scenario:
            continue
        print(f"[{i + 2}/6] scenario {name}…", flush=True)
        t = time.perf_counter()
        try:
            scenarios[name] = run()
        except Exception as e:
            scenarios[name] = {"scenario": name, "pass": False,
                               "error": f"{type(e).__name__}: {e}"}
        scenarios[name]["wall_s"] = round(time.perf_counter() - t, 1)
        print(f"  {name}: "
              f"{'PASS' if scenarios[name].get('pass') else 'FAIL'} "
              f"({scenarios[name]['wall_s']}s)", flush=True)

    try:
        n_cpus = len(os.sched_getaffinity(0))
    except AttributeError:
        n_cpus = os.cpu_count() or 1
    backend = jax.devices()[0].platform
    record = {
        "generated_unix": int(t0),
        "host": {"cpus": n_cpus, "platform": sys.platform,
                 "backend": backend},
        "host_caveat": (
            f"cpu-backend record on {n_cpus} core(s): detection "
            "latencies and p95s are time-shared-host numbers; judge "
            "the structural checks (paged within bound, bundle names "
            "program/replica/bucket with the curve, clean run green, "
            "ledger within budget), not wall-ms"
            if backend != "tpu" else None),
        "skipped": ("tpu rows: not measured (this run used the cpu "
                    "backend)" if backend != "tpu" else None),
        "config": {
            "nodes": args.nodes, "rate_rps": args.rate,
            "batch_rows": BATCH_ROWS,
            "detect_bound_s": DETECT_BOUND_S,
            "eff_env": EFF_ENV,
            "overhead_budget_pct": OVERHEAD_PCT,
            "overhead_noise_floor_ms": OVERHEAD_FLOOR_MS,
            "cache_dir": args.cache_dir,
            "quick": bool(args.quick),
        },
        "scenarios": scenarios,
    }
    if args.scenario:
        record["partial"] = f"--scenario {args.scenario} (debug run)"
    record["checks"] = {name: bool(s.get("pass"))
                        for name, s in scenarios.items()}
    record["all_pass"] = (bool(record["checks"])
                          and all(record["checks"].values())
                          and (args.scenario is not None
                               or len(scenarios) == 4))
    record["wall_s"] = round(time.time() - t0, 1)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2)
    print(f"\n[6/6] checks: "
          + " ".join(f"{k}={'PASS' if v else 'FAIL'}"
                     for k, v in record["checks"].items())
          + f"\n→ {args.out} (all_pass={record['all_pass']}, "
            f"{record['wall_s']}s)", flush=True)
    # _exit, not sys.exit: loadgen daemon threads racing interpreter
    # teardown must not turn a written verdict into a crash.
    os._exit(0 if record["all_pass"] else 1)


if __name__ == "__main__":
    main()
