"""Live traffic end to end → artifacts/live_traffic.json.

The payoff bench for the live subsystem (docs/ARCHITECTURE.md "Live
traffic"): a real fleet (supervisor + worker + gateway + netbus
broker) serves the Manila metro extract under the open-loop mixed
load generator while a simulated probe fleet streams per-edge speed
observations. A third of the way in, the scenario driver jams a named
corridor; the run passes iff

- served ETAs and chosen routes for a probe OD pair straddling the
  corridor measurably shift, within the configured staleness bound
  (probe-injection → served-effect latency is measured and reported);
- post-flip served durations match a scipy Dijkstra oracle re-solved
  on the replica's OWN exported live metric (``/api/live?metric=1``);
- zero client 5xx and the SLO engine stays green on BOTH tiers across
  ≥ 3 metric-generation flips and ≥ 3 verified road-GNN hot-swaps
  (the continuous trainer runs in this driver process, landing
  artifacts through the router's verified swap);
- overlay metric customization is reported ≪ the full overlay build
  per flip (CRP-style re-pricing, not a rebuild).

Usage: python scripts/bench_live_traffic.py [--nodes 30000]
       [--duration 150] [--drivers 250] [--quick]
       [--out artifacts/live_traffic.json]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import socket
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

MODEL = os.path.join(REPO, "artifacts", "eta_mlp.msgpack")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def build_extract(n_nodes: int, out_dir: str):
    """Manila metro extract (same recipe as the router scale benches) +
    its overlay cache, prebuilt in-process so the worker rehydrates."""
    from routest_tpu.data.osm import load_osm, save_osm
    from routest_tpu.data.road_graph import (generate_road_graph,
                                             subdivide_graph)
    from routest_tpu.optimize.road_router import RoadRouter

    n_int = max(1024, int(n_nodes / 5.86))
    base = generate_road_graph(n_nodes=n_int, k=4, seed=0)
    streets = subdivide_graph(base, bends_per_edge=2, oneway_frac=0.1,
                              seed=0)
    path = os.path.join(out_dir, f"manila_{n_nodes}.osm.gz")
    save_osm(path, streets)
    extract = load_osm(path)
    t0 = time.perf_counter()
    router = RoadRouter(graph=extract, use_gnn=False,
                        use_transformer=False)
    print(f"  overlay prebuilt in {time.perf_counter() - t0:.1f}s "
          f"({router.n_nodes:,} nodes, {len(router.senders):,} edges)",
          flush=True)
    return path, router


def _fetch(url: str, timeout: float = 30.0):
    import urllib.request

    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def _post(url: str, body: dict, timeout: float = 120.0):
    import urllib.request

    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def main() -> None:
    parser = argparse.ArgumentParser()
    # Defaults are sized for the 1-core CI/dev host every bench here
    # records on (the worker, the driver-side trainer, the probe fleet
    # and the load generator all time-slice one core); on a real
    # multi-core box, raise --nodes/--drivers/--rps freely.
    parser.add_argument("--nodes", type=int, default=20_000)
    parser.add_argument("--duration", type=float, default=180.0)
    parser.add_argument("--drivers", type=int, default=160)
    parser.add_argument("--rps", type=float, default=1.5)
    parser.add_argument("--customize-s", type=float, default=8.0)
    parser.add_argument("--half-life-s", type=float, default=15.0)
    parser.add_argument("--staleness-bound", type=float, default=None,
                        help="max allowed probe-injection → served-"
                             "effect latency. Default derives from the "
                             "loop's own physics: two estimator half-"
                             "lives (EWMA convergence to the new "
                             "regime) + two customize intervals (one "
                             "may be mid-flight at injection) + 15 s "
                             "ingest/sampler margin")
    parser.add_argument("--retrain-steps", type=int, default=10)
    parser.add_argument("--obs-per-tick", type=int, default=6)
    parser.add_argument("--slo-ms", type=float, default=8000.0)
    parser.add_argument("--quick", action="store_true",
                        help="10k extract, 100 s, 96 drivers — the "
                             "slow-test preset")
    parser.add_argument("--seed", type=int, default=42)
    parser.add_argument("--out", default=None)
    args = parser.parse_args()
    if args.quick:
        args.nodes = min(args.nodes, 10_000)
        args.duration = min(args.duration, 100.0)
        args.drivers = min(args.drivers, 96)
        args.customize_s = min(args.customize_s, 6.0)
    if args.staleness_bound is None:
        args.staleness_bound = (2 * args.half_life_s
                                + 2 * args.customize_s + 15.0)

    os.environ.setdefault("ROUTEST_FORCE_CPU", "1")
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from routest_tpu.core.cache import enable_compile_cache
    from routest_tpu.core.config import FleetConfig
    from routest_tpu.data.locations import SEED_LOCATIONS
    from routest_tpu.live.ingest import ProbeIngester
    from routest_tpu.live.probes import (CongestionScenario, ProbeFleet,
                                         corridor_edges)
    from routest_tpu.live.state import CongestionState
    from routest_tpu.live.trainer import ContinuousTrainer
    from routest_tpu.loadgen import (MixedWorkload, RateCurve,
                                     SseClients, poisson_schedule,
                                     run_open_loop, summarize)
    from routest_tpu.serve.fleet.gateway import Gateway
    from routest_tpu.serve.fleet.supervisor import ReplicaSupervisor
    from routest_tpu.serve.netbus import NetBus, start_broker

    work_dir = tempfile.mkdtemp(prefix="live-traffic-")
    hier_cache = os.path.join(work_dir, "hier")
    gnn_path = os.path.join(work_dir, "road_gnn_live.msgpack")
    os.environ["ROUTEST_HIER_CACHE"] = hier_cache
    os.environ["RTPU_RECORDER_DIR"] = os.path.join(work_dir,
                                                   "postmortems")
    enable_compile_cache()
    channel = "rtpu.probes"
    slo_spec = (f"/api/request_route:latency_ms={args.slo_ms:.0f},"
                f"latency_target=0.9,availability=0.99;"
                f"/api/predict_eta:latency_ms=2500,latency_target=0.9,"
                f"availability=0.99")
    # The in-process GATEWAY's engine reads this env too — without it
    # the gateway would judge by the built-in defaults (tighter
    # latency thresholds than this 1-core host can honor).
    os.environ["RTPU_SLO_OBJECTIVES"] = slo_spec

    print(f"[1/6] building {args.nodes:,}-node Manila extract + overlay "
          f"cache…", flush=True)
    extract, oracle_router = build_extract(args.nodes, work_dir)
    n_edges = len(oracle_router.senders)

    # Corridor: between two seed sites, wide enough to carry traffic.
    a = (SEED_LOCATIONS[2][1], SEED_LOCATIONS[2][2])
    b = (SEED_LOCATIONS[11][1], SEED_LOCATIONS[11][2])
    # Narrow band: wide enough to jam every lane ALONG the line, narrow
    # enough that parallel streets outside it offer real detours — the
    # route-shift half of the acceptance needs an escape to exist.
    corridor_width = 220.0
    corridor = corridor_edges(oracle_router.coords,
                              oracle_router.senders,
                              oracle_router.receivers, a, b,
                              width_m=corridor_width)
    print(f"  corridor {len(corridor)} edges between "
          f"{SEED_LOCATIONS[2][0]} and {SEED_LOCATIONS[11][0]}",
          flush=True)

    def corridor_overlap(coords_lonlat) -> float:
        """Fraction of a served polyline's vertices inside the corridor
        band — the route-shift witness (drops when routes detour)."""
        pts = np.asarray(coords_lonlat, np.float64)
        if len(pts) == 0:
            return 0.0
        latlon = pts[:, ::-1]
        lat0 = math.radians((a[0] + b[0]) / 2.0)
        scale = np.asarray([111_194.9, 111_194.9 * math.cos(lat0)])
        p = (latlon - np.asarray(a)) * scale
        seg = (np.asarray(b) - np.asarray(a)) * scale
        t = np.clip((p @ seg) / float(seg @ seg), 0.0, 1.0)
        d = np.sqrt(((p - t[:, None] * seg[None, :]) ** 2).sum(axis=1))
        return float((d <= corridor_width).mean())

    print("[2/6] starting broker + fleet (1 worker + gateway)…",
          flush=True)
    broker, _bt = start_broker()
    bus_url = f"tcp://127.0.0.1:{broker.port}"
    env = dict(os.environ)
    env.update({
        "ROAD_GRAPH_OSM": extract,
        "ROUTEST_HIER_CACHE": hier_cache,
        "ROUTEST_MESH": "0",
        "ROUTEST_WARM_BUCKETS": "0",
        "ETA_MODEL_PATH": MODEL,
        "ROAD_GNN_PATH": gnn_path,
        "REDIS_URL": bus_url,
        "RTPU_SLO_OBJECTIVES": slo_spec,
        "RTPU_LIVE": "1",
        "RTPU_LIVE_CHANNEL": channel,
        "RTPU_LIVE_CUSTOMIZE_S": str(args.customize_s),
        "RTPU_LIVE_HALF_LIFE_S": str(args.half_life_s),
        "RTPU_LIVE_MIN_OBS_EDGES": "50",
    })
    ports = [_free_port()]
    sup = ReplicaSupervisor(ports, env=env, cwd=REPO,
                            probe_interval_s=0.5, backoff_base_s=0.2,
                            backoff_cap_s=2.0)
    sup.start()
    gw = httpd = None
    fleet = ingester = trainer = None
    record: dict = {}
    try:
        if not sup.ready(timeout=600):
            raise RuntimeError("fleet worker never became ready")
        replica_base = f"http://127.0.0.1:{ports[0]}"
        gw = Gateway([("127.0.0.1", p) for p in ports],
                     FleetConfig(hedge=False, max_inflight=32,
                                 queue_depth=64), supervisor=sup)
        httpd = gw.serve("127.0.0.1", 0)
        base = f"http://127.0.0.1:{httpd.server_address[1]}"

        print("[3/6] warming worker (router from cache) + arming "
              "probes/trainer…", flush=True)
        od_body = {
            "source_point": {"lat": a[0], "lon": a[1]},
            "destination_points": [{"lat": b[0], "lon": b[1],
                                    "payload": 1}],
            "driver_details": {"vehicle_type": "car",
                               "vehicle_capacity": 100,
                               "maximum_distance": 900_000},
            "road_graph": True,
        }
        t0 = time.perf_counter()
        _post(f"{base}/api/request_route", od_body, timeout=600)
        warm_s = time.perf_counter() - t0
        deadline = time.time() + 300
        while time.time() < deadline:
            if _fetch(f"{replica_base}/api/live").get("ready"):
                break
            time.sleep(0.5)
        else:
            raise RuntimeError("replica live service never armed")

        scenario = CongestionScenario(corridor, speed_factor=0.25)
        graph = oracle_router.graph_dict()
        probe_bus = NetBus(bus_url)
        fleet = ProbeFleet(graph, args.drivers, probe_bus.publish,
                           seed=args.seed, channel=channel,
                           obs_per_tick=args.obs_per_tick,
                           scenario=scenario)
        fleet.start(tick_s=1.0)
        # Driver-side estimator feeding the continuous trainer (its own
        # subscription on the same stream the replicas fold).
        train_bus = NetBus(bus_url)
        state = CongestionState(oracle_router.freeflow_time_s,
                                half_life_s=args.half_life_s,
                                stale_s=600.0)
        ingester = ProbeIngester(train_bus, state,
                                 oracle_router.length_m,
                                 channel=channel)
        ingester.start()
        trainer = ContinuousTrainer(oracle_router, state, gnn_path,
                                    steps=args.retrain_steps,
                                    min_obs=400)
        swap_stop = threading.Event()

        def retrain_loop() -> None:
            while not swap_stop.wait(2.0):
                trainer.run_once()

        retrain_thread = threading.Thread(target=retrain_loop,
                                          daemon=True)
        retrain_thread.start()

        # Probe OD sampler: the served route/ETA timeline the staleness
        # measurement reads.
        samples: list = []
        sample_stop = threading.Event()

        def sample_loop() -> None:
            while not sample_stop.is_set():
                try:
                    t = time.time()
                    feat = _post(f"{base}/api/request_route", od_body,
                                 timeout=120)
                    summary = feat.get("properties", {}).get("summary",
                                                             {})
                    samples.append({
                        "t": t,
                        "duration_s": float(summary.get("duration", 0)),
                        "distance_m": float(summary.get("distance", 0)),
                        "overlap": corridor_overlap(
                            feat.get("geometry", {}).get("coordinates",
                                                         [])),
                    })
                except Exception as e:
                    samples.append({"t": time.time(),
                                    "error": f"{type(e).__name__}: {e}"})
                sample_stop.wait(1.5)

        threading.Thread(target=sample_loop, daemon=True).start()

        print(f"[4/6] open loop {args.rps} rps × {args.duration:.0f}s, "
              f"{args.drivers} probe drivers; corridor jam at "
              f"t+{args.duration / 3:.0f}s…", flush=True)
        workload = MixedWorkload(
            mix={"request_route": 0.25, "predict_eta": 0.45,
                 "history": 0.1, "update_tracker": 0.1, "probe": 0.1},
            seed=args.seed, road_graph=True, probe_edges=n_edges)
        sse = SseClients(base, 2, channel=workload.sse_channel)
        sse.__enter__()
        curve = RateCurve.constant(args.rps)
        offsets = poisson_schedule(curve, args.duration, seed=args.seed)
        requests = workload.sequence(len(offsets))
        t_start = time.time()
        t_inject = t_start + args.duration / 3.0

        def inject_later() -> None:
            delay = t_inject - time.time()
            if delay > 0:
                time.sleep(delay)
            scenario.set_active(True)
            print(f"  corridor jam ACTIVE at t+{time.time() - t_start:.0f}s",
                  flush=True)

        threading.Thread(target=inject_later, daemon=True).start()
        records = run_open_loop([base], offsets, requests, workers=16,
                                timeout=max(60.0, 4 * args.slo_ms / 1000))
        report = summarize(records, args.duration, len(offsets))
        sample_stop.set()
        swap_stop.set()
        # Let an in-flight retrain cycle finish before teardown — a
        # daemon thread mid-jax-dispatch at interpreter exit segfaults.
        retrain_thread.join(timeout=60.0)
        sse.__exit__()
        sse_events = sse.snapshot()

        print("[5/6] oracle check + fleet judgement…", flush=True)
        # Post-flip oracle: served duration vs scipy Dijkstra on the
        # replica's OWN exported metric, fetched at a stable epoch.
        oracle = {"checked": False}
        for _attempt in range(5):
            live0 = _fetch(f"{replica_base}/api/live?metric=1",
                           timeout=120)
            feat = _post(f"{base}/api/request_route", od_body,
                         timeout=120)
            live1 = _fetch(f"{replica_base}/api/live")
            if live0.get("epoch") != live1.get("epoch"):
                continue  # flipped mid-check: retry at the next epoch
            import scipy.sparse as sp
            from scipy.sparse.csgraph import dijkstra

            metric = np.asarray(live0["edge_time_s"], np.float64)
            n = oracle_router.n_nodes
            adj = sp.coo_matrix(
                (metric, (oracle_router.senders,
                          oracle_router.receivers)),
                shape=(n, n)).tocsr()
            src = oracle_router.snap(np.asarray([a, b], np.float32))
            want = dijkstra(adj, directed=True,
                            indices=np.asarray(src[:1], np.int64))
            from routest_tpu.data.road_graph import haversine_np

            snap_m = haversine_np(
                np.asarray([a[0], b[0]]), np.asarray([a[1], b[1]]),
                oracle_router.coords[src, 0],
                oracle_router.coords[src, 1])
            oracle_s = float(want[0, src[1]]) \
                + float(snap_m.sum()) / 8.3
            served_s = float(feat["properties"]["summary"]["duration"])
            rel = abs(served_s - oracle_s) / max(oracle_s, 1.0)
            oracle = {"checked": True, "epoch": live0.get("epoch"),
                      "served_duration_s": round(served_s, 2),
                      "oracle_duration_s": round(oracle_s, 2),
                      "rel_err": round(rel, 6),
                      "pass": rel < 2e-3}
            break

        live_final = _fetch(f"{replica_base}/api/live", timeout=60)
        replica_metrics = _fetch(f"{replica_base}/api/metrics",
                                 timeout=60)
        replica_slo = _fetch(f"{replica_base}/api/slo", timeout=60)
        gw.slo.tick()
        gateway_slo = gw.slo.snapshot()
        health = _fetch(f"{replica_base}/api/health", timeout=60)
    finally:
        for part in (fleet, ingester):
            if part is not None:
                part.stop()
        try:
            if httpd is not None:
                gw.drain(timeout=5)
        finally:
            sup.drain(timeout=20)
            broker.shutdown()

    # ── staleness + shift analysis ────────────────────────────────────
    good = [s for s in samples if "duration_s" in s]
    pre = [s for s in good if s["t"] < t_inject]
    post = [s for s in good if s["t"] >= t_inject]
    base_dur = (sorted(s["duration_s"] for s in pre)[len(pre) // 2]
                if pre else float("nan"))
    base_dist = (sorted(s["distance_m"] for s in pre)[len(pre) // 2]
                 if pre else float("nan"))
    base_overlap = (sorted(s["overlap"] for s in pre)[len(pre) // 2]
                    if pre else float("nan"))
    # Detection = TWO consecutive over-threshold samples: a single
    # sample can cross 1.10× on baseline noise (a model swap re-pricing
    # unobserved edges), which would report a physically impossible
    # sub-second staleness.
    detect_t = None
    for i in range(len(post) - 1):
        if (post[i]["duration_s"] >= base_dur * 1.10
                and post[i + 1]["duration_s"] >= base_dur * 1.10):
            detect_t = post[i]["t"]
            break
    staleness_s = (detect_t - t_inject) if detect_t is not None else None
    tail = [s for s in post if detect_t is not None and s["t"] >= detect_t]
    tail_dur = (sorted(s["duration_s"] for s in tail)[len(tail) // 2]
                if tail else float("nan"))
    tail_dist = (sorted(s["distance_m"] for s in tail)[len(tail) // 2]
                 if tail else float("nan"))
    tail_overlap = (sorted(s["overlap"] for s in tail)[len(tail) // 2]
                    if tail else float("nan"))
    eta_shift = (tail_dur / base_dur - 1.0) if base_dur else 0.0
    # Route shift: the served geometry leaves the jammed band (overlap
    # drops) and/or the chosen path's length changes.
    dist_changed = (abs(tail_dist - base_dist) / base_dist > 0.002
                    if base_dist and not math.isnan(tail_dist) else False)
    overlap_dropped = (not math.isnan(tail_overlap)
                       and not math.isnan(base_overlap)
                       and tail_overlap <= base_overlap - 0.05)
    route_shift = dist_changed or overlap_dropped

    # ── fleet-level verdicts ──────────────────────────────────────────
    flips = int(live_final.get("customize", {}).get("flips", 0))
    registry = replica_metrics.get("registry", {})

    def _counter(name: str, **labels) -> int:
        total = 0
        for series in registry.get(name, {}).get("series", ()):
            if all(series.get("labels", {}).get(k) == v
                   for k, v in labels.items()):
                total += int(series.get("value", 0))
        return total

    swaps_accepted = _counter("rtpu_road_model_swaps_total",
                              result="accepted")
    client_5xx = sum(1 for r in records
                     if r.status is not None and r.status >= 500)
    slo_green = (gateway_slo.get("state") == "ok"
                 and replica_slo.get("state") == "ok")
    # Customization vs rebuild: the flip re-prices the overlay against
    # the new metric reusing partition + contraction; the honest
    # comparison is the recorded FULL build (which a per-flip rebuild
    # would pay, contraction walk and partition included). The gap
    # widens with scale — at quick/10k the python contraction walk is
    # small, at metro/250k it dominates — so the gate is directional
    # (strictly faster) and the ratio is reported for the record.
    metric_info = live_final.get("metric") or {}
    customize_s = metric_info.get("customize_s")
    full_build_s = metric_info.get("full_build_s")
    customization_fast = (customize_s is not None
                          and full_build_s is not None
                          and customize_s < full_build_s)
    customize_ratio = (round(full_build_s / customize_s, 2)
                       if customization_fast and customize_s else None)

    checks = {
        "eta_shifted": eta_shift >= 0.10,
        "route_shifted": bool(route_shift),
        "staleness_within_bound": (staleness_s is not None
                                   and staleness_s
                                   <= args.staleness_bound),
        "oracle_parity": bool(oracle.get("pass")),
        "zero_client_5xx": client_5xx == 0,
        "slo_green_both_tiers": slo_green,
        "metric_flips_ge_3": flips >= 3,
        "verified_swaps_ge_3": swaps_accepted >= 3,
        "customize_beats_full_build": bool(customization_fast),
    }
    passed = all(checks.values())
    try:
        n_cpus = len(os.sched_getaffinity(0))
    except AttributeError:
        n_cpus = os.cpu_count() or 1
    record = {
        "host": {"cpus": n_cpus,
                 "note": "1 worker + driver-side trainer share the "
                         "host; wall latency scales with cores"},
        "extract_nodes": args.nodes,
        "edges": n_edges,
        "corridor_edges": int(len(corridor)),
        "drivers": args.drivers,
        "duration_s": args.duration,
        "customize_interval_s": args.customize_s,
        "staleness_bound_s": args.staleness_bound,
        "warm_first_request_s": round(warm_s, 1),
        "workload": workload.describe(),
        "load": report,
        "sse_events": sse_events,
        "timeline": {
            "inject_at_s": round(t_inject - t_start, 1),
            "baseline_median_duration_s": round(base_dur, 1),
            "post_detect_median_duration_s": round(tail_dur, 1)
            if not math.isnan(tail_dur) else None,
            "baseline_median_distance_m": round(base_dist, 1),
            "post_detect_median_distance_m": round(tail_dist, 1)
            if not math.isnan(tail_dist) else None,
            "baseline_corridor_overlap": round(base_overlap, 3)
            if not math.isnan(base_overlap) else None,
            "post_detect_corridor_overlap": round(tail_overlap, 3)
            if not math.isnan(tail_overlap) else None,
            "eta_shift_frac": round(eta_shift, 4),
            "injection_to_served_effect_s":
                round(staleness_s, 1) if staleness_s is not None
                else None,
            "samples": len(good),
        },
        "oracle": oracle,
        "live": {"flips": flips,
                 "final_epoch": live_final.get("epoch"),
                 "ingest": live_final.get("ingest"),
                 "customize_s_last": customize_s,
                 "full_build_s": full_build_s,
                 "customize_speedup": customize_ratio,
                 "retrain_cycles": trainer.cycles if trainer else 0,
                 "swaps_accepted": swaps_accepted,
                 "swaps_rejected": _counter(
                     "rtpu_road_model_swaps_total", result="rejected")},
        "slo": {"gateway_state": gateway_slo.get("state"),
                "replica_state": replica_slo.get("state"),
                "green": slo_green},
        "client_5xx": client_5xx,
        "road_router": (health.get("checks", {}).get("engine", {})
                        .get("road_router")),
        "checks": checks,
        "pass": passed,
    }
    out = args.out or os.path.join(REPO, "artifacts",
                                   "live_traffic.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(record, f, indent=2)
    print(f"\n[6/6] checks: "
          + " ".join(f"{k}={'PASS' if v else 'FAIL'}"
                     for k, v in checks.items()))
    print(f"ETA shift {eta_shift:+.0%}, injection→served "
          f"{record['timeline']['injection_to_served_effect_s']}s "
          f"(bound {args.staleness_bound:.0f}s), flips {flips}, "
          f"verified swaps {swaps_accepted}, customize "
          f"{customize_s}s vs build {full_build_s}s → {out}")
    sys.stdout.flush()
    # _exit, not sys.exit: lingering daemon threads (probe fleet /
    # ingester jax work) racing interpreter teardown can segfault AFTER
    # the verdict is decided and written — the exit code must reflect
    # the bench, not the teardown.
    os._exit(0 if passed else 1)


if __name__ == "__main__":
    main()
