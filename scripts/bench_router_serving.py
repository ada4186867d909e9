"""Metro routing as a SERVING workload → artifacts/router_serving.json.

The scale bench (``bench_osm_scale.py``) proves the solver; this one
proves the serving claim: a real fleet (supervisor + worker process +
gateway) pointed at a metro-scale OSM extract (``ROAD_GRAPH_OSM``)
answers ``/api/request_route`` with ``road_graph: true`` — street-
network shortest paths through the multi-level partition overlay —
under the open-loop load generator, with the SLO engine judging the
result. The workload's route traffic is Zipf-skewed over the OD
vocabulary (byte-stable bodies per pair), so the route fastlane and
the solve batcher are exercised the way production traffic would:
recorded alongside the CO-correct latency percentiles are the route-
cache hit rate and the batcher's merged-dispatch stats, read from the
worker's health provenance after the run.

``--compare-cache`` reruns the IDENTICAL offered load against a second
worker booted with ``ROUTEST_ROUTE_CACHE=0`` — same extract, same
overlay cache, same arrival schedule — so the artifact carries a
measured cache-on vs cache-off p95 on this host, not a claim.

The worker rehydrates the overlay from the shared
``ROUTEST_HIER_CACHE`` dir (this process builds it first) and reuses
this process's XLA compile cache, so replica boot measures cache-warm
fleet bring-up — the deployment path, not a cold lab build.

Usage: python scripts/bench_router_serving.py [--nodes 250000]
       [--rps 1.0] [--duration 90] [--quick] [--slo-ms 2500]
       [--compare-cache] [--out artifacts/router_serving.json]
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

MODEL = os.path.join(REPO, "artifacts", "eta_mlp.msgpack")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def build_extract(n_nodes: int, out_dir: str) -> str:
    """Generate the OSM-topology metro extract (same recipe as the
    scale benches) and pre-build its overlay cache in-process."""
    from routest_tpu.data.osm import load_osm, save_osm
    from routest_tpu.data.road_graph import generate_road_graph, subdivide_graph
    from routest_tpu.optimize.road_router import RoadRouter

    n_int = max(1024, int(n_nodes / 5.86))
    base = generate_road_graph(n_nodes=n_int, k=4, seed=0)
    streets = subdivide_graph(base, bends_per_edge=2, oneway_frac=0.1, seed=0)
    path = os.path.join(out_dir, f"metro_{n_nodes}.osm.gz")
    save_osm(path, streets)
    extract = load_osm(path)
    t0 = time.perf_counter()
    router = RoadRouter(graph=extract, use_gnn=False, use_transformer=False)
    print(f"  overlay prebuilt in {time.perf_counter() - t0:.1f}s "
          f"({router.n_nodes:,} nodes, "
          f"{router.solver_info.get('overlay', {}).get('n_levels')} levels, "
          f"hub_labels={router.solver_info.get('hub_labels')})",
          flush=True)
    return path


def run_phase(label: str, env: dict, workload, offsets, requests,
              slo_ms: float) -> dict:
    """Boot ONE worker + gateway under ``env``, warm it, replay the
    offered schedule, and return the phase record (load report, SLO
    states, worker health provenance)."""
    from routest_tpu.core.config import FleetConfig
    from routest_tpu.loadgen import KeepAliveClient, run_open_loop, summarize
    from routest_tpu.serve.fleet.gateway import Gateway
    from routest_tpu.serve.fleet.supervisor import ReplicaSupervisor

    ports = [_free_port()]
    sup = ReplicaSupervisor(ports, env=env, cwd=REPO,
                            probe_interval_s=0.5, backoff_base_s=0.2,
                            backoff_cap_s=2.0)
    sup.start()
    gw = httpd = None
    try:
        if not sup.ready(timeout=600):
            raise RuntimeError(f"{label}: fleet worker never became ready")
        gw = Gateway([("127.0.0.1", p) for p in ports],
                     FleetConfig(hedge=False, max_inflight=32,
                                 queue_depth=64), supervisor=sup)
        httpd = gw.serve("127.0.0.1", 0)
        base = f"http://127.0.0.1:{httpd.server_address[1]}"

        print(f"  [{label}] warming (first road request builds the "
              f"worker's router from cache)…", flush=True)
        client = KeepAliveClient(base, timeout=600.0)
        t0 = time.perf_counter()
        try:
            for req in workload.sequence(6):
                client.send(req)
        finally:
            client.close()
        warm_s = time.perf_counter() - t0

        duration = float(offsets[-1]) if len(offsets) else 0.0
        print(f"  [{label}] open loop: {len(offsets)} arrivals over "
              f"{duration:.0f}s…", flush=True)
        records = run_open_loop([base], offsets, requests, workers=16,
                                timeout=max(60.0, 4 * slo_ms / 1000))
        report = summarize(records, duration, len(offsets))

        gw.slo.tick()
        gateway_slo = gw.slo.snapshot()
        import urllib.request

        with urllib.request.urlopen(f"{base}/api/slo", timeout=30) as r:
            replica_slo = json.loads(r.read())
        health = json.loads(urllib.request.urlopen(
            f"{base}/api/health", timeout=30).read())
    finally:
        try:
            if httpd is not None:
                gw.drain(timeout=5)
        finally:
            sup.drain(timeout=20)

    road = (health.get("checks", {}).get("engine", {})
            .get("road_router")) or {}
    rr = report["routes"].get("/api/request_route", {})
    return {
        "label": label,
        "warm_first_requests_s": round(warm_s, 1),
        "load": report,
        "request_route_p95_ms": rr.get("latency", {}).get(
            "p95_ms", float("inf")),
        "slo": {"gateway_state": gateway_slo.get("state"),
                "replica_state": replica_slo.get("state"),
                "green": (gateway_slo.get("state") == "ok"
                          and replica_slo.get("state") == "ok")},
        "road_router": road,
        "route_cache": road.get("route_cache"),
        "batch": road.get("batch"),
    }


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--nodes", type=int, default=250_000)
    parser.add_argument("--rps", type=float, default=1.0,
                        help="offered open-loop arrival rate")
    parser.add_argument("--duration", type=float, default=90.0)
    parser.add_argument("--slo-ms", type=float, default=2500.0,
                        help="request_route latency SLO threshold "
                             "(registry bucket edges: 1000/2500/5000)")
    parser.add_argument("--quick", action="store_true",
                        help="50k extract, 45 s run — the slow-test "
                             "preset")
    parser.add_argument("--compare-cache", action="store_true",
                        help="rerun the identical offered load with the "
                             "route fastlane disabled and record both")
    parser.add_argument("--seed", type=int, default=42)
    parser.add_argument("--out", default=None)
    args = parser.parse_args()
    if args.quick:
        args.nodes = min(args.nodes, 50_000)
        args.duration = min(args.duration, 45.0)

    os.environ.setdefault("ROUTEST_FORCE_CPU", "1")
    import jax

    jax.config.update("jax_platforms", "cpu")

    from routest_tpu.core.cache import enable_compile_cache
    from routest_tpu.loadgen import MixedWorkload, RateCurve, poisson_schedule

    work_dir = tempfile.mkdtemp(prefix="router-serving-")
    hier_cache = os.path.join(work_dir, "hier")
    os.environ["ROUTEST_HIER_CACHE"] = hier_cache
    # Postmortem bundles from warm-phase SLO edges (the first road
    # request pays the router build) belong to the run dir, not the
    # repo's artifacts/.
    os.environ["RTPU_RECORDER_DIR"] = os.path.join(work_dir, "postmortems")
    enable_compile_cache()
    slo_spec = (f"/api/request_route:latency_ms={args.slo_ms:.0f},"
                f"latency_target=0.95,availability=0.99;"
                f"/api/predict_eta:latency_ms=1000,latency_target=0.95,"
                f"availability=0.999")
    os.environ["RTPU_SLO_OBJECTIVES"] = slo_spec

    print(f"[1/3] building {args.nodes:,}-node extract + overlay cache…",
          flush=True)
    extract = build_extract(args.nodes, work_dir)

    env = dict(os.environ)
    env.update({
        "ROAD_GRAPH_OSM": extract,
        "ROUTEST_HIER_CACHE": hier_cache,
        "ROUTEST_MESH": "0",
        "ROUTEST_WARM_BUCKETS": "0",
        "ETA_MODEL_PATH": MODEL,
        "RTPU_SLO_OBJECTIVES": slo_spec,
        # Route bodies are 3 waypoints (bucket 4); matrix/bench traffic
        # pads to 16; the batcher merges up to 32 rows.
        "ROUTEST_ROUTER_AOT": "2,4,16,32",
    })

    workload = MixedWorkload(
        mix={"request_route": 0.7, "predict_eta": 0.3},
        seed=args.seed, road_graph=True)
    curve = RateCurve.constant(args.rps)
    offsets = poisson_schedule(curve, args.duration, seed=args.seed)
    requests = workload.sequence(len(offsets))

    print("[2/3] fastlane-on phase (fleet: 1 worker + gateway)…",
          flush=True)
    phase_on = run_phase("cache-on", env, workload, offsets, requests,
                         args.slo_ms)

    phase_off = None
    if args.compare_cache:
        print("[3/3] fastlane-off phase (same offered load, "
              "ROUTEST_ROUTE_CACHE=0)…", flush=True)
        env_off = dict(env)
        env_off["ROUTEST_ROUTE_CACHE"] = "0"
        phase_off = run_phase("cache-off", env_off, workload, offsets,
                              requests, args.slo_ms)
    else:
        print("[3/3] skipped (--compare-cache off)", flush=True)

    p95_ms = phase_on["request_route_p95_ms"]
    slo_green = phase_on["slo"]["green"]
    passed = (p95_ms <= args.slo_ms and slo_green
              and phase_on["load"]["error_rate"] <= 0.01)
    try:
        n_cpus = len(os.sched_getaffinity(0))
    except AttributeError:
        n_cpus = os.cpu_count() or 1
    cache_stats = phase_on.get("route_cache") or {}
    record = {
        "host": {"cpus": n_cpus,
                 "note": "1 worker; wall latency scales with cores"},
        "host_caveat": f"cpu-backend record on {n_cpus} core(s): compare "
                       f"cache-on/off and batching ratios, not wall ms",
        "extract_nodes": args.nodes,
        "workload": workload.describe(),
        "offered": {"rps": args.rps, "duration_s": args.duration,
                    "arrivals": len(offsets)},
        "slo_threshold_ms": args.slo_ms,
        "warm_first_requests_s": phase_on["warm_first_requests_s"],
        "load": phase_on["load"],
        "request_route_p95_ms": p95_ms,
        "slo": phase_on["slo"],
        "road_router": phase_on["road_router"],
        "route_cache": cache_stats,
        "batch": phase_on.get("batch"),
        "pass": passed,
    }
    if phase_off is not None:
        off_p95 = phase_off["request_route_p95_ms"]
        record["cache_off"] = {
            "request_route_p95_ms": off_p95,
            "warm_first_requests_s": phase_off["warm_first_requests_s"],
            "load": phase_off["load"],
            "slo": phase_off["slo"],
            "route_cache": phase_off.get("route_cache"),
        }
        record["cache_speedup_p95"] = (
            round(off_p95 / p95_ms, 3)
            if p95_ms and p95_ms == p95_ms else None)

        def _mean(phase):
            return (phase["load"]["routes"]
                    .get("/api/request_route", {})
                    .get("latency", {}).get("mean_ms"))

        # At light offered load p95 is set by the occasional slow MISS
        # in either phase; the MEAN is the statistically meaningful
        # cache signal there (hits answer in ms, so the mean drops by
        # roughly the hit rate × miss cost).
        mean_on, mean_off = _mean(phase_on), _mean(phase_off)
        record["cache_speedup_mean"] = (
            round(mean_off / mean_on, 3)
            if mean_on and mean_off else None)
    out = args.out or os.path.join(REPO, "artifacts", "router_serving.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(record, f, indent=2)
    msg = (f"\nrequest_route p95 {p95_ms} ms (SLO {args.slo_ms:.0f} ms) | "
           f"cache hit rate {cache_stats.get('hit_rate')} | "
           f"slo gateway={record['slo']['gateway_state']} "
           f"replica={record['slo']['replica_state']} | "
           f"errors {phase_on['load']['error_rate']:.2%}")
    if phase_off is not None:
        off_p95 = record["cache_off"]["request_route_p95_ms"]
        msg += (f" | cache-off p95 {off_p95} ms "
                f"({record['cache_speedup_p95']}x)")
    print(msg + f" → {out}")
    sys.exit(0 if passed else 1)


if __name__ == "__main__":
    main()
