"""Road-router scale benchmark: metro-scale graphs (VERDICT r2 #5, r3 #1).

Measures the on-device shortest-path solver (``optimize/road_router.py``)
from the 2k-node serving default up to a ≥250k-node metro network with
OSM-extract topology — ORS-class territory, the engine the reference
outsources its matrix calls to (``Flaskr/utils.py:97-103``).

Two solver regimes are exercised: the flat batched Bellman-Ford below
``ROUTEST_HIER_MIN_NODES`` and the two-level partition overlay
(``optimize/hierarchy.py``) above it. Per size: graph build time,
router init (bridging + overlay precompute + device upload), cold solve
(XLA compile for that source bucket), warm solve wall time for a
16-waypoint batch (the quantity that gates request latency — one solve
prices a whole (M, M) leg matrix), the full matrix-operation time
(solve + M×M priced pairs incl. duration walks — the ORS matrix call
the reference rents), and with ``--verify`` a scipy Dijkstra oracle
parity check.

The ``--osm-nodes`` row builds an OSM-*topology* network (degree-2 bend
chains + one-ways via ``data/road_graph.py:subdivide_graph``), writes it
as real OSM XML and re-ingests it through ``data/osm.py:load_osm`` (the
native-scanner path), so the row routes what an actual extract parse
produces. A licensed real-city extract can't ship in this zero-egress
sandbox; topology + ingest path are the honest stand-in.

Writes artifacts/router_scale.json and prints a markdown table.
Runs on whatever jax backend is active and records which; --cpu forces
the hermetic CPU backend.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _time_solves(router, nodes):
    """One timing protocol for every regime: cold (pays compile) then
    min-of-3 warm. ``shortest`` host-syncs internally (device_get)."""
    t0 = time.perf_counter()
    dist, _ = router.shortest(nodes)
    t_cold = time.perf_counter() - t0
    solves = []
    for _ in range(3):
        t0 = time.perf_counter()
        dist, _ = router.shortest(nodes)
        solves.append(time.perf_counter() - t0)
    return dist, t_cold, min(solves)


def _bench_router(router, args, np, rng):
    pts = np.stack([
        rng.uniform(14.40, 14.68, args.waypoints),
        rng.uniform(120.96, 121.10, args.waypoints),
    ], axis=1).astype(np.float32)
    nodes = router.snap(pts)
    dist, t_cold, t_warm = _time_solves(router, nodes)
    phases = {}
    if router._hier is not None:
        # Per-phase breakdown (own dispatches — the fused program is
        # what t_warm measures): regressions localize to a phase.
        router._hier.timed_query(np.asarray(nodes, np.int32))
        _, phases = router._hier.timed_query(np.asarray(nodes, np.int32))
    # Full matrix operation (the ORS-comparable call the reference
    # rents per optimize request): solve + the M x M distance AND
    # duration matrices, exactly as /api/matrix serves them (durations
    # via the device-side pointer-doubling table, not per-pair walks).
    # Same min-of-3 protocol as the warm solve (fresh RoadLegs per
    # pass — memoization would make reused-object passes nearly free).
    matrix_times = []
    for _ in range(3):
        t0 = time.perf_counter()
        legs = router.route_legs(pts, 1.0, hour=8)
        legs.duration_matrix()
        matrix_times.append(time.perf_counter() - t0)
    return nodes, dist, t_cold, t_warm, min(matrix_times), phases


def _verify(router, nodes, dist, np):
    """Max relative error vs a float64 Dijkstra oracle (scipy)."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import dijkstra

    n = router.n_nodes
    adj = sp.coo_matrix(
        (router.length_m, (router.senders, router.receivers)),
        shape=(n, n)).tocsr()
    want = dijkstra(adj, directed=True, indices=np.asarray(nodes, np.int64))
    finite = np.isfinite(want)
    # Disagreement in EITHER direction is a failure: router-unreachable
    # where the oracle routes, or router-finite where the oracle says
    # unreachable (one-way pockets on the osm_extract row).
    if (dist[finite] > 1e37).any() or (dist[~finite] < 1e37).any():
        return float("inf")
    err = np.abs(dist[finite] - want[finite]) / np.maximum(want[finite], 1.0)
    return float(err.max())


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--sizes", type=int, nargs="+",
                        default=[2048, 8192, 50_000])
    parser.add_argument("--osm-nodes", type=int, default=250_000,
                        help="target size for the OSM-topology extract row "
                             "(0 skips it)")
    parser.add_argument("--osm-file", default="auto",
                        help="route a COMMITTED OSM extract as its own row "
                             "(topology=osm_file). Default 'auto' = the "
                             "curated Metro Manila arterial network "
                             "(artifacts/manila_arterials.osm.gz) when "
                             "present; 'none' skips; any path routes that "
                             "extract")
    parser.add_argument("--waypoints", type=int, default=16)
    parser.add_argument("--verify", action="store_true",
                        help="scipy Dijkstra oracle parity per row")
    parser.add_argument("--cpu", action="store_true",
                        help="hermetic CPU backend")
    parser.add_argument("--out", default=None,
                        help="artifact path (default artifacts/"
                             "router_scale.json); point one-off runs — "
                             "e.g. a country-scale probe — elsewhere so "
                             "the canonical record survives")
    parser.add_argument("--flat-compare", action="store_true",
                        help="for overlay rows, also time the flat "
                             "Bellman-Ford regime on the SAME graph, "
                             "waypoints and backend, recording "
                             "flat_warm_ms + overlay_speedup — the "
                             "apples-to-apples claim a cross-backend "
                             "comparison can't make")
    parser.add_argument("--flat-compare-max", type=int, default=50_000,
                        help="skip the flat comparison above this node "
                             "count (the diameter-bound sweep takes "
                             "minutes per solve there — the wall being "
                             "demonstrated)")
    parser.add_argument("--ml-compare", action="store_true",
                        help="for multi-level rows, also time a "
                             "SINGLE-level overlay on the same graph "
                             "(ROUTEST_HIER_MAX_LEVELS=1), recording "
                             "single_level_warm_ms + multi_level_speedup")
    parser.add_argument("--quick", action="store_true",
                        help="small preset for the slow-marked test: "
                             "one flat row, one overlay row with both "
                             "comparisons, no committed-extract row")
    args = parser.parse_args()
    # Solver bench: keep the route fastlane out of the matrix timings
    # (bench_router_serving.py measures the cache).
    os.environ.setdefault("ROUTEST_ROUTE_CACHE", "0")
    if args.quick:
        args.sizes = [2048, 24_000]
        args.osm_nodes = 0
        args.osm_file = "none"
        args.flat_compare = True
        args.ml_compare = True
    if args.cpu or os.environ.get("ROUTEST_FORCE_CPU") == "1":
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        import jax

        jax.config.update("jax_platforms", "cpu")

    import jax
    import numpy as np

    from routest_tpu.data.road_graph import generate_road_graph, subdivide_graph
    from routest_tpu.optimize.road_router import RoadRouter

    rows = []
    rng = np.random.default_rng(7)

    def _with_env(key, value, fn):
        old = os.environ.get(key)
        os.environ[key] = value
        try:
            return fn()
        finally:
            if old is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = old

    def run_case(graph, t_gen, topology):
        t0 = time.perf_counter()
        router = RoadRouter(graph=graph, use_gnn=False, use_transformer=False)
        t_init = time.perf_counter() - t0
        nodes, dist, t_cold, t_warm, t_matrix, phases = _bench_router(
            router, args, np, rng)
        reach = float((dist < 1e37).mean())
        row = {
            "nodes": router.n_nodes,
            "edges": int(len(router.senders)),
            "topology": topology,
            "waypoints": args.waypoints,
            "graph_build_s": round(t_gen, 2),
            "router_init_s": round(t_init, 2),
            "solve_cold_ms": round(1000 * t_cold, 1),
            "solve_warm_ms": round(1000 * t_warm, 1),
            "matrix_warm_ms": round(1000 * t_matrix, 1),
            "reachable_frac": round(reach, 4),
            "query_phases_ms": phases,
            **router.solver_info,
        }
        if args.verify:
            row["oracle_max_rel_err"] = _verify(router, nodes, dist, np)
        if (args.flat_compare and row.get("solver") == "hierarchy"
                and router.n_nodes <= args.flat_compare_max):
            flat = _with_env("ROUTEST_HIER_MIN_NODES", "0",
                             lambda: RoadRouter(graph=graph, use_gnn=False,
                                                use_transformer=False))
            _, _, flat_warm = _time_solves(flat, nodes)  # same waypoints
            row["flat_warm_ms"] = round(1000 * flat_warm, 1)
            row["overlay_speedup"] = round(flat_warm / max(t_warm, 1e-9), 1)
            print(f"      flat_bf same graph/backend: warm "
                  f"{row['flat_warm_ms']}ms → overlay speedup "
                  f"{row['overlay_speedup']}x", flush=True)
        if (args.ml_compare and row.get("solver") == "hierarchy"
                and row.get("overlay", {}).get("n_levels", 1) > 1):
            # The baseline is the PR-8 regime: ONE level, no hub
            # labels — with labels enabled a single-level overlay
            # would get the top for free from the label fold, and the
            # comparison would no longer measure what stacking buys.
            single = _with_env(
                "ROUTEST_HIER_MAX_LEVELS", "1",
                lambda: _with_env(
                    "ROUTEST_HIER_LABELS", "0",
                    lambda: RoadRouter(graph=graph, use_gnn=False,
                                       use_transformer=False)))
            _, _, single_warm = _time_solves(single, nodes)
            row["single_level_warm_ms"] = round(1000 * single_warm, 1)
            row["multi_level_speedup"] = round(
                single_warm / max(t_warm, 1e-9), 2)
            print(f"      single-level same graph/backend: warm "
                  f"{row['single_level_warm_ms']}ms → multi-level "
                  f"speedup {row['multi_level_speedup']}x", flush=True)
        rows.append(row)
        print(f"  {row['nodes']:>7,} nodes {row['edges']:>9,} edges "
              f"[{topology}/{row['solver']}] | build {row['graph_build_s']}s "
              f"init {row['router_init_s']}s | solve cold "
              f"{row['solve_cold_ms']}ms warm {row['solve_warm_ms']}ms "
              f"matrix {row['matrix_warm_ms']}ms"
              + (f" | oracle err {row.get('oracle_max_rel_err'):.2e}"
                 if args.verify else ""), flush=True)

    for n in args.sizes:
        if n <= 0:          # `--sizes 0` = osm-extract row only
            continue
        t0 = time.perf_counter()
        graph = generate_road_graph(n_nodes=n, k=4, seed=0)
        run_case(graph, time.perf_counter() - t0, "generator")

    if args.osm_nodes:
        # intersections + 2 bends/street ≈ 1 + 2·2.43 nodes per
        # intersection for the k=4 kNN street graph
        n_int = max(1024, int(args.osm_nodes / 5.86))
        t0 = time.perf_counter()
        base = generate_road_graph(n_nodes=n_int, k=4, seed=0)
        streets = subdivide_graph(base, bends_per_edge=2, oneway_frac=0.1,
                                  seed=0)
        from routest_tpu.data.osm import load_osm, save_osm

        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "metro.osm.gz")
            save_osm(path, streets)
            extract = load_osm(path)
        run_case(extract, time.perf_counter() - t0, "osm_extract")

    osm_file = args.osm_file
    if osm_file == "auto":
        osm_file = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "artifacts",
            "manila_arterials.osm.gz")
        if not os.path.exists(osm_file):
            osm_file = "none"
    if osm_file != "none":
        # A real-provenance network (curated Metro Manila arterials,
        # scripts/make_manila_extract.py — VERDICT r4 next #6) beside
        # the generator rows: same solver, real street geometry.
        from routest_tpu.data.osm import load_osm as _load

        t0 = time.perf_counter()
        extract = _load(osm_file)
        run_case(extract, time.perf_counter() - t0, "osm_file")

    report = {"backend": jax.default_backend(), "rows": rows}
    out = args.out or os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "artifacts", "router_scale.json")
    if os.path.dirname(out):
        os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=2)

    print(f"\n| nodes | edges | topology | solver | warm solve "
          f"({args.waypoints} sources) | matrix ({args.waypoints}x"
          f"{args.waypoints}) | cold (compile) |")
    print("|---|---|---|---|---|---|---|")
    for r in rows:
        print(f"| {r['nodes']:,} | {r['edges']:,} | {r['topology']} | "
              f"{r['solver']} | {r['solve_warm_ms']} ms | "
              f"{r['matrix_warm_ms']} ms | {r['solve_cold_ms']} ms |")
    print(f"\nbackend={report['backend']} → {out}")


if __name__ == "__main__":
    main()
