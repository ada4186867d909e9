"""Blackbox probing end to end → artifacts/probing.json.

The ISSUE-15 acceptance scenario: a real fleet (supervisor + workers +
in-process gateway, live traffic where the scenario needs metric
epochs) under open-loop load, with the blackbox prober armed. Three
injected correctness faults — each invisible to every layer built
before this PR, because the replica keeps answering well-formed 200s —
must each be detected by the prober, page the correctness SLO within a
bounded window, and produce a flight-recorder bundle naming the
faulty replica and embedding the probe/oracle pair:

- ``compute_divergence`` — a replica rolled onto seeded
  ``device.compute:skew`` chaos (the silently-wrong device: outputs
  perturbed, status 200);
- ``stale_epoch``       — a replica whose ``live.customize`` cycles
  are chaos-dropped, so it serves a frozen metric epoch while the
  fleet moves on (the skew failure rollouts / multi-region create);
- ``divergent_model``   — a corrupt-ish artifact (params + 1e6,
  finite outputs, divergence far past the swap gate's margin) landed
  on one replica via a fresh-boot rollout — the path the golden gate
  never sees.

The ``clean`` scenario proves the other half: across ≥1 legitimate
metric flip and ≥1 verified model swap the prober raises ZERO
correctness pages, probe traffic appears in no user-facing SLO family,
the served route answer matches the scipy oracle on the replica's own
exported metric, and arming the prober adds ≤1% (with a small absolute
noise floor, recorded structurally) to serving p95.

The overlay hierarchy and the synthetic extract are shared across
scenarios and runs via ``--cache-dir`` (default
``artifacts/bench_cache/probing``), so only the first run pays the
cold road-graph build; XLA compiles go to the one compile cache
(``core/cache.py``).

Usage: python scripts/bench_probing.py [--quick]
       [--out artifacts/probing.json] [--cache-dir DIR]
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

MODEL = os.path.join(REPO, "artifacts", "eta_mlp.msgpack")

# The swap gate's margin for this bench's fleet — the prober derives
# its golden tolerance from it (a model the gate would accept never
# trips the prober; one past the gate always does).
SWAP_MAX_DIV_MIN = 30.0
PROBE_INTERVAL_S = 1.0
# Probe-scale SLO windows: pages after ~5 consecutive failing rounds.
PROBE_FAST_S, PROBE_SLOW_S = 10.0, 30.0
DETECT_BOUND_S = 90.0
# Overhead gate: ≤1% of serving p95, with an absolute noise floor for
# a 1-core time-shared host (recorded structurally in the artifact).
OVERHEAD_PCT = 0.01
OVERHEAD_FLOOR_MS = 2.0


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


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


def build_extract(n_nodes: int, cache_dir: str) -> str:
    """Synthetic street extract, cached across scenarios and battery
    rounds (the probe-subgraph build rides the shared warm-cache path
    — ROADMAP housekeeping: no cold hierarchy build per round)."""
    path = os.path.join(cache_dir, f"probing_{n_nodes}.osm.gz")
    if os.path.exists(path):
        return path
    from routest_tpu.data.osm import load_osm, save_osm
    from routest_tpu.data.road_graph import (generate_road_graph,
                                             subdivide_graph)
    from routest_tpu.optimize.road_router import RoadRouter

    n_int = max(512, int(n_nodes / 5.86))
    base = generate_road_graph(n_nodes=n_int, k=4, seed=0)
    streets = subdivide_graph(base, bends_per_edge=2, oneway_frac=0.1,
                              seed=0)
    save_osm(path, streets)
    # Prebuild the overlay so every worker rehydrates from cache.
    t0 = time.perf_counter()
    RoadRouter(graph=load_osm(path), use_gnn=False,
               use_transformer=False)
    print(f"  overlay prebuilt in {time.perf_counter() - t0:.1f}s",
          flush=True)
    return path


class Fleet:
    """One scenario's fleet: supervisor + workers + in-process gateway
    + (optionally) broker, probe drivers, and the armed prober."""

    def __init__(self, *, live: bool, extract: str, cache_dir: str,
                 work_dir: str, replicas: int = 2,
                 drivers: int = 48, customize_s: float = 3.0,
                 probe_interval: float = PROBE_INTERVAL_S) -> None:
        from routest_tpu.core.config import (FleetConfig, ProberConfig,
                                             RecorderConfig)
        from routest_tpu.obs.recorder import (FlightRecorder,
                                              configure_recorder)
        from routest_tpu.serve.fleet.gateway import Gateway
        from routest_tpu.serve.fleet.supervisor import ReplicaSupervisor

        self.live = live
        self.work_dir = work_dir
        self.recorder_dir = os.path.join(work_dir, "postmortems")
        self.recorder = FlightRecorder(RecorderConfig(
            dir=self.recorder_dir, min_interval_s=0.0))
        configure_recorder(self.recorder)
        self.model_path = os.path.join(work_dir, "eta_serving.msgpack")
        shutil.copy(MODEL, self.model_path)
        self.broker = None
        self.probe_fleet = None
        env = dict(os.environ)
        env.update({
            "ROUTEST_FORCE_CPU": "1",
            "ROUTEST_WARM_BUCKETS": "0",
            "ROUTEST_MESH": "0",
            "ETA_MODEL_PATH": self.model_path,
            "ROUTEST_RELOAD_SEC": "0.5",
            "RTPU_SWAP_MAX_DIV": f"{SWAP_MAX_DIV_MIN:g}",
            "RTPU_RECORDER_DIR": os.path.join(work_dir, "workers"),
        })
        if live:
            from routest_tpu.serve.netbus import start_broker

            self.broker, _ = start_broker()
            env.update({
                "ROAD_GRAPH_OSM": extract,
                "ROUTEST_HIER_CACHE": os.path.join(cache_dir, "hier"),
                "REDIS_URL": f"tcp://127.0.0.1:{self.broker.port}",
                "RTPU_LIVE": "1",
                "RTPU_LIVE_CUSTOMIZE_S": f"{customize_s:g}",
                "RTPU_LIVE_HALF_LIFE_S": "10",
                "RTPU_LIVE_MIN_OBS_EDGES": "10",
            })
        self.env = env
        self.ports = [_free_port() for _ in range(replicas)]
        self.sup = ReplicaSupervisor(self.ports, env=env, cwd=REPO,
                                     probe_interval_s=0.5,
                                     backoff_base_s=0.2,
                                     backoff_cap_s=2.0)
        self.sup.start()
        if not self.sup.ready(timeout=600):
            self.sup.drain(timeout=10)
            raise RuntimeError("fleet workers never became ready")
        self.gw = Gateway([("127.0.0.1", p) for p in self.ports],
                          FleetConfig(hedge=False, max_inflight=64,
                                      queue_depth=256), supervisor=self.sup)
        self.httpd = self.gw.serve("127.0.0.1", 0)
        self.base = f"http://127.0.0.1:{self.httpd.server_address[1]}"
        from routest_tpu.data.locations import SEED_LOCATIONS

        a, b = SEED_LOCATIONS[2], SEED_LOCATIONS[11]
        self.prober_cfg = ProberConfig(
            enabled=True, interval_s=probe_interval, timeout_s=20.0,
            eta_tolerance=SWAP_MAX_DIV_MIN,
            route_tolerance_rel=0.02,   # cross-replica EWMA drift; the
            # strict per-replica 2e-3 parity is measured separately
            routes=(f"{a[1]},{a[2]}|{b[1]},{b[2]}" if live else ""),
            skew_after=3, epoch_gap=2,
            fast_window_s=PROBE_FAST_S, slow_window_s=PROBE_SLOW_S)
        self.prober = None
        self._driver_count = drivers
        if live:
            self._wait_live_ready()

    def start_probe_drivers(self) -> None:
        from routest_tpu.data.osm import load_osm
        from routest_tpu.live.probes import ProbeFleet
        from routest_tpu.optimize.road_router import RoadRouter
        from routest_tpu.serve.netbus import NetBus

        if self.probe_fleet is not None:
            return
        router = RoadRouter(graph=load_osm(self.env["ROAD_GRAPH_OSM"]),
                            use_gnn=False, use_transformer=False)
        self.oracle_router = router
        bus = NetBus(f"tcp://127.0.0.1:{self.broker.port}")
        self.probe_fleet = ProbeFleet(router.graph_dict(),
                                      self._driver_count,
                                      bus.publish, seed=42,
                                      obs_per_tick=6)
        self.probe_fleet.start(tick_s=1.0)

    def _wait_live_ready(self, timeout: float = 300.0) -> None:
        deadline = time.time() + timeout
        for port in self.ports:
            while time.time() < deadline:
                try:
                    if _fetch(f"http://127.0.0.1:{port}/api/live",
                              timeout=10).get("ready"):
                        break
                except OSError:
                    pass
                time.sleep(0.5)
            else:
                raise RuntimeError(f"replica :{port} live never armed")

    def arm_prober(self):
        from routest_tpu.obs.prober import BlackboxProber

        self.prober = BlackboxProber(
            self.prober_cfg, gateway_base=self.base,
            targets_fn=self.gw._probe_targets, recorder=self.recorder)
        self.gw.prober = self.prober     # /api/probes surfaces it
        self.prober.start()
        return self.prober

    def replica_rids(self):
        with self.gw._lock:
            return sorted((r.id for r in self.gw.replicas
                           if not r.draining),
                          key=lambda rid: int(rid[1:]))

    def inject_replacement(self, rid: str, overlay: dict,
                           version: str) -> str:
        """Roll ONE replica onto (version, overlay); returns the
        successor's rid — the replica the prober must name."""
        from routest_tpu.serve.fleet.rollout import replace_replica

        old_port = self.ports[int(rid[1:])]
        result = replace_replica(self.sup, self.gw, rid,
                                 version=version, env=overlay,
                                 boot_timeout_s=300.0,
                                 health_timeout_s=60.0)
        if not result.get("ok"):
            raise RuntimeError(f"fault injection rollout failed: "
                               f"{result}")
        self.ports = [p for p in self.ports if p != old_port] \
            + [result["port"]]
        if self.live:
            self._wait_live_ready()
        return result["new_rid"]

    def stop(self) -> None:
        from routest_tpu.obs.recorder import configure_recorder

        if self.prober is not None:
            self.prober.stop()
        if self.probe_fleet is not None:
            self.probe_fleet.stop()
        try:
            self.gw.drain(timeout=5)
        finally:
            self.sup.drain(timeout=15)
            if self.broker is not None:
                self.broker.shutdown()
            configure_recorder(None)


def open_loop(base: str, rate: float, duration_s: float, stop=None):
    """Blocking open-loop predict_eta load (unique bodies) → records."""
    from routest_tpu.loadgen.arrivals import RateCurve, paced_schedule
    from routest_tpu.loadgen.engine import run_open_loop
    from routest_tpu.loadgen.workload import PlannedRequest

    offsets = paced_schedule(RateCurve.constant(rate), duration_s)
    requests = [PlannedRequest(
        method="POST", path="/api/predict_eta",
        body={"summary": {"distance": 7000 + i}, "weather": "Sunny",
              "traffic": "Medium", "driver_age": 33,
              "pickup_time": "2026-08-05T18:00:00"},
        route="predict_eta") for i in range(len(offsets))]
    return run_open_loop([base], offsets, requests, workers=8,
                         timeout=30.0, stop=stop)


def _p95_ms(records) -> float:
    ok = sorted(r.latency_s for r in records if 200 <= r.status < 400)
    if not ok:
        return float("nan")
    return ok[min(len(ok) - 1, int(0.95 * len(ok)))] * 1000.0


def wait_for_page(prober, bound_s: float):
    """Poll the prober's dedicated engine until any correctness
    objective pages."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < bound_s:
        snap = prober.slo.snapshot()
        for name, obj in snap["objectives"].items():
            if obj["state"] == "page":
                return {"paged": True, "objective": name,
                        "detect_s": round(time.monotonic() - t0, 2)}
        time.sleep(0.2)
    return {"paged": False, "detect_s": None}


def correctness_bundles(recorder_dir: str):
    out = []
    if not os.path.isdir(recorder_dir):
        return out
    for name in sorted(os.listdir(recorder_dir)):
        if not name.startswith("pm_") or "correctness" not in name:
            continue
        bundle = os.path.join(recorder_dir, name)
        try:
            evidence = json.load(open(
                os.path.join(bundle, "probe_evidence.json")))
            manifest = json.load(open(
                os.path.join(bundle, "manifest.json")))
        except (OSError, ValueError):
            continue
        out.append({"name": name, "evidence": evidence,
                    "manifest_reason": manifest.get("reason"),
                    "detail": manifest.get("detail")})
    return out


def judge_fault_bundle(bundles, faulty_rid: str,
                       require_dimensions=None) -> dict:
    """A correctness bundle must name the faulty replica and embed the
    probe request, served answer, oracle/pinned answer, divergence.
    ``require_dimensions`` additionally demands a skew failure on one
    of the given dimensions (e.g. the stale-epoch scenario must be
    identified AS an epoch skew, not only as a divergent answer)."""
    for b in bundles:
        ev = b["evidence"]
        if faulty_rid not in (ev.get("replicas") or []):
            continue
        for f in reversed(ev.get("failures") or []):
            named = faulty_rid in (f.get("replicas") or [])
            embedded = (f.get("request") is not None
                        and f.get("served") is not None
                        and (f.get("expected") is not None
                             or f.get("oracle") is not None
                             or f.get("dimensions") is not None))
            has_div = (f.get("divergence") is not None
                       or f.get("dimensions") is not None)
            dims = sorted(f.get("dimensions") or ())
            if require_dimensions is not None and \
                    not (set(dims) & set(require_dimensions)):
                continue
            if named and embedded and has_div:
                return {"ok": True, "bundle": b["name"],
                        "verdict": f.get("verdict"),
                        "divergence": f.get("divergence"),
                        "dimensions": dims}
    return {"ok": False,
            "bundles_seen": [b["name"] for b in bundles]}


def zero_pages(prober, recorder_dir: str) -> dict:
    snap = prober.slo.snapshot()
    states = {k: v["state"] for k, v in snap["objectives"].items()}
    return {"objective_states": states,
            "correctness_bundles": len(correctness_bundles(recorder_dir)),
            "ok": all(s == "ok" for s in states.values())
            and not correctness_bundles(recorder_dir)}


# ── scenarios ────────────────────────────────────────────────────────


def scenario_clean(extract, cache_dir, rate, quick) -> dict:
    work = tempfile.mkdtemp(prefix="probing-clean-")
    window_s = 12.0 if quick else 20.0
    out: dict = {"scenario": "clean"}
    # The clean scenario measures the STANDING cost of probing, so it
    # runs the production-shaped interval (the fault scenarios crank
    # the interval down for fast detection, a deliberate trade).
    fleet = Fleet(live=True, extract=extract, cache_dir=cache_dir,
                  work_dir=work, probe_interval=2.5)
    try:
        # (1) overhead: alternating prober-off / prober-on load
        # windows, best (min) p95 per mode — the obs-overhead bench's
        # order-drift cancellation, cheap edition. Probe DRIVERS stay
        # off for this phase (they are scenario background, not the
        # treatment variable — their ingest work swamps a 1-core
        # host's p95 in both modes); the prober warms first (oracle
        # armed, probe shapes compiled, caches primed): the claim is
        # the STANDING cost of probing, not the one-time arm cost.
        prober = fleet.arm_prober()
        time.sleep(4 * fleet.prober_cfg.interval_s)
        prober.stop()
        offs, ons = [], []
        offs.append(_p95_ms(open_loop(fleet.base, rate, window_s)))
        prober.start()
        ons.append(_p95_ms(open_loop(fleet.base, rate, window_s)))
        prober.stop()
        offs.append(_p95_ms(open_loop(fleet.base, rate, window_s)))
        prober.start()
        ons.append(_p95_ms(open_loop(fleet.base, rate, window_s)))
        p95_off, p95_on = min(offs), min(ons)
        overhead_ok = (p95_on <= p95_off * (1 + OVERHEAD_PCT)
                       or p95_on - p95_off <= OVERHEAD_FLOOR_MS)
        out["overhead"] = {
            "p95_off_ms": round(p95_off, 2),
            "p95_on_ms": round(p95_on, 2),
            "windows_off_ms": [round(v, 2) for v in offs],
            "windows_on_ms": [round(v, 2) for v in ons],
            "budget_pct": OVERHEAD_PCT * 100,
            "noise_floor_ms": OVERHEAD_FLOOR_MS,
            "ok": bool(overhead_ok),
        }

        # (2) scenario background on: probe drivers stream per-edge
        # observations so the live metric flips for real; then a
        # verified model swap mid-run — rewrite the fleet's artifact
        # with a within-gate perturbation; both replicas' reload
        # watchers land it through the golden gate.
        fleet.start_probe_drivers()
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
            for port in fleet.ports:
                reg = _fetch(f"http://127.0.0.1:{port}/api/metrics",
                             timeout=30).get("registry", {})
                for s in reg.get("rtpu_model_swaps_total",
                                 {}).get("series", ()):
                    if s.get("labels", {}).get("result") == "accepted":
                        total += int(s.get("value", 0))
            return total

        epoch0 = max(e for e in (
            _fetch(f"http://127.0.0.1:{p}/api/live",
                   timeout=30).get("epoch", 0) for p in fleet.ports))
        deadline = time.time() + (60 if quick else 120)
        while time.time() < deadline:
            if swaps_accepted() >= 2:
                break
            time.sleep(1.0)
        # (3) ≥1 legitimate metric flip while the prober watches.
        flips = 0
        while time.time() < deadline and flips < 1:
            flips = max(e for e in (
                _fetch(f"http://127.0.0.1:{p}/api/live",
                       timeout=30).get("epoch", 0)
                for p in fleet.ports)) - epoch0
            time.sleep(1.0)
        time.sleep(5 * PROBE_INTERVAL_S)   # post-flip probe rounds
        out["swaps_accepted"] = swaps_accepted()
        out["metric_flips"] = flips

        # (4) strict per-replica oracle parity (the PR-9 invariant, as
        # the prober's own oracle computes it): served duration vs
        # scipy on the SAME replica's export.
        out["strict_oracle"] = strict_oracle_check(fleet)

        # (5) verdicts, zero pages, exclusion.
        out["final_verdicts"] = {
            k: v.get("verdict")
            for k, v in fleet.prober.snapshot()["probes"].items()}
        out["zero_pages"] = zero_pages(fleet.prober, fleet.recorder_dir)
        out["exclusion"] = exclusion_check(fleet)
        out["probe_rounds"] = fleet.prober._rounds
        checks = {
            "zero_correctness_pages": out["zero_pages"]["ok"],
            "verified_swap_ge_1": out["swaps_accepted"] >= 1,
            "metric_flip_ge_1": flips >= 1,
            "all_probes_pass_at_end": all(
                v == "pass" for v in out["final_verdicts"].values()),
            "strict_oracle_parity": out["strict_oracle"]["ok"],
            "probe_traffic_excluded": out["exclusion"]["ok"],
            "overhead_within_budget": out["overhead"]["ok"],
        }
        out["checks"] = checks
        out["pass"] = all(checks.values())
    finally:
        fleet.stop()
        shutil.rmtree(work, ignore_errors=True)
    return out


def strict_oracle_check(fleet) -> dict:
    """Served route duration ≡ scipy Dijkstra on the replica's OWN
    exported metric (epoch-stable fetch), to 2e-3 — the oracle the
    prober re-derives per flip, verified at full strictness against
    one replica (gateway-path probes tolerate cross-replica EWMA
    drift)."""
    import numpy as np
    import scipy.sparse as sp
    from scipy.sparse.csgraph import dijkstra

    wps = fleet.prober.route_waypoints
    replica = f"http://127.0.0.1:{fleet.ports[0]}"
    body = {"source_point": {"lat": wps[0][0], "lon": wps[0][1]},
            "destination_points": [{"lat": wps[1][0], "lon": wps[1][1],
                                    "payload": 1}],
            "driver_details": {"vehicle_type": "car",
                               "vehicle_capacity": 1e9,
                               "maximum_distance": 1e9},
            "road_graph": True}
    topo = _fetch(f"{replica}/api/debug/probe_subgraph?"
                  f"wp={wps[0][0]},{wps[0][1]}&wp={wps[1][0]},{wps[1][1]}",
                  timeout=60)
    for _attempt in range(5):
        live0 = _fetch(f"{replica}/api/live?metric=1", timeout=60)
        feat = _post(f"{replica}/api/request_route", body, timeout=120)
        live1 = _fetch(f"{replica}/api/live", timeout=60)
        if live0.get("epoch") != live1.get("epoch") \
                or "edge_time_s" not in live0:
            continue
        metric = np.asarray(live0["edge_time_s"], np.float64)
        adj = sp.coo_matrix(
            (metric, (np.asarray(topo["senders"]),
                      np.asarray(topo["receivers"]))),
            shape=(topo["nodes"], topo["nodes"])).tocsr()
        snapped = np.asarray(topo["snapped"])
        want = dijkstra(adj, directed=True, indices=snapped[:1])
        oracle_s = float(want[0, snapped[1]]) \
            + float(sum(topo["snap_m"])) / 8.3
        served_s = float(feat["properties"]["summary"]["duration"])
        rel = abs(served_s - oracle_s) / max(oracle_s, 1.0)
        return {"ok": rel < 2e-3, "epoch": live0.get("epoch"),
                "served_s": round(served_s, 2),
                "oracle_s": round(oracle_s, 2),
                "rel_err": round(rel, 6)}
    return {"ok": False, "error": "no epoch-stable window"}


def exclusion_check(fleet) -> dict:
    """Probe traffic appears in no user-facing family: the probed
    routes' user request families stay at zero while the probe
    families carry the traffic."""
    reg = _fetch(f"{fleet.base}/api/metrics", timeout=30)["registry"]

    def family(name):
        return {tuple(s.get("labels", {}).values()):
                s.get("value", s.get("count", 0))
                for s in reg.get(name, {}).get("series", ())}

    user = family("rtpu_gateway_request_seconds")
    probe = family("rtpu_probe_gateway_requests_total")
    probed_routes = ["/api/predict_eta_batch", "/api/request_route",
                     "/api/matrix"]
    leaked = {r: user.get((r,), 0) for r in probed_routes
              if user.get((r,), 0)}
    carried = sum(probe.get((r,), 0) for r in probed_routes)
    return {"ok": not leaked and carried > 0,
            "leaked_user_counts": leaked,
            "probe_family_count": carried,
            "user_predict_eta_count":
                user.get(("/api/predict_eta",), 0)}


def scenario_fault(name, extract, cache_dir, rate, quick, *,
                   live, overlay=None, corrupt_model=False,
                   expect_dimensions=None) -> dict:
    """Shared fault harness: boot → arm → baseline all-pass → inject
    via replace_replica → page within bound → bundle names replica."""
    work = tempfile.mkdtemp(prefix=f"probing-{name}-")
    out: dict = {"scenario": name}
    fleet = Fleet(live=live, extract=extract, cache_dir=cache_dir,
                  work_dir=work)
    load_stop = threading.Event()
    try:
        if live:
            fleet.start_probe_drivers()
        prober = fleet.arm_prober()
        # Light background load for realism (user SLO must stay ok).
        def _load():
            while not load_stop.is_set():
                try:
                    open_loop(fleet.base, rate, 10.0, stop=load_stop)
                except Exception:
                    pass

        load_thread = threading.Thread(target=_load, daemon=True)
        load_thread.start()
        baseline_deadline = time.time() + (30 if quick else 60)
        while time.time() < baseline_deadline:
            snap = prober.snapshot()["probes"]
            if snap and all(v.get("verdict") == "pass"
                            for v in snap.values()):
                break
            time.sleep(1.0)
        out["baseline_verdicts"] = {
            k: v.get("verdict")
            for k, v in prober.snapshot()["probes"].items()}
        overlay = dict(overlay or {})
        if corrupt_model:
            import jax

            from routest_tpu.train.checkpoint import (load_model,
                                                      save_model)

            # ×1.5-scaled weights: outputs stay finite and plausibly
            # sized (median ~100 min off, no timestamp overflow — the
            # replica keeps answering clean 200s) yet sit far past the
            # swap gate's margin. The corrupt-ISH artifact: wrong, not
            # broken.
            model, params = load_model(fleet.model_path)
            garbage = jax.tree_util.tree_map(lambda x: x * 1.5, params)
            bad_path = os.path.join(work, "eta_bad.msgpack")
            save_model(bad_path, model, garbage)
            overlay["ETA_MODEL_PATH"] = bad_path
        victim = fleet.replica_rids()[0]
        t_fault = time.time()
        faulty_rid = fleet.inject_replacement(victim, overlay,
                                              version=f"v-{name}")
        out.update({"victim": victim, "faulty_rid": faulty_rid,
                    "inject_wall_s": round(time.time() - t_fault, 1)})
        page = wait_for_page(prober, DETECT_BOUND_S)
        out["page"] = page
        out["detect_bound_s"] = DETECT_BOUND_S
        # The FIRST page may come from a probe kind that names the
        # replica indirectly (a gateway-path divergence carries the
        # serving replica; the fan-out skew verdict lands a few
        # debounce rounds later) — poll until a bundle naming the
        # faulty replica exists, still inside the detection bound.
        deadline = time.monotonic() + 45.0
        while time.monotonic() < deadline:
            bundles = correctness_bundles(fleet.recorder_dir)
            out["bundle"] = judge_fault_bundle(
                bundles, faulty_rid,
                require_dimensions=expect_dimensions)
            if out["bundle"]["ok"]:
                break
            time.sleep(1.0)
        if expect_dimensions:
            dims = set(out["bundle"].get("dimensions") or ())
            out["bundle"]["expected_dimensions_seen"] = \
                bool(dims & set(expect_dimensions))
        # User SLO must be untouched by the correctness incident (the
        # replica answered 200s throughout).
        gw_slo = fleet.gw.slo
        if gw_slo is not None:
            gw_slo.tick()
            out["user_slo_state"] = gw_slo.worst_state()
        checks = {
            "detected_and_paged": bool(page["paged"]),
            "within_bound": bool(page["paged"]
                                 and page["detect_s"] <= DETECT_BOUND_S),
            "bundle_names_faulty_replica": out["bundle"]["ok"],
            "user_slo_ok": out.get("user_slo_state", "ok") == "ok",
        }
        if expect_dimensions:
            checks["skew_dimension_identified"] = \
                out["bundle"].get("expected_dimensions_seen", False)
        out["checks"] = checks
        out["pass"] = all(checks.values())
    finally:
        load_stop.set()
        # Join BEFORE teardown: late client requests against a
        # draining gateway would record 503s into the GLOBAL gateway
        # families and poison the next scenario's user-SLO engine.
        try:
            load_thread.join(timeout=20)
        except (NameError, RuntimeError):
            pass
        fleet.stop()
        shutil.rmtree(work, ignore_errors=True)
    return out


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--quick", action="store_true",
                        help="smaller extract + shorter phases (CI)")
    parser.add_argument("--nodes", type=int, default=6000)
    parser.add_argument("--rate", type=float, default=3.0)
    parser.add_argument("--cache-dir", default=os.path.join(
        REPO, "artifacts", "bench_cache", "probing"))
    parser.add_argument("--out", default=os.path.join(
        REPO, "artifacts", "probing.json"))
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
    os.environ["RTPU_SWAP_MAX_DIV"] = f"{SWAP_MAX_DIV_MIN:g}"

    t0 = time.time()
    print(f"[1/5] extract + overlay cache ({args.nodes:,} nodes)…",
          flush=True)
    extract = build_extract(args.nodes, args.cache_dir)

    scenarios: dict = {}
    plan = [
        ("clean", lambda: scenario_clean(
            extract, args.cache_dir, args.rate, args.quick)),
        ("compute_divergence", lambda: scenario_fault(
            "compute_divergence", extract, args.cache_dir, args.rate,
            args.quick, live=False,
            overlay={"RTPU_CHAOS_SPEC": "device.compute:skew=1.0/60",
                     "RTPU_CHAOS_SEED": "7"})),
        ("stale_epoch", lambda: scenario_fault(
            "stale_epoch", extract, args.cache_dir, args.rate,
            args.quick, live=True,
            overlay={"RTPU_CHAOS_SPEC": "live.customize:error=1.0",
                     "RTPU_CHAOS_SEED": "7"},
            expect_dimensions=("epoch",))),
        ("divergent_model", lambda: scenario_fault(
            "divergent_model", extract, args.cache_dir, args.rate,
            args.quick, live=False, corrupt_model=True)),
    ]
    for i, (name, run) in enumerate(plan):
        if args.scenario and name != args.scenario:
            continue
        print(f"[{i + 2}/5] scenario {name}…", flush=True)
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
        # Structural caveats (ROADMAP housekeeping: skip reasons are
        # fields, never prose in `note`): detection windows and the
        # overhead floor are host-scaled; the invariants (detected →
        # paged → bundle names replica; clean stays green) are not.
        "host_caveat": (
            f"cpu-backend record on {n_cpus} core(s): detection "
            "latencies and p95s are time-shared-host numbers; judge "
            "the structural checks (paged within bound, bundle names "
            "the replica, clean run green, exclusion exact), not "
            "wall-ms" if backend != "tpu" else None),
        "skipped": ("tpu rows: not measured (this run used the cpu "
                    "backend)" if backend != "tpu" else None),
        "config": {
            "nodes": args.nodes, "rate_rps": args.rate,
            "probe_interval_s": PROBE_INTERVAL_S,
            "probe_fast_s": PROBE_FAST_S,
            "probe_slow_s": PROBE_SLOW_S,
            "swap_gate_margin_min": SWAP_MAX_DIV_MIN,
            "detect_bound_s": DETECT_BOUND_S,
            "overhead_budget_pct": OVERHEAD_PCT * 100,
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
    print(f"\n[5/5] checks: "
          + " ".join(f"{k}={'PASS' if v else 'FAIL'}"
                     for k, v in record["checks"].items())
          + f"\n→ {args.out} (all_pass={record['all_pass']}, "
            f"{record['wall_s']}s)", flush=True)
    # _exit, not sys.exit: probe-driver daemon threads racing
    # interpreter teardown must not turn a written verdict into a
    # crash (same contract as bench_live_traffic).
    os._exit(0 if record["all_pass"] else 1)


if __name__ == "__main__":
    main()
