"""Per-chip fleet scaling: the chips={1,2,4,8} preds/s curve plus the
8-chip placement comparison (8×1 vs 2×4 vs 1×8) — topology-aware
placement proven end to end.

PR 10 made the compute side multi-chip (the AOT scoring artifact
compiles under mesh batch shardings) but nothing fleet-side ever
*placed* more than one chip, so BASELINE's ≥10k preds/s/chip was
unmeasurable per chip. This bench pins the whole shape on virtual
devices (``XLA_FLAGS --xla_force_host_platform_device_count``) so it
runs identically the moment real hardware shows up:

1. **curve** — ONE replica pinned to k ∈ {1,2,4,8} chips via the
   placement overlay machinery (``serve/fleet/placement.slice_env``;
   multi-chip slices serve with the mesh batch sharding), driven with
   ``/api/predict_eta_batch`` through a real gateway → preds/s,
   preds/s/chip, and per-chip efficiency.
2. **placements** — three fleets spending the SAME 8 chips (8×1-chip,
   2×4-chip, 1×8-chip), same offered load → preds/s + client errors,
   with every placement's scores checked against the single-replica
   scorer oracle (the chips=1 fleet's response to one fixed batch).
3. **weighted_routing** — a mixed-capacity gateway (no processes):
   capacity-normalized least-outstanding must spread held work in
   proportion to capacity (a 4-unit upstream absorbs ~4× a 1-unit one).
4. **rolling_restart** — the 2×4 fleet restarts under live traffic;
   zero client errors and every successor keeps its predecessor's
   device overlay (placement label + chip count via
   ``checks.engine.mesh``).

Honesty: virtual chips TIME-SHARE the host's cores, so raw preds/s
cannot grow past the core count — ``host_caveat`` (structural, PR
10/11 convention) says so, and ``efficiency`` normalizes by
``chips_effective = min(chips, cores)`` on the CPU backend (= chips on
real accelerators, where the field becomes the honest per-chip claim).

Usage: python scripts/bench_fleet_chips.py [--quick]
       [--chips 1 2 4 8] [--out artifacts/fleet_chips.json]
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import socket
import sys
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from routest_tpu.serve.fleet.placement import (  # noqa: E402
    PLACEMENT_LABEL_ENV, slice_env)

FIXED_BATCH = 256      # rows in the oracle batch (deterministic body)


def _load_load_test():
    spec = importlib.util.spec_from_file_location(
        "load_test", os.path.join(REPO, "scripts", "load_test.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _post(base, path, payload, timeout=180.0):
    req = urllib.request.Request(
        f"{base}{path}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def _get(base, path, timeout=15.0):
    with urllib.request.urlopen(f"{base}{path}", timeout=timeout) as resp:
        return json.loads(resp.read())


def _fixed_batch_payload():
    # Deterministic body: the SAME rows go through every placement, so
    # responses are directly comparable to the single-replica oracle.
    return {
        "distance_m": [500.0 + 153.0 * i for i in range(FIXED_BATCH)],
        "weather": "Cloudy",
        "traffic": [("Low", "Medium", "High", "Jam")[i % 4]
                    for i in range(FIXED_BATCH)],
        "driver_age": [25.0 + (i % 30) for i in range(FIXED_BATCH)],
        "pickup_time": "2026-08-05T08:30:00",
    }


def boot_layout(layout, warm_batch: int):
    """Boot one real-worker fleet with per-replica device pinning:
    ``layout`` is a list of per-replica chip counts (virtual CPU
    devices; multi-chip slices serve mesh-sharded). → (supervisor,
    gateway, base_url, ports)."""
    from routest_tpu.core.config import FleetConfig
    from routest_tpu.serve.fleet.gateway import Gateway
    from routest_tpu.serve.fleet.supervisor import ReplicaSupervisor

    ports = [_free_port() for _ in layout]
    env = dict(os.environ)
    env.update({
        "ROUTEST_FORCE_CPU": "1",
        # The fastlane cache would serve the repeated oracle batch from
        # memory — this bench measures the DEVICE path per chip.
        "RTPU_FASTLANE_CACHE": "0",
        "ETA_MODEL_PATH": os.path.join(REPO, "artifacts",
                                       "eta_mlp.msgpack"),
    })
    sup = ReplicaSupervisor(ports, env=env, cwd=REPO,
                            probe_interval_s=0.5, backoff_base_s=0.2,
                            backoff_cap_s=2.0)
    # Pin each replica's slice by hand (the same overlays
    # plan_placement emits for a forced layout on this platform).
    next_id = 0
    for i, (r, k) in enumerate(zip(sup._replicas, layout)):
        ids = tuple(range(next_id, next_id + k))
        next_id += k
        label = f"s{i}:{k}chip"
        r.placement_env = slice_env("cpu", k, ids, label)
        r.chips, r.capacity, r.placement_label = k, float(k), label
    sup.start()
    if not sup.ready(timeout=600):
        sup.drain(timeout=10)
        raise RuntimeError(f"layout {layout}: workers never ready")
    for port in ports:   # warm every replica's device path directly
        base = f"http://127.0.0.1:{port}"
        _post(base, "/api/predict_eta_batch",
              {"distance_m": [1000.0] * warm_batch})
        _post(base, "/api/predict_eta_batch", _fixed_batch_payload())
    gw = Gateway([("127.0.0.1", p) for p in ports],
                 FleetConfig(hedge=False, eject_after=3, cooldown_s=1.0,
                             max_inflight=64, queue_depth=256),
                 supervisor=sup)
    for i, k in enumerate(layout):
        gw.set_topology(f"r{i}", chips=k)
    httpd = gw.serve("127.0.0.1", 0)
    return sup, gw, f"http://127.0.0.1:{httpd.server_address[1]}", ports


def replica_mesh(port: int) -> dict:
    health = _get(f"http://127.0.0.1:{port}", "/api/health")
    return ((health.get("checks") or {}).get("engine") or {}).get(
        "mesh") or {}


def run_curve(chips_list, lt, args, cores):
    rows = []
    oracle = None
    for k in chips_list:
        print(f"[bench_fleet_chips] === curve: {k} chip(s) ===",
              file=sys.stderr)
        sup, gw, base, ports = boot_layout([k], args.batch_size)
        try:
            mesh = replica_mesh(ports[0])
            if mesh.get("devices") != k:
                raise RuntimeError(
                    f"placement overlay failed: wanted {k} devices, "
                    f"replica reports {mesh}")
            t0 = time.time()
            batch, errs = lt.run_batch_load([base], args.batch_threads,
                                            args.batch_requests,
                                            args.batch_size)
            status, body = _post(base, "/api/predict_eta_batch",
                                 _fixed_batch_payload())
            fixed = body.get("eta_minutes_ml") or []
            row = {
                "chips": k,
                "preds_per_s": batch["preds_per_s"],
                "preds_per_s_per_chip": round(
                    (batch["preds_per_s"] or 0.0) / k, 1),
                "mesh": mesh,
                "sharded": bool(mesh.get("sharded")),
                "p50_ms": batch.get("p50_ms"),
                "p95_ms": batch.get("p95_ms"),
                "client_errors": len(errs) + (0 if status == 200 else 1),
                "wall_seconds": round(time.time() - t0, 1),
            }
            if k == 1:
                oracle = fixed
                row["oracle"] = "this row IS the single-replica oracle"
            rows.append((row, fixed))
            print(f"[bench_fleet_chips] {k} chip(s): "
                  f"{row['preds_per_s']} preds/s", file=sys.stderr)
        finally:
            gw.drain(timeout=10)
            sup.drain(timeout=20)
    base_rate = rows[0][0]["preds_per_s"] or 1.0
    out = []
    for row, fixed in rows:
        k = row["chips"]
        k_eff = min(k, cores)
        row["chips_effective"] = k_eff
        row["efficiency"] = round(
            (row["preds_per_s"] or 0.0) / (k_eff * base_rate), 3)
        # Projected = what this row would deliver if every virtual
        # chip were a real core at the MEASURED per-sharded-chip rate
        # (= measured preds/s exactly when chips_effective == chips,
        # i.e. on real hardware). The curve's monotone claim binds on
        # this, structurally, on any host.
        row["preds_per_s_projected"] = round(
            (row["preds_per_s"] or 0.0) * k / k_eff, 1)
        if oracle and row.get("oracle") is None:
            row["oracle_max_abs_diff"] = _max_abs_diff(fixed, oracle)
        out.append(row)
    return out, oracle


def _max_abs_diff(a, b) -> float:
    if not a or not b or len(a) != len(b):
        return float("inf")
    return round(max(abs(float(x) - float(y)) for x, y in zip(a, b)), 9)


def run_placements(layouts, oracle, lt, args):
    rows = []
    for layout in layouts:
        name = "+".join(str(k) for k in layout) if len(set(layout)) > 1 \
            else f"{len(layout)}x{layout[0]}"
        print(f"[bench_fleet_chips] === placement {name} ===",
              file=sys.stderr)
        sup, gw, base, ports = boot_layout(layout, args.batch_size)
        try:
            t0 = time.time()
            batch, errs = lt.run_batch_load(
                [base], args.batch_threads, args.batch_requests,
                args.batch_size)
            status, body = _post(base, "/api/predict_eta_batch",
                                 _fixed_batch_payload())
            fixed = body.get("eta_minutes_ml") or []
            snap = gw.snapshot()
            rows.append({
                "layout": name,
                "replicas": len(layout),
                "chips_total": sum(layout),
                "capacity_units": snap["fleet"]["capacity_units"],
                "preds_per_s": batch["preds_per_s"],
                "p95_ms": batch.get("p95_ms"),
                "client_errors": len(errs) + (0 if status == 200 else 1),
                "per_replica_requests": {
                    rid: r["requests"]
                    for rid, r in snap["replicas"].items()},
                "oracle_max_abs_diff": _max_abs_diff(fixed, oracle),
                "wall_seconds": round(time.time() - t0, 1),
            })
            print(f"[bench_fleet_chips] {name}: "
                  f"{rows[-1]['preds_per_s']} preds/s, oracle diff "
                  f"{rows[-1]['oracle_max_abs_diff']}", file=sys.stderr)
        finally:
            gw.drain(timeout=10)
            sup.drain(timeout=20)
    return rows


def run_weighted_routing(picks: int = 500) -> dict:
    """No processes: a gateway holding work must spread HELD
    outstanding in proportion to advertised capacity. 500 picks, none
    completed — a capacity-4 upstream should hold ~4× a capacity-1."""
    from routest_tpu.core.config import FleetConfig
    from routest_tpu.serve.fleet.gateway import Gateway

    capacities = [4.0, 2.0, 1.0, 1.0]
    gw = Gateway([("127.0.0.1", 10000 + i)
                  for i in range(len(capacities))],
                 FleetConfig(hedge=False))
    for i, cap in enumerate(capacities):
        gw.set_topology(f"r{i}", chips=int(cap), capacity=cap)
    for _ in range(picks):
        r = gw._pick()
        assert r is not None
    with gw._lock:
        held = {r.id: r.outstanding for r in gw.replicas}
    total_cap = sum(capacities)
    shares = {}
    ok = True
    for i, cap in enumerate(capacities):
        want = cap / total_cap
        got = held[f"r{i}"] / picks
        shares[f"r{i}"] = {"capacity": cap, "picks": held[f"r{i}"],
                           "share": round(got, 3),
                           "want_share": round(want, 3)}
        ok = ok and abs(got - want) <= 0.10
    return {"picks": picks, "shares": shares,
            "within_10pct_of_capacity": ok}


def run_rolling_restart(lt, args) -> dict:
    """The 2×4 fleet restarts under live single-row traffic: zero
    client errors, and each successor must report the SAME placement
    label + device count its predecessor owned (the overlay survives
    the rollout machinery)."""
    from routest_tpu.serve.fleet.rollout import rolling_restart

    sup, gw, base, ports = boot_layout([4, 4], args.batch_size)
    errors = []
    count = [0]
    stop = threading.Event()
    payload = {"summary": {"distance": 12_000}, "weather": "Sunny",
               "traffic": "Medium", "driver_age": 35,
               "pickup_time": "2026-08-05T08:30:00"}

    def pump():
        while not stop.is_set():
            try:
                status, _ = _post(base, "/api/predict_eta", payload,
                                  timeout=60)
                count[0] += 1
                if status >= 500:
                    errors.append(status)
            except Exception as e:
                errors.append(str(e)[:80])

    try:
        before = {f"r{i}": replica_mesh(p) for i, p in enumerate(ports)}
        threads = [threading.Thread(target=pump, daemon=True)
                   for _ in range(2)]
        for t in threads:
            t.start()
        time.sleep(1.0)
        out = rolling_restart(sup, gw, version="chips-bench-v2",
                              env={"RTPU_VERSION": "chips-bench-v2"},
                              max_unavailable=1, drain_timeout_s=10.0,
                              boot_timeout_s=600.0,
                              health_timeout_s=30.0)
        time.sleep(1.0)
        stop.set()
        for t in threads:
            t.join(timeout=90)
        after = {}
        with sup._lock:
            live = [(r.index, r.port, r.placement_label, r.chips)
                    for r in sup._replicas if not r.retired]
        for index, port, label, chips_n in live:
            after[f"r{index}"] = {"label": label, "chips": chips_n,
                                  "mesh": replica_mesh(port)}
        preserved = (
            sorted((v["label"], v["chips"]) for v in after.values())
            == sorted((m.get("placement"), m.get("devices"))
                      for m in before.values())
            and all(v["mesh"].get("devices") == v["chips"]
                    for v in after.values()))
        return {
            "restart_ok": bool(out.get("ok")),
            "replaced": len(out.get("replaced", [])),
            "requests_during": count[0],
            "client_errors": len(errors),
            "errors_sample": errors[:5],
            "overlay_before": {k: {"placement": m.get("placement"),
                                   "devices": m.get("devices")}
                               for k, m in before.items()},
            "overlay_after": {k: {"placement": v["label"],
                                  "devices": v["mesh"].get("devices")}
                              for k, v in after.items()},
            "overlay_preserved": bool(preserved),
        }
    finally:
        stop.set()
        gw.drain(timeout=10)
        sup.drain(timeout=20)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--chips", type=int, nargs="+",
                        default=[1, 2, 4, 8])
    parser.add_argument("--batch-size", type=int, default=2048,
                        help="OD pairs per predict_eta_batch request")
    parser.add_argument("--batch-requests", type=int, default=8,
                        help="batch requests per client thread")
    parser.add_argument("--batch-threads", type=int, default=4)
    parser.add_argument("--skip-restart", action="store_true")
    parser.add_argument("--quick", action="store_true")
    parser.add_argument("--out", default=os.path.join(
        REPO, "artifacts", "fleet_chips.json"))
    args = parser.parse_args()
    if args.quick:
        args.batch_requests, args.batch_threads = 3, 2
        args.batch_size = min(args.batch_size, 1024)

    lt = _load_load_test()
    cores = len(os.sched_getaffinity(0)) \
        if hasattr(os, "sched_getaffinity") else (os.cpu_count() or 1)
    # Every replica is booted with a forced-CPU slice overlay
    # (boot_layout), so the record's backend is the CPU whatever this
    # host holds — and the parent never initialises a JAX backend, which
    # on a chip host would take a chip from the processes it spawns.
    backend = "cpu"

    curve, oracle = run_curve(args.chips, lt, args, cores)
    max_chips = max(args.chips)
    layouts = [[1] * max_chips,
               [max_chips // 2] * 2 if max_chips >= 2 else [1],
               [max_chips]]
    placements = run_placements(layouts, oracle, lt, args)
    weighted = run_weighted_routing()
    restart = None if args.skip_restart else run_rolling_restart(lt, args)

    report = {
        "recorded_unix": int(time.time()),
        "host": {"cpu_count": cores, "backend": backend,
                 "multi_core": cores > 1},
        # Structural caveat (PR 10/11 convention; the ROADMAP
        # housekeeping item: NOT a free-text note) — None only on a
        # real accelerator backend.
        "host_caveat": (None if backend == "tpu" else
                        f"cpu-backend record on {cores} core(s): "
                        "virtual chips time-share the host, so raw "
                        "preds/s cannot grow past the core count; "
                        "'efficiency' normalizes by chips_effective = "
                        "min(chips, cores) and becomes the true "
                        "per-chip efficiency on real hardware — "
                        "re-record there (PERFORMANCE.md §8)"),
        "efficiency_basis": {
            "chips_effective": "min(chips, host cores) on cpu; chips "
                               "on real accelerators",
            "formula": "preds_per_s / (chips_effective * "
                       "preds_per_s[chips=1])",
        },
        "oracle": {"batch_rows": FIXED_BATCH,
                   "source": "chips=1 single-replica response to the "
                             "fixed deterministic batch"},
        "curve": curve,
        "placements": placements,
        "weighted_routing": weighted,
        "rolling_restart": restart,
    }
    out_dir = os.path.dirname(args.out)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps({k: report[k] for k in
                      ("host", "host_caveat", "curve", "placements",
                       "weighted_routing")}, indent=2))
    if restart is not None:
        print(json.dumps({"rolling_restart": {
            k: restart[k] for k in ("restart_ok", "client_errors",
                                    "overlay_preserved")}}, indent=2))
    print(f"[bench_fleet_chips] report → {args.out}", file=sys.stderr)


if __name__ == "__main__":
    main()
