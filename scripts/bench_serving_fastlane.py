"""Serving fast-lane bench: closed-loop load with the fast lane off/on.

The measurement of record for ISSUE 4's acceptance criteria. Boots ONE
real replica (the full WSGI app over a threaded werkzeug server) with
the fleet gateway in front — the exact production path client →
gateway → WSGI → fastlane → batcher → device — and drives a closed
loop of single-row ``/api/predict_eta`` requests through it in four
configurations:

  {fast lane OFF, fast lane ON} × {repeated-OD-pair, all-unique}

OFF is the PR-3 serving path exactly: no prediction cache, no
singleflight, fixed 2 ms flush window. ON adds the content-addressed
cache + singleflight (``serve/fastlane.py``) and the adaptive flush
window. The repeated workload draws every request from a small pool of
OD pairs (a dispatch dashboard refreshing the same routes — the
Clipper-motivating distribution); the all-unique workload never repeats
a feature row, so the cache can only add overhead — it is the
no-regression guard.

Per mode: client-side p50/p95 latency and preds/s, plus server-side
registry deltas (cache hit rate, coalesced rows, batcher fill ratio,
zero-copy flushes). Writes ``artifacts/serving_fastlane.json`` with
pass/fail against the acceptance gates (≥20% p95 cut OR ≥1.3×
throughput on repeated; no p95 regression beyond the guardband on
unique).

Usage: python scripts/bench_serving_fastlane.py [--quick]
       [--threads 4] [--seconds 4.0] [--pool 32]
       [--out artifacts/serving_fastlane.json]
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# Hermetic: this bench judges host-side cache/coalescing behaviour
# (hit rates, dispatch counts), and it must run identically in CI.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# The acceptance gates (ISSUE 4): EITHER of the repeated-workload gates
# must pass; the unique workload must stay inside the guardband.
P95_CUT_GATE = 0.20          # ≥20% p95 reduction, fast lane on vs off
THROUGHPUT_GATE = 1.30       # or ≥1.3× preds/s
UNIQUE_GUARDBAND = 1.15      # unique workload: p95_on ≤ 1.15 × p95_off


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _percentile(samples, p):
    if not samples:
        return None
    xs = sorted(samples)
    idx = min(len(xs) - 1, max(0, int(round(p * (len(xs) - 1)))))
    return xs[idx]


def _registry_totals():
    """Cumulative counters/histogram sums we diff around each run (the
    registry is process-wide; deltas isolate one mode's traffic)."""
    from routest_tpu.obs import get_registry

    snap = get_registry().snapshot()

    def total(name, field="value"):
        fam = snap.get(name)
        if not fam:
            return 0.0
        return sum(s.get(field, 0.0) or 0.0 for s in fam["series"])

    return {
        "hits": total("rtpu_cache_hits_total"),
        "misses": total("rtpu_cache_misses_total"),
        "coalesced": total("rtpu_cache_coalesced_total"),
        "rows": total("rtpu_batcher_rows_total"),
        "flushes": total("rtpu_batcher_flushes_total"),
        "zero_copy": total("rtpu_batcher_zero_copy_flushes_total"),
        "fill_sum": total("rtpu_batcher_fill_ratio", "sum"),
        "fill_count": total("rtpu_batcher_fill_ratio", "count"),
    }


def _make_stack(fastlane_on: bool, model_path: str):
    """One replica + gateway, fast lane configured per mode. Returns
    (gateway_base, shutdown_fn)."""
    import logging

    from werkzeug.serving import make_server

    from routest_tpu.core.config import Config, FleetConfig, ServeConfig

    # Per-request access-log lines are stderr writes on the hot path —
    # measurement pollution, not signal.
    logging.getLogger("werkzeug").setLevel(logging.ERROR)
    from routest_tpu.serve.app import create_app
    from routest_tpu.serve.fleet.gateway import Gateway
    from routest_tpu.serve.ml_service import EtaService

    serve_cfg = ServeConfig(
        fastlane_cache=fastlane_on,
        fastlane_singleflight=fastlane_on,
        adaptive_wait=fastlane_on,
    )
    eta = EtaService(serve_cfg, model_path=model_path)
    assert eta.available, eta.load_error
    app = create_app(Config(serve=serve_cfg), eta_service=eta)
    rep_port = _free_port()
    server = make_server("127.0.0.1", rep_port, app, threaded=True)
    rep_thread = threading.Thread(target=server.serve_forever, daemon=True)
    rep_thread.start()

    gw = Gateway([("127.0.0.1", rep_port)],
                 FleetConfig(max_inflight=128, queue_depth=256, hedge=False))
    gw_port = _free_port()
    httpd = gw.serve("127.0.0.1", gw_port)

    def shutdown():
        httpd.shutdown()
        httpd.server_close()
        server.shutdown()
        server.server_close()

    return f"http://127.0.0.1:{gw_port}", shutdown


def _payloads(workload: str, pool: int):
    """Request-body factory. ``repeated``: a fixed pool of OD pairs (the
    pickup_time is pinned so the encoded feature row is bit-identical
    per pool entry). ``unique``: a per-call novel distance, so no two
    feature rows ever match."""
    base_time = "2026-08-04T08:30:00"
    weathers = ("Sunny", "Rainy", "Cloudy")
    traffics = ("Low", "Medium", "High")
    if workload == "repeated":
        bodies = [json.dumps({
            "summary": {"distance": 2000.0 + 137.0 * i},
            "weather": weathers[i % 3], "traffic": traffics[(i // 3) % 3],
            "driver_age": 25 + (i % 20), "pickup_time": base_time,
        }).encode() for i in range(pool)]

        def make(thread_id: int, i: int) -> bytes:
            return bodies[(thread_id * 7919 + i) % pool]

        return make

    def make_unique(thread_id: int, i: int) -> bytes:
        return json.dumps({
            "summary": {"distance": 1000.0 + thread_id * 1e6 + i * 0.25},
            "weather": weathers[i % 3], "traffic": traffics[i % 3],
            "driver_age": 25 + (i % 20), "pickup_time": base_time,
        }).encode()

    return make_unique


def _drive(base: str, workload: str, pool: int, threads: int,
           seconds: float) -> dict:
    """Closed loop: each thread posts back-to-back until the clock runs
    out. Persistent keep-alive connections (the client cost must not
    mask the server-side win)."""
    import http.client
    from urllib.parse import urlsplit

    host, port = urlsplit(base).hostname, urlsplit(base).port
    make = _payloads(workload, pool)
    latencies = [[] for _ in range(threads)]
    errors = [0] * threads
    stop_at = [0.0]
    barrier = threading.Barrier(threads + 1)

    def worker(t: int) -> None:
        conn = http.client.HTTPConnection(host, port, timeout=30)
        barrier.wait()
        i = 0
        while time.monotonic() < stop_at[0]:
            body = make(t, i)
            t0 = time.perf_counter()
            try:
                conn.request("POST", "/api/predict_eta", body=body,
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                resp.read()
                ok = resp.status == 200
            except (http.client.HTTPException, OSError):
                conn.close()
                conn = http.client.HTTPConnection(host, port, timeout=30)
                ok = False
            if ok:
                latencies[t].append(time.perf_counter() - t0)
            else:
                errors[t] += 1
            i += 1
        conn.close()

    ths = [threading.Thread(target=worker, args=(t,)) for t in range(threads)]
    for th in ths:
        th.start()
    # Warmup outside the window: first requests pay route/bucket JIT.
    warm = _payloads(workload, pool)
    import urllib.request

    for i in range(8):
        req = urllib.request.Request(base + "/api/predict_eta",
                                     data=warm(99, i),
                                     headers={"Content-Type":
                                              "application/json"})
        try:
            urllib.request.urlopen(req, timeout=30).read()
        except OSError:
            pass
    before = _registry_totals()
    t_start = time.monotonic()
    stop_at[0] = t_start + seconds
    barrier.wait()
    for th in ths:
        th.join(timeout=seconds + 60)
    wall = time.monotonic() - t_start
    after = _registry_totals()
    lat = [x for per in latencies for x in per]
    delta = {k: after[k] - before[k] for k in after}
    lookups = delta["hits"] + delta["misses"] + delta["coalesced"]
    return {
        "requests": len(lat),
        "errors": sum(errors),
        "wall_s": round(wall, 3),
        "preds_per_sec": round(len(lat) / wall, 1),
        "p50_ms": round(1000 * _percentile(lat, 0.50), 3) if lat else None,
        "p95_ms": round(1000 * _percentile(lat, 0.95), 3) if lat else None,
        "p99_ms": round(1000 * _percentile(lat, 0.99), 3) if lat else None,
        "cache_hit_rate": round(delta["hits"] / lookups, 4) if lookups
        else None,
        "coalesced_rows": int(delta["coalesced"]),
        "device_rows": int(delta["rows"]),
        "device_flushes": int(delta["flushes"]),
        "zero_copy_flushes": int(delta["zero_copy"]),
        "fill_ratio_mean": round(delta["fill_sum"] / delta["fill_count"], 4)
        if delta["fill_count"] else None,
    }


def run(args) -> dict:
    import tempfile

    import jax

    from routest_tpu.core.dtypes import F32_POLICY
    from routest_tpu.models.eta_mlp import EtaMLP
    from routest_tpu.train.checkpoint import default_model_path, save_model

    model_path = default_model_path()
    tmp = None
    if not os.path.exists(model_path):
        # No trained artifact (fresh checkout/CI): a randomly
        # initialized trunk times identically — the bench measures the
        # serving path, not the weights.
        tmp = tempfile.mkdtemp(prefix="fastlane_bench_")
        model_path = os.path.join(tmp, "m.msgpack")
        model = EtaMLP(policy=F32_POLICY)
        save_model(model_path, model, model.init(jax.random.PRNGKey(0)))

    out: dict = {
        "bench": "serving_fastlane",
        "quick": bool(args.quick),
        "threads": args.threads,
        "seconds": args.seconds,
        "pool": args.pool,
        "topology": "client -> gateway -> replica (1 replica, in-process)",
        "host": {"cpu_count": os.cpu_count(),
                 "backend": "cpu"},
        "workloads": {},
    }
    for workload in ("repeated", "unique"):
        modes = {}
        for label, fastlane_on in (("off", False), ("on", True)):
            base, shutdown = _make_stack(fastlane_on, model_path)
            try:
                modes[label] = _drive(base, workload, args.pool,
                                      args.threads, args.seconds)
            finally:
                shutdown()
            print(f"fastlane bench: {workload}/{label}: {modes[label]}",
                  file=sys.stderr)
        off, on = modes["off"], modes["on"]
        summary = {
            "p95_cut": round(1.0 - on["p95_ms"] / off["p95_ms"], 4)
            if off["p95_ms"] else None,
            "throughput_ratio": round(
                on["preds_per_sec"] / off["preds_per_sec"], 4)
            if off["preds_per_sec"] else None,
        }
        if workload == "repeated":
            summary["pass"] = bool(
                (summary["p95_cut"] or 0) >= P95_CUT_GATE
                or (summary["throughput_ratio"] or 0) >= THROUGHPUT_GATE)
            summary["gate"] = (f"p95_cut>={P95_CUT_GATE} or "
                               f"throughput_ratio>={THROUGHPUT_GATE}")
        else:
            summary["pass"] = bool(
                on["p95_ms"] is not None and off["p95_ms"] is not None
                and on["p95_ms"] <= off["p95_ms"] * UNIQUE_GUARDBAND)
            summary["gate"] = f"p95_on <= {UNIQUE_GUARDBAND} * p95_off"
        out["workloads"][workload] = {"off": off, "on": on,
                                      "summary": summary}
    out["pass"] = all(w["summary"]["pass"] for w in out["workloads"].values())
    out["recorded_unix"] = int(time.time())
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="short windows for CI (the slow-marked "
                         "regression test uses this)")
    # Default 2: the win under test is latency-mode + cache on the
    # request path, which saturation queueing hides — on an N-core host
    # keep the closed loop just below the serving stack's capacity.
    ap.add_argument("--threads", type=int,
                    default=max(2, min(4, (os.cpu_count() or 1))))
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--pool", type=int, default=32,
                    help="distinct OD pairs in the repeated workload")
    ap.add_argument("--out", default=os.path.join(REPO, "artifacts",
                                                  "serving_fastlane.json"))
    args = ap.parse_args()
    if args.quick:
        args.seconds = min(args.seconds, 1.5)
        args.threads = min(args.threads, 2)
    rec = run(args)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=2)
        f.write("\n")
    print(json.dumps({k: rec[k] for k in ("bench", "pass")}
                     | {w: rec["workloads"][w]["summary"]
                        for w in rec["workloads"]}))


if __name__ == "__main__":
    main()
