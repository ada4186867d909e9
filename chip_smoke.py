#!/usr/bin/env python3
"""Chip smoke: the system's main path, once, on a real TPU.

``python chip_smoke.py`` drives train -> artifact -> server -> batched
device programs through the entry points a user would call, at the width
of the model the repo ships (``EtaMLP`` 13->256->256->128 with three
quantile heads, bf16, ``artifacts/eta_mlp.msgpack``; all six serving
buckets 8...4096), and compares what comes out with plain host
references written here: a NumPy float32 forward of the same parameters
for ETA, SciPy Dijkstra for road distances, a pure-Python greedy for the
VRP. It needs one TPU chip; with ``--chips 4`` it runs ONLY the paths
that exist across chips (the mesh-sharded server and a fleet of four
one-chip replicas) on a four-chip host.

One process holds a chip at a time, so this parent never imports JAX:
every phase is a child process, run one after another.

  device    JAX finds a TPU with the expected chip count (fails in
            seconds where there is none - before any server boots)
  serve     the real server (``python -m routest_tpu.serve``, default
            config, committed artifact, the README's real-network
            configuration) answers every device program it owns, at
            parity with the host references; health/metrics name the
            chip; the log shows no fallback event
  programs  outside HTTP, on the chip: the fused Pallas kernel compiled
            (bf16/f32/int8 x point/quantile heads) at every bucket and
            at 131,072 rows; the ROUTEST_FUSED=1 serving path; the
            partition-overlay router on an 8,192-node metro extract; a
            few ``fit`` steps at batch 8192 with save -> load ->
            identical predictions

Each phase prints one JSON object per line; any failed phase makes the
exit code non-zero. The LAST line of stdout on success is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.

Tolerances (|got - want| <= atol + rtol*|want|, the repo's own classes
from tests/test_ops_fused.py): float32 1e-4/1e-3, bfloat16 2e-2/0.5.
The served model computes in bfloat16. The kernel's int8-weight variant
is compared, at bfloat16 tolerance, with the forward of its own 8-bit
weights.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
PHASES = {1: ("device", "serve", "programs"),
          4: ("device", "mesh_serve", "fleet")}
# Seconds per phase; the whole run (compilation included) must end
# inside the 1200 s the chip check allows.
PHASE_TIMEOUT_S = {"device": 120, "serve": 480, "programs": 540,
                   "mesh_serve": 420, "fleet": 600}
TOTAL_BUDGET_S = 1140

TOL = {"float32": (1e-4, 1e-3), "bfloat16": (2e-2, 0.5)}
BUCKETS = (8, 64, 512, 1024, 2048, 4096)   # ServeConfig.batch_buckets
BIG_BATCH = 131072                         # one slice of the od-score cell
# Log events that mean a device path was quietly replaced by another
# (ISSUE 21 section 2): seeing one in a server's log fails the phase.
FALLBACK_EVENTS = (
    "aot_compile_unavailable", "fused_kernel_unavailable",
    "fused_kernel_ignored", "tp_serving_unavailable",
    "aot_mesh_incompatible", "bucket_warm_failed",
    "model_bootstrap_started", "osm_extract_unusable",
    "chip_peaks_unavailable", "predict_batch_failed",
    "replica_exited", "replica_unresponsive",
)
PICKUP = "2026-08-05T08:30:00"   # a Wednesday: weekday 2, hour 8


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


# ── parent: phase runner (no JAX here) ───────────────────────────────

def run_phase(name: str, args, timeout_s: float) -> dict:
    result_path = os.path.join(args.out, f"{name}.json")
    if os.path.exists(result_path):
        os.unlink(result_path)
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", name,
           "--chips", str(args.chips), "--seed", str(args.seed),
           "--out", args.out]
    t0 = time.time()
    # Own session: a timeout kills the phase's whole process tree
    # (servers, replicas), so nothing this script started outlives it.
    proc = subprocess.Popen(cmd, cwd=REPO, start_new_session=True)
    try:
        rc = proc.wait(timeout=timeout_s)
        reason = f"exit code {rc}"
    except subprocess.TimeoutExpired:
        rc, reason = 124, f"timed out after {timeout_s:.0f}s"
    finally:
        kill_group(proc.pid)
    try:
        with open(result_path) as f:
            result = json.load(f)
    except (OSError, ValueError):
        result = {"ok": False, "error": f"phase wrote no result ({reason})"}
    if rc != 0:
        result["ok"] = False
        result.setdefault("error", reason)
    result["wall_s"] = round(time.time() - t0, 1)
    return result


def kill_group(pgid: int) -> None:
    for sig in (signal.SIGTERM, signal.SIGKILL):
        try:
            os.killpg(pgid, sig)
        except (ProcessLookupError, PermissionError):
            return
        deadline = time.time() + 10
        while time.time() < deadline:
            try:
                os.killpg(pgid, 0)
            except (ProcessLookupError, PermissionError):
                return
            time.sleep(0.2)


def main() -> int:
    parser = argparse.ArgumentParser(
        description="Drive the serving, training and kernel paths once "
                    "on a TPU and check the answers against host "
                    "references. Needs one chip by default.")
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="1 (default): serve + programs on one chip. "
                             "4: ONLY the mesh-sharded server and the "
                             "four-replica fleet, on a four-chip host")
    parser.add_argument("--seed", type=int, default=0,
                        help="seed for request data, weights and training "
                             "data made at run time")
    parser.add_argument("--out", default=os.path.join(
        REPO, "chiprun_out", "chip_smoke"),
        help="directory for server logs, phase results and the trained "
             "smoke artifact (default: chiprun_out/chip_smoke)")
    parser.add_argument("--phase", help=argparse.SUPPRESS)
    args = parser.parse_args()
    args.out = os.path.abspath(args.out)
    os.makedirs(args.out, exist_ok=True)

    if args.phase:
        return run_child(args)

    missing = [p for p in ("routest_tpu",
                           os.path.join("artifacts", "eta_mlp.msgpack"))
               if not os.path.exists(os.path.join(REPO, p))]
    if missing:
        emit(ok=False, error=f"not a routest-tpu checkout: missing {missing}")
        return 2

    t_start = time.time()
    device = None
    for name in PHASES[args.chips]:
        left = TOTAL_BUDGET_S - (time.time() - t_start)
        result = run_phase(name, args, min(PHASE_TIMEOUT_S[name], left))
        emit(phase=name, **result)
        if not result.get("ok"):
            emit(ok=False, failed_phase=name, error=result.get("error"))
            return 1
        if name == "device":
            device = result["device"]
    emit(total_wall_s=round(time.time() - t_start, 1),
         phases=list(PHASES[args.chips]))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def run_child(args) -> int:
    phase = {"device": phase_device, "serve": phase_serve,
             "programs": phase_programs, "mesh_serve": phase_mesh_serve,
             "fleet": phase_fleet}[args.phase]
    result = {"ok": False}
    try:
        result.update(phase(args) or {})
        result["ok"] = True
    except SmokeFailure as e:
        result["error"] = str(e)
    except Exception as e:  # the phase boundary: report, then fail
        import traceback

        traceback.print_exc()
        result["error"] = f"{type(e).__name__}: {e}"
    with open(os.path.join(args.out, f"{args.phase}.json"), "w") as f:
        json.dump(result, f, default=str)
    return 0 if result["ok"] else 1


# ── phase: device ────────────────────────────────────────────────────

def phase_device(args) -> dict:
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    check(device["platform"] == "tpu",
          f"JAX found no TPU (platform {device['platform']!r})")
    check(device["count"] == args.chips,
          f"--chips {args.chips} but JAX sees {device['count']} devices")
    sys.path.insert(0, REPO)
    from routest_tpu.core.mesh import chip_peaks

    chip_peaks(device["kind"])   # an unknown kind raises
    return {"device": device, "jax": jax.__version__}


# ── host references (NumPy / SciPy / pure Python) ────────────────────

WEATHER = ("Cloudy", "Stormy", "Sunny", "Windy")
TRAFFIC = ("High", "Jam", "Low", "Medium")


def encode_rows(weather, traffic, pickup_iso, distance_m, driver_age):
    """Request fields -> (N, 12) float32 rows of the reference ABI
    (SURVEY.md Appendix B), written out here independently of the
    package's encoders."""
    import datetime as dt

    import numpy as np

    t = dt.datetime.fromisoformat(pickup_iso)
    n = len(distance_m)
    x = np.zeros((n, 12), np.float32)
    for i in range(n):
        if weather[i] in WEATHER:
            x[i, WEATHER.index(weather[i])] = 1.0
        if traffic[i] in TRAFFIC:
            x[i, 4 + TRAFFIC.index(traffic[i])] = 1.0
    x[:, 8], x[:, 9] = t.weekday(), t.hour
    x[:, 10] = np.asarray(distance_m, np.float32) / np.float32(1000.0)
    x[:, 11] = driver_age
    return x


def eta_reference(params, n_q: int, x, int8_weights: bool = False):
    """Plain NumPy float32 forward of ``EtaMLP`` on (N, 12) rows:
    (N,) minutes for a point model, (N, n_q) for a quantile model.

    ``int8_weights`` gives the reference for the kernel's int8 variant:
    the same forward over weights rounded to 8 bits per output column
    (symmetric, scale = max|column| / 127, as the variant's
    documentation states; the normalizer stays outside the weights).
    What is left between it and the kernel is bfloat16 arithmetic."""
    import numpy as np

    f32 = np.float32
    x = np.asarray(x, f32)
    mean = np.asarray(params["norm"]["mean"], f32)
    std = np.asarray(params["norm"]["std"], f32)
    dist = np.maximum(x[:, 10], 0).astype(f32)
    ws = [np.asarray(layer["w"], f32) for layer in params["layers"]]
    bs = [np.asarray(layer["b"], f32) for layer in params["layers"]]
    if int8_weights:
        for i, w in enumerate(ws):
            scale = np.abs(w).max(axis=0) / 127.0
            scale[scale < 1e-12] = 1.0
            ws[i] = (np.rint(w / scale) * scale).astype(f32)
    feats = np.concatenate([
        x[:, :8],
        np.eye(7, dtype=f32)[x[:, 8].astype(np.int64)],
        np.eye(24, dtype=f32)[x[:, 9].astype(np.int64)],
        ((dist - mean[10]) / std[10])[:, None],
        np.log1p(dist)[:, None],
        ((x[:, 11] - mean[11]) / std[11])[:, None]], axis=1).astype(f32)
    h = feats
    for w, b in zip(ws[:-1], bs[:-1]):
        z = h @ w + b
        h = (0.5 * z * (1 + np.tanh(np.sqrt(2 / np.pi)
                                    * (z + 0.044715 * z ** 3)))).astype(f32)
    out = h @ ws[-1] + bs[-1]
    sp = np.logaddexp(out, 0.0)
    if not n_q:
        return sp[:, 0] * dist + sp[:, 1]
    return (np.cumsum(sp[:, :n_q], axis=1) * dist[:, None]
            + np.cumsum(sp[:, n_q:2 * n_q], axis=1))


def close_to(got, want, dtype: str, what: str) -> float:
    """Assert parity in ``dtype``'s tolerance class; returns the worst
    error as a share of what the tolerance allows."""
    import numpy as np

    rtol, atol = TOL[dtype]
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    check(got.shape == want.shape, f"{what}: shape {got.shape} != "
                                   f"{want.shape}")
    check(bool(np.isfinite(got).all()), f"{what}: non-finite values")
    share = float((np.abs(got - want) / (atol + rtol * np.abs(want))).max())
    check(share <= 1.0, f"{what}: error is {share:.2f}x the {dtype} "
                        f"tolerance (rtol={rtol}, atol={atol})")
    return round(share, 4)


def dijkstra_reference(graph: dict, sources):
    """(S, N) shortest-path meters over the directed street graph."""
    import numpy as np
    import scipy.sparse as sp
    from scipy.sparse.csgraph import dijkstra

    n = len(graph["node_coords"])
    s = np.asarray(graph["senders"], np.int64)
    r = np.asarray(graph["receivers"], np.int64)
    w = np.asarray(graph["length_m"], np.float64)
    order = np.lexsort((w, r, s))          # shortest parallel edge first
    s, r, w = s[order], r[order], w[order]
    first = np.ones(len(s), bool)
    first[1:] = (s[1:] != s[:-1]) | (r[1:] != r[:-1])
    adj = sp.csr_matrix((w[first], (s[first], r[first])), shape=(n, n))
    return dijkstra(adj, directed=True, indices=np.asarray(sources, np.int64))


def greedy_reference(dist, demands, cap: float, maxd: float):
    """The documented greedy VRP in plain Python (same semantics as the
    oracle in tests/test_vrp.py): origin-sorted candidate scan, capacity
    + (leg + return <= max distance) acceptance, only the leg
    accumulates, multi-trip spill."""
    n = len(demands)
    unvisited = [i for i in range(n) if demands[i] <= cap
                 and dist[0][i + 1] + dist[i + 1][0] <= maxd]
    scan = sorted(range(n), key=lambda i: dist[0][i + 1])
    trips = []
    while unvisited:
        current, load, tdist, trip = 0, 0.0, 0.0, []
        for j in scan:
            node = j + 1
            if j in unvisited and load + demands[j] <= cap and \
                    tdist + dist[current][node] + dist[node][0] <= maxd:
                trip.append(j)
                load += demands[j]
                tdist += dist[current][node]
                current = node
        check(bool(trip), "greedy reference made no progress")
        unvisited = [j for j in unvisited if j not in trip]
        trips.append(trip)
    return trips


def tour_meters(dist, trips) -> float:
    total = 0.0
    for trip in trips:
        seq = [0] + [j + 1 for j in trip] + [0]
        total += sum(float(dist[a][b]) for a, b in zip(seq[:-1], seq[1:]))
    return total


def haversine_matrix(latlon):
    import numpy as np

    lat, lon = np.radians(np.asarray(latlon, np.float64)).T
    a = (np.sin((lat[:, None] - lat[None]) / 2) ** 2
         + np.cos(lat[:, None]) * np.cos(lat[None])
         * np.sin((lon[:, None] - lon[None]) / 2) ** 2)
    return 2 * 6371000.0 * np.arcsin(np.sqrt(a))


# ── HTTP + server process helpers ────────────────────────────────────

def http(base: str, path: str, body=None, timeout: float = 120.0):
    import urllib.request

    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        base + path, data=data,
        headers={"Content-Type": "application/json"} if data else {})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def clean_env() -> dict:
    """The environment a server child starts from: the caller's, minus
    every knob of this repo, so what boots is the DEFAULT configuration
    plus exactly what the phase sets."""
    return {k: v for k, v in os.environ.items()
            if not k.startswith(("RTPU_", "ROUTEST_", "ROAD_", "ETA_MODEL",
                                 "REDIS_URL", "SUPABASE_"))}


class Server:
    """A server (or fleet) process this phase owns: started from
    ``argv`` with its log under the output directory, and told to drain
    (SIGTERM) on exit. It stays in the phase's process group, which the
    parent kills once the phase ends - whatever a drain leaves behind
    goes then."""

    def __init__(self, argv, env: dict, log_path: str, url: str,
                 boot_timeout_s: float) -> None:
        self.url, self.log_path = url, log_path
        self._log = open(log_path, "w")
        self.proc = subprocess.Popen(argv, env=env, cwd=REPO,
                                     stdout=self._log, stderr=self._log)
        self.boot_s = self._wait_ready(boot_timeout_s)

    def _wait_ready(self, timeout_s: float) -> float:
        t0 = time.time()
        while time.time() - t0 < timeout_s:
            if self.proc.poll() is not None:
                break
            try:
                if http(self.url, "/api/ping", timeout=2).get("ok"):
                    return round(time.time() - t0, 1)
            except (OSError, ValueError):
                time.sleep(0.5)
        tail = self.log_tail()
        self.stop()
        raise SmokeFailure(f"{self.url} never answered /api/ping; "
                           f"log tail: {tail}")

    def log_tail(self, n: int = 12) -> str:
        self._log.flush()
        with open(self.log_path, errors="replace") as f:
            return " | ".join(line.strip()[-300:]
                              for line in f.readlines()[-n:])

    def records(self) -> list:
        """The JSON lines of the log, parsed."""
        self._log.flush()
        out = []
        with open(self.log_path, errors="replace") as f:
            for line in f:
                if line.startswith("{"):
                    try:
                        out.append(json.loads(line))
                    except ValueError:
                        continue
        return out

    def stop(self) -> None:
        self.proc.terminate()
        try:
            self.proc.wait(timeout=45)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait(timeout=15)
        self._log.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        self.stop()


def assert_no_fallback(server: Server) -> dict:
    """event name -> count over the server's log; none may be a
    fallback event."""
    events: dict = {}
    for record in server.records():
        name = record.get("event")
        events[name] = events.get(name, 0) + 1
    seen = {e: events[e] for e in FALLBACK_EVENTS if e in events}
    check(not seen, f"fallback events in {server.log_path}: {seen}")
    return events


def committed_artifact() -> tuple:
    """(path, sha256[:16], model, params) of the ETA artifact git holds."""
    import hashlib

    from routest_tpu.train.checkpoint import load_model

    path = os.path.join(REPO, "artifacts", "eta_mlp.msgpack")
    with open(path, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    model, params = load_model(path)
    return path, digest, model, params


# ── served ETA: requests, references, health ─────────────────────────

def eta_rows(rng, n: int) -> dict:
    """n distinct request rows (distinct distances, so neither the
    fast-lane cache nor singleflight can answer one from another)."""
    import numpy as np

    distance = np.round(rng.uniform(300.0, 60000.0, n), 1)
    return {
        "distance_m": distance.tolist(),
        "weather": [WEATHER[i] for i in rng.integers(0, 4, n)],
        "traffic": [TRAFFIC[i] for i in rng.integers(0, 4, n)],
        "driver_age": np.round(rng.uniform(18, 65, n), 0).tolist(),
        "pickup_time": PICKUP,
    }


def served_parity(got: dict, rows: dict, c, what: str) -> float:
    """Served p10 / median / p90 columns for ``rows`` against the NumPy
    forward of the committed parameters."""
    import numpy as np

    want = eta_reference(c.params, c.n_q, encode_rows(
        rows["weather"], rows["traffic"], PICKUP, rows["distance_m"],
        rows["driver_age"]))
    served = np.stack([got["eta_minutes_ml_p10"], got["eta_minutes_ml"],
                       got["eta_minutes_ml_p90"]], axis=1)
    return close_to(served, want, c.dtype, what)


def batch_parity(base: str, n: int, c, what: str) -> float:
    rows = eta_rows(c.rng, n)
    got = http(base, "/api/predict_eta_batch", rows)
    check(got["count"] == n, f"{what}: count {got['count']}")
    return served_parity(got, rows, c, what)


def check_served_eta(base: str, c) -> dict:
    """Single-row requests sent concurrently (the batcher coalesces)
    and one batch per bucket, every row against the NumPy forward."""
    import threading

    check(c.n_q == 3, f"expected the three-quantile artifact, n_q={c.n_q}")
    out: dict = {}
    rows = eta_rows(c.rng, 24)
    got = [None] * 24

    def one(i: int) -> None:
        got[i] = http(base, "/api/predict_eta", {
            "summary": {"distance": rows["distance_m"][i]},
            "weather": rows["weather"][i], "traffic": rows["traffic"][i],
            "pickup_time": PICKUP, "driver_age": rows["driver_age"][i]})

    threads = [threading.Thread(target=one, args=(i,)) for i in range(24)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    check(all(g is not None for g in got), "a predict_eta request failed")
    columns = {k: [g[k] for g in got] for k in (
        "eta_minutes_ml_p10", "eta_minutes_ml", "eta_minutes_ml_p90")}
    out["predict_eta_err"] = served_parity(columns, rows, c, "predict_eta")
    # (a gateway's metrics carry no batcher block: None there)
    out["coalesced_max_batch"] = http(base, "/api/metrics").get(
        "batcher", {}).get("max_batch_seen")

    out["batch_err"] = {n: batch_parity(base, n, c, f"predict_eta_batch[{n}]")
                        for n in BUCKETS}
    return out


def check_health(base: str, device: dict, digest: str,
                 want_buckets=BUCKETS) -> dict:
    """Health and metrics name the chip, the committed artifact and all
    AOT buckets, and every bucket's device program has run."""
    health = http(base, "/api/health")
    checks = health["checks"]
    mesh, model = checks["engine"]["mesh"], checks["model"]
    scoring = model["scoring"]
    check(mesh["platform"] == "tpu", f"health platform {mesh['platform']}")
    check(mesh["device_kind"] == device["kind"] ==
          checks["tpu"]["device_kind"], f"health device kind {mesh}")
    check(mesh["devices"] == device["count"], f"health devices {mesh}")
    check(model["status"] == "ok" and "error" not in model,
          f"model degraded: {model}")
    check(model["fingerprint"] == digest,
          f"served fingerprint {model['fingerprint']} is not the "
          f"committed artifact {digest}")
    check(scoring["aot"] and tuple(scoring["aot_buckets"]) == tuple(
        want_buckets), f"AOT buckets {scoring}")
    check("peak_tflops_bf16" in checks["tpu"],
          f"no peak row for this chip: {checks['tpu'].get('peaks_error')}")
    registry = http(base, "/api/metrics")["registry"]
    flushed = {int(s["labels"]["bucket"]): s["count"] for s in registry[
        "rtpu_batcher_device_compute_seconds"]["series"]}
    check(all(flushed.get(b, 0) >= 1 for b in want_buckets),
          f"buckets without a device flush: {flushed}")
    compile_s = sum(s["sum"] for s in registry[
        "rtpu_replica_aot_compile_seconds"]["series"])
    return {"kernel": scoring["kernel"], "dtype": scoring["dtype"],
            "aot_buckets": scoring["aot_buckets"],
            "aot_compile_s": round(compile_s, 2),
            "flushes_by_bucket": flushed,
            "max_batch_seen": checks["tpu"]["batcher"]["max_batch_seen"],
            "last_flush": checks["tpu"]["batcher"].get("last_flush"),
            "mesh": mesh}


def cache_entries() -> tuple:
    from routest_tpu.core.cache import COMPILE_CACHE_DIR

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or COMPILE_CACHE_DIR
    try:
        n = sum(1 for e in os.listdir(cache) if not e.endswith("-atime"))
    except OSError:
        n = 0
    return cache, n


def device_from(args) -> dict:
    with open(os.path.join(args.out, "device.json")) as f:
        return json.load(f)["device"]


def client_setup(args):
    """What every client phase starts from. A client holds no chip: its
    own JAX (pulled in by the package's loaders) is pinned to the host
    CPU, and the environment its SERVER gets is captured before that
    pin. Also: the device the ``device`` phase saw, the committed
    artifact (digest, parameters, head count, compute dtype) and the
    seeded generator for request data."""
    import types

    env = clean_env()
    env["RTPU_RECORDER_DIR"] = os.path.join(args.out, "postmortems")
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, REPO)
    import numpy as np

    _, digest, model, params = committed_artifact()
    return types.SimpleNamespace(
        env=env, device=device_from(args), digest=digest, params=params,
        n_q=len(model.quantiles),
        dtype=np.dtype(model.policy.compute_dtype).name,
        rng=np.random.default_rng(args.seed))


# ── phase: serve ─────────────────────────────────────────────────────

def phase_serve(args) -> dict:
    c = client_setup(args)
    port = free_port()
    osm = os.path.join("artifacts", "manila_arterials.osm.gz")
    c.env.update({
        "PORT": str(port),
        # README "serve over the curated REAL Metro Manila arterial
        # network" configuration.
        "ROAD_GRAPH_OSM": osm,
        "ROAD_GNN_PATH": os.path.join("artifacts", "road_gnn_manila.msgpack"),
    })
    _, entries_before = cache_entries()
    out: dict = {"tolerance": {c.dtype: TOL[c.dtype]}}
    with Server([sys.executable, "-m", "routest_tpu.serve"], c.env,
                os.path.join(args.out, "serve.log"),
                f"http://127.0.0.1:{port}", boot_timeout_s=300) as srv:
        out["boot_s"] = srv.boot_s
        out["eta"] = check_served_eta(srv.url, c)
        out["routing"] = check_served_routing(srv.url, osm, c.rng)
        out["dispatch"] = check_served_dispatch(srv.url, c.rng)
        out["health"] = check_health(srv.url, c.device, c.digest)
        road = http(srv.url, "/api/health")["checks"]["engine"]["road_router"]
        check(road["nodes"] == out["routing"]["graph_nodes"],
              f"server routes on {road['nodes']} nodes, the extract has "
              f"{out['routing']['graph_nodes']}")
        out["events"] = assert_no_fallback(srv)
    cache, entries = cache_entries()
    out["compile_cache"] = {"dir": cache, "entries": entries,
                            "new_entries": entries - entries_before}
    return out


def check_served_routing(base: str, osm: str, rng) -> dict:
    """optimize_route (plain / refine / road_graph), its batch form and
    the road matrix against the greedy and Dijkstra references."""
    import numpy as np

    from routest_tpu.data import geo
    from routest_tpu.data.osm import load_osm

    graph = load_osm(os.path.join(REPO, osm))
    coords = np.asarray(graph["node_coords"], np.float32)
    # Waypoints ON graph nodes: snapping is then exact and a served road
    # distance is the shortest-path distance itself.
    nodes = rng.choice(len(coords), 11, replace=False)
    points = [{"lat": float(coords[i, 0]), "lon": float(coords[i, 1]),
               "payload": 1.0} for i in nodes]
    demands, cap, maxd = [1.0] * 10, 4.0, 100000.0
    body = {"source_point": points[0], "destination_points": points[1:],
            "driver_details": {"vehicle_type": "car", "vehicle_capacity": cap,
                               "maximum_distance": maxd, "driver_age": 35},
            "use_ml_eta": True, "pickup_time": PICKUP}

    factor = geo.PROFILE_ROAD_FACTOR[geo.profile_for_vehicle("car")]
    gc = haversine_matrix(coords[nodes]) * factor
    road = dijkstra_reference(graph, nodes)[:, nodes]
    check(bool(np.isfinite(road).all()), "reference: unreachable waypoint")

    def served_trips(feature: dict) -> list:
        """Trips back out of a response: ``optimized_order`` cut at
        every segment that arrives at the origin."""
        props = feature["properties"]
        order, trips, trip = list(props["optimized_order"]), [], []
        for seg in props["segments"]:
            if "origin" in seg["steps"][-1]["instruction"]:
                trips.append(trip)
                trip = []
            else:
                trip.append(order.pop(0))
        check(not order and not trip and len(trips) ==
              props["summary"]["trips"], "could not rebuild trips")
        return trips

    def check_greedy(feature: dict, dist, what: str) -> None:
        want = greedy_reference(dist, demands, cap, maxd)
        check(served_trips(feature) == want,
              f"{what}: trips {served_trips(feature)} != greedy {want}")
        got = feature["properties"]["summary"]["distance"]
        check(abs(got - tour_meters(dist, want)) <= 1e-3 * got,
              f"{what}: distance {got} vs {tour_meters(dist, want)}")

    def check_refined(feature: dict, dist, what: str) -> None:
        trips = served_trips(feature)
        check(sorted(j for t in trips for j in t) == list(range(10)),
              f"{what}: not a partition of the stops: {trips}")
        for t in trips:
            check(len(t) <= cap, f"{what}: trip over capacity: {t}")
            check(tour_meters(dist, [t]) <= maxd * (1 + 1e-6),
                  f"{what}: trip over the distance limit: {t}")
        got = feature["properties"]["summary"]["distance"]
        check(abs(got - tour_meters(dist, trips)) <= 1e-3 * got,
              f"{what}: distance {got} vs {tour_meters(dist, trips)}")
        greedy = tour_meters(dist, greedy_reference(dist, demands, cap, maxd))
        check(got <= greedy * (1 + 1e-6),
              f"{what}: refined {got} m worse than greedy {greedy} m")

    plain = http(base, "/api/optimize_route", body)
    check_greedy(plain, gc, "optimize_route")
    check("eta_minutes_ml" in plain["properties"], "no ML ETA on the route")
    check_refined(http(base, "/api/optimize_route", dict(body, refine=True)),
                  gc, "optimize_route refine")
    on_road = http(base, "/api/optimize_route", dict(body, road_graph=True))
    check(on_road["properties"]["leg_cost_model"] == "gnn",
          f"road legs priced by {on_road['properties']['leg_cost_model']}, "
          f"not the configured GNN")
    check_greedy(on_road, road, "optimize_route road_graph")

    batch = http(base, "/api/optimize_route_batch", {"items": [
        body, dict(body, refine=True), dict(body, road_graph=True)]})
    check(batch["count"] == 3, f"optimize_route_batch: {batch.get('count')}")
    check_greedy(batch["items"][0], gc, "optimize_route_batch[plain]")
    check_refined(batch["items"][1], gc, "optimize_route_batch[refine]")
    check_greedy(batch["items"][2], road, "optimize_route_batch[road]")

    matrix = http(base, "/api/matrix", {"points": points, "road_graph": True,
                                        "pickup_time": PICKUP})
    got = np.asarray(matrix["distances_m"], np.float64)
    err = float(np.abs(got - road).max() / road.max())
    check(np.allclose(got, road, rtol=1e-4, atol=1.0),
          f"road matrix differs from Dijkstra (max err {err:.2e} of range)")
    durations = np.asarray(matrix["durations_s"], np.float64)
    check(bool(np.isfinite(durations).all() and (durations >= 0).all()),
          "road matrix durations not finite")
    check(matrix["leg_cost_model"] == "gnn", "matrix not priced by the GNN")
    return {"graph_nodes": len(coords), "road_matrix_max_rel_err": err,
            "leg_cost_model": matrix["leg_cost_model"],
            "greedy_trips": greedy_reference(road, demands, cap, maxd)}


def check_served_dispatch(base: str, rng) -> dict:
    """One /api/dispatch solve (enabled by default, docs/API.md) in
    matrix mode: window-free and feasible, the dispatch program IS the
    reference greedy."""
    import numpy as np

    n = 12
    pts = rng.random((n + 1, 2)) * 3600.0
    matrix = np.round(np.sqrt(((pts[:, None] - pts[None]) ** 2).sum(-1)), 3)
    demands = rng.integers(1, 4, n).astype(float)
    state = http(base, "/api/dispatch")
    check(state["enabled"], "dispatch subsystem is disabled")
    got = http(base, "/api/dispatch", {
        "matrix": matrix.tolist(), "demands": demands.tolist(),
        "capacity": 8.0, "max_distance": 9000.0})
    want = greedy_reference(matrix, demands, 8.0, 9000.0)
    plan = got["plan"]
    check(plan["trips"] == want, f"dispatch trips {plan['trips']} != {want}")
    check(not plan["spill_lane"] and not plan["unroutable"]
          and plan["penalty"] == 0.0, f"dispatch spilled: {plan}")
    return {"mode": got["mode"], "n_trips": plan["n_trips"]}


# ── phase: programs (one process, on the chip, outside HTTP) ─────────

def phase_programs(args) -> dict:
    sys.path.insert(0, REPO)
    import jax
    import numpy as np

    check(jax.devices()[0].platform == "tpu", "programs phase needs the TPU")
    from routest_tpu import native
    from routest_tpu.core.cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    rng = np.random.default_rng(args.seed)
    out: dict = {"native_data_plane": "cpp" if native.available()
                 else "python", "compile_cache_dir": cache_dir}
    for name, fn in (("fused_kernel", programs_fused_kernel),
                     ("fused_serving", programs_fused_serving),
                     ("overlay_router", programs_overlay_router),
                     ("train", programs_train)):
        t0 = time.time()
        out[name] = fn(args, rng)
        out[name]["wall_s"] = round(time.time() - t0, 1)
        emit(phase="programs", step=name, **out[name])
    out["compile_cache_entries"] = cache_entries()[1]
    return out


def random_rows(rng, n: int):
    import numpy as np

    x = np.zeros((n, 12), np.float32)
    x[np.arange(n), rng.integers(0, 4, n)] = 1.0
    x[np.arange(n), 4 + rng.integers(0, 4, n)] = 1.0
    x[:, 8], x[:, 9] = rng.integers(0, 7, n), rng.integers(0, 24, n)
    x[:, 10] = rng.uniform(0.3, 60.0, n)
    x[:, 11] = rng.uniform(18, 65, n)
    return x


def programs_fused_kernel(args, rng) -> dict:
    """(a) The fused kernel COMPILED (interpret=False) in every dtype
    variant and head shape, at each serving bucket and at 131,072 rows,
    against the NumPy forward — and the XLA path with it."""
    import jax
    import numpy as np

    from routest_tpu.ops import fused_eta_forward, pack_eta_params
    from routest_tpu.train.checkpoint import load_model

    x_all = random_rows(rng, BIG_BATCH)
    worst: dict = {}
    for artifact in ("eta_mlp.msgpack", "eta_mlp_point.msgpack"):
        model, params = load_model(os.path.join(REPO, "artifacts", artifact))
        n_q = len(model.quantiles)
        want_all = eta_reference(params, n_q, x_all)
        want_int8 = eta_reference(params, n_q, x_all, int8_weights=True)
        xla = jax.jit(model.apply_quantiles_xla if n_q else model.apply_xla)
        chosen = jax.jit(model.apply_quantiles if n_q else model.apply)
        # variant -> (reference, tolerance class). The int8 variant is
        # held to ITS weights' forward at bfloat16 tolerance: 8-bit
        # weights move the shipped point model by up to 7.6% from the
        # float32 answer (CPU interpreter, 32,768 rows), which is the
        # variant's price and not an error of the chip.
        for variant, (want, tol) in {
                "bfloat16": (want_all, "bfloat16"),
                "float32": (want_all, "float32"),
                "int8": (want_int8, "bfloat16")}.items():
            packed = jax.device_put(
                pack_eta_params(model, params, dtype=variant))
            for batch in BUCKETS + (BIG_BATCH,):
                x = jax.device_put(x_all[:batch])
                got = fused_eta_forward(packed, x, n_q=n_q, interpret=False)
                key = f"n_q={n_q} {variant}"
                worst[key] = max(worst.get(key, 0.0), close_to(
                    got, want[:batch], tol,
                    f"fused kernel {key} batch={batch}"))
        hlo = fused_eta_forward.lower(
            packed, x, n_q=n_q, interpret=False).compile().as_text()
        check("tpu_custom_call" in hlo,
              "fused kernel did not lower to a Mosaic custom call")
        worst[f"n_q={n_q} xla bfloat16"] = close_to(
            xla(jax.device_put(params), x_all[:BUCKETS[-1]]),
            want_all[:BUCKETS[-1]], "bfloat16", f"xla path n_q={n_q}")
        # what EtaMLP itself runs at 131,072 rows on this chip: the
        # kernel, chosen by eta_path with no switch set
        on_chip = jax.device_put(params)
        check("eta_mlp_fused" in chosen.lower(on_chip, x).compile().as_text(),
              f"EtaMLP did not choose the fused kernel at {BIG_BATCH} rows")
        worst[f"n_q={n_q} chosen bfloat16"] = close_to(
            chosen(on_chip, x), want_all, "bfloat16",
            f"the chosen path n_q={n_q} batch={BIG_BATCH}")
    return {"error_share_of_tolerance": worst,
            "shapes": list(BUCKETS + (BIG_BATCH,))}


def programs_fused_serving(args, rng) -> dict:
    """(b) ROUTEST_FUSED=1: EtaService serves the kernel (not XLA)."""
    import numpy as np

    from routest_tpu.core.config import ServeConfig
    from routest_tpu.serve.ml_service import EtaService

    path, _, model, params = committed_artifact()
    os.environ["ROUTEST_FUSED"] = "1"
    try:
        svc = EtaService(ServeConfig(), model_path=path)
    finally:
        del os.environ["ROUTEST_FUSED"]
    check(svc.kernel == "pallas_fused" and svc.available,
          f"ROUTEST_FUSED=1 served kernel={svc.kernel!r} "
          f"error={svc.load_error!r}")
    n_q = len(model.quantiles)
    errs = {}
    for n in (8, 4096):
        x = random_rows(rng, n)
        got = svc.predict_batch(x)
        errs[n] = close_to(got, eta_reference(params, n_q, x),
                           svc.kernel_dtype, f"fused EtaService[{n}]")
    return {"kernel": svc.kernel, "dtype": svc.kernel_dtype,
            "error_share_of_tolerance": errs}


def programs_overlay_router(args, rng) -> dict:
    """(c) The partition-overlay router above its 4,096-node threshold:
    point-to-point solves on metro_8192 against SciPy Dijkstra. The
    overlay is BUILT here (no warm overlay cache is read)."""
    import numpy as np

    os.environ["ROUTEST_HIER_CACHE"] = "0"
    from routest_tpu.data.osm import load_osm
    from routest_tpu.optimize.road_router import RoadRouter

    t0 = time.time()
    router = RoadRouter(graph=load_osm(os.path.join(
        REPO, "artifacts", "metro_8192.osm.gz")),
        use_gnn=False, use_transformer=False)
    build_s = round(time.time() - t0, 1)
    check(router._hier is not None, "overlay did not engage at 8,192 nodes")
    pairs = rng.integers(0, router.n_nodes, (6, 2))
    want = dijkstra_reference(router.graph_dict(), pairs[:, 0])
    worst = 0.0
    for (src, dst), want_row in zip(pairs, want):
        dist, _pred = router.shortest(np.asarray([src, dst]))
        got = float(dist[0, dst])
        check(np.isfinite(want_row[dst]), "reference: unreachable pair")
        rel = abs(got - want_row[dst]) / max(want_row[dst], 1.0)
        check(rel <= 1e-4, f"overlay {src}->{dst}: {got} m vs Dijkstra "
                           f"{want_row[dst]} m")
        worst = max(worst, rel)
    info = router.solver_info
    return {"nodes": router.n_nodes, "solver": info["solver"],
            "levels": info["overlay"]["n_levels"],
            "hub_labels": info["hub_labels"],
            "aot_buckets": info["aot_buckets"],
            "aot_compile_s": info["aot_compile_s"],
            "build_and_compile_s": build_s, "pairs": len(pairs),
            "max_rel_err": worst}


def programs_train(args, rng) -> dict:
    """(d) A few optimizer steps of ``fit`` at default width and
    TrainConfig.batch_size, then save -> load -> identical predictions.
    Written under the output directory only."""
    import jax
    import numpy as np

    from routest_tpu.core.config import TrainConfig
    from routest_tpu.data.features import batch_from_mapping
    from routest_tpu.data.synthetic import generate_dataset, train_eval_split
    from routest_tpu.models.eta_mlp import EtaMLP
    from routest_tpu.train.checkpoint import load_model, save_model
    from routest_tpu.train.loop import fit

    cfg = TrainConfig(epochs=4, seed=args.seed)
    train, ev = train_eval_split(
        generate_dataset(5 * cfg.batch_size, seed=args.seed), eval_frac=0.2)
    model = EtaMLP(quantiles=(0.1, 0.5, 0.9))
    result = fit(model, train, ev, cfg)
    losses = result.train_losses
    check(bool(np.isfinite(losses).all()), f"loss not finite: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    path = os.path.join(args.out, "smoke_eta_mlp.msgpack")
    save_model(path, model, result.state.params)
    loaded_model, loaded_params = load_model(path)
    x = np.asarray(batch_from_mapping(ev), np.float32)[:4096]
    before = np.asarray(jax.jit(model.apply_quantiles)(
        result.state.params, x))
    after = np.asarray(jax.jit(loaded_model.apply_quantiles)(
        jax.device_put(loaded_params), x))
    check(np.array_equal(before, after), "reloaded artifact predicts "
                                         "differently")
    check(bool(np.isfinite(after).all()), "non-finite predictions")
    return {"batch_size": cfg.batch_size,
            "steps": cfg.epochs * -(-len(train["eta_minutes"])
                                    // cfg.batch_size),
            "epoch_losses": [round(float(v), 4) for v in losses],
            "eval_rmse_min": round(result.eval_rmse, 3), "artifact": path}


# ── phase: mesh_serve (--chips 4) ────────────────────────────────────

def phase_mesh_serve(args) -> dict:
    """The default server on a four-chip host auto-builds a data=4 mesh:
    same ETA requests, same references, and the 4,096-row flush must be
    split over four devices."""
    c = client_setup(args)
    port = free_port()
    c.env["PORT"] = str(port)
    out: dict = {}
    with Server([sys.executable, "-m", "routest_tpu.serve"], c.env,
                os.path.join(args.out, "mesh_serve.log"),
                f"http://127.0.0.1:{port}", boot_timeout_s=300) as srv:
        out["boot_s"] = srv.boot_s
        out["eta"] = check_served_eta(srv.url, c)
        # check_served_eta's last request is the 4,096-row batch.
        out["health"] = health = check_health(srv.url, c.device, c.digest)
        mesh, last = health["mesh"], health["last_flush"]
        check(mesh["sharded"] and mesh["axis_shapes"].get("data") == 4,
              f"no data=4 mesh: {mesh}")
        check(last == {"bucket": 4096, "devices": mesh["device_ids"],
                       "rows_per_device": 1024}
              and len(set(mesh["device_ids"])) == 4,
              f"4,096-row flush not split over four devices: {last}")
        events = assert_no_fallback(srv)
        check(events.get("mesh_serving") == 1, "server logged no mesh_serving")
    return out


# ── phase: fleet (--chips 4) ─────────────────────────────────────────

def phase_fleet(args) -> dict:
    """``python -m routest_tpu.serve.fleet`` plans four one-chip
    replicas behind the gateway: parity through the gateway and on
    every replica, each replica alone on its own chip."""
    c = client_setup(args)
    gateway_port, base_port = free_port(), 5301
    c.env.update({"RTPU_FLEET_REPLICAS": "4",
                  "RTPU_FLEET_PLACEMENT": "replica",
                  "RTPU_GATEWAY_PORT": str(gateway_port),
                  "RTPU_FLEET_BASE_PORT": str(base_port)})
    out: dict = {"replicas": []}
    with Server([sys.executable, "-m", "routest_tpu.serve.fleet"], c.env,
                os.path.join(args.out, "fleet.log"),
                f"http://127.0.0.1:{gateway_port}",
                boot_timeout_s=480) as fleet:
        out["boot_s"] = fleet.boot_s
        out["eta_via_gateway"] = check_served_eta(fleet.url, c)
        seen_chips = []
        for i in range(4):
            base = f"http://127.0.0.1:{base_port + i}"
            err = batch_parity(base, 512, c, f"replica r{i}")
            checks = http(base, "/api/health")["checks"]
            mesh = checks["engine"]["mesh"]
            check(mesh["platform"] == "tpu" and mesh["devices"] == 1
                  and mesh["device_kind"] == c.device["kind"],
                  f"replica r{i} does not hold one chip: {mesh}")
            check(checks["model"]["fingerprint"] == c.digest
                  and checks["model"]["status"] == "ok",
                  f"replica r{i} model: {checks['model']}")
            seen_chips.append(mesh.get("visible_chips"))
            out["replicas"].append({"replica": f"r{i}", "mesh": mesh,
                                    "err": err})
        # Four processes are alive on TPUs at once and a chip admits one
        # process, so four distinct masks are four distinct chips.
        check(None not in seen_chips and len(set(seen_chips)) == 4,
              f"replicas do not each own a chip: {seen_chips}")
        assert_no_fallback(fleet)
        plan = [r for r in fleet.records() if r.get("event") ==
                "placement_plan"]
        check(len(plan) == 1 and plan[0]["platform"] == "tpu"
              and plan[0]["layout"] == "4x1", f"placement plan: {plan}")
        out["placement"] = {k: plan[0][k] for k in
                            ("platform", "chips", "layout", "source")}
    return out


if __name__ == "__main__":
    sys.exit(main())
