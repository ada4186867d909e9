"""Incident-correlation bench: every page names its suspect change.

The ISSUE-20 acceptance bar for the change ledger + suspect ranker
(docs/OBSERVABILITY.md "Change ledger & incident correlation"): three
injected incidents, each flowing through the REAL pipeline — state
changes recorded into the process ChangeLedger, a page edge fired by
the real machinery, the flight recorder ranking suspects into the
bundle's ``suspects.json`` — with the injected cause ranked #1:

- ``bad_deploy`` — a broken version (stub worker serving 500s) rolled
  out through the canary state machine over a real multi-process stub
  fleet; the ``canary_error_rate`` rollback bundle must rank the
  rollout's own ``rollout.phase`` transition first, matched on the
  offending version, above the live-flip noise recorded beside it.
- ``jammed_customize`` — a chaos-jammed metric customize cycle
  (``live.customize:error=1.0``) driven through the real
  ``MetricCustomizer`` → a real ``SloEngine`` burn-rate page; the
  suspect must be the jam (``live.customize_failed`` / ``chaos.*``),
  never a legitimate pre-jam flip.
- ``region_kill`` — a geo-front ``kill_region`` over two stub regions;
  a reachability SLO pages naming the dead region, and ``region.kill``
  must rank first matched on the region label.

Plus ``clean_window``: ≥20 legitimate metric flips (real customize
cycles) and ≥2 verified model swaps (real ``EtaService`` golden-batch
gate) under a healthy ticking SLO engine — zero pages, zero false
attributions.

Each scenario installs a PRIVATE ledger + recorder (swap-and-restore,
same discipline as ``tests/test_ledger.py``), so the artifact shows
exactly the events that scenario produced.

Usage: python scripts/bench_incidents.py [--quick]
       [--scenarios bad_deploy jammed_customize region_kill
        clean_window]
       [--out artifacts/incidents.json]
"""

from __future__ import annotations

import argparse
import http.server
import json
import os
import shutil
import socket
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


# ── stub workers (same harness as tests/test_rollout.py) ─────────────

_STUB_WORKER = """
import http.server, json, os
VERSION = os.environ.get("RTPU_VERSION") or None
FAIL = os.environ.get("STUB_FAIL") == "1"
class H(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    def log_message(self, *a):
        pass
    def _send(self, code, payload):
        b = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(b)))
        self.end_headers()
        self.wfile.write(b)
    def do_GET(self):
        bare = self.path.split("?", 1)[0]
        if bare == "/api/health":
            self._send(200, {"checks": {"model": {
                "status": "ok", "generation": 1,
                "fingerprint": "stub-" + (VERSION or "none")}},
                "status": "ok"})
        elif bare == "/api/version":
            self._send(200, {"version_label": VERSION,
                             "build": {"version": "stub"},
                             "model": {"generation": 1,
                                       "fingerprint":
                                       "stub-" + (VERSION or "none")}})
        else:
            self._send(200, {"ok": True, "version": VERSION})
    def do_POST(self):
        n = int(self.headers.get("Content-Length") or 0)
        self.rfile.read(n)
        if FAIL:
            self._send(500, {"error": "stub failure", "version": VERSION})
        else:
            self._send(200, {"eta_minutes_ml": 1.0, "version": VERSION})
srv = http.server.ThreadingHTTPServer(("127.0.0.1", int(os.environ["PORT"])), H)
srv.daemon_threads = True
srv.serve_forever()
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _post(base, path, payload, timeout=15.0):
    req = urllib.request.Request(
        f"{base}{path}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


class _Pump:
    """Background client pumping the gateway so the canary comparison
    has traffic to judge."""

    def __init__(self, base, interval_s=0.005):
        self.base = base
        self.interval_s = interval_s
        self.statuses = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        while not self._stop.is_set():
            try:
                status, _ = _post(self.base, "/api/predict_eta", {},
                                  timeout=10)
                self.statuses.append(status)
            except Exception:
                pass
            time.sleep(self.interval_s)

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=10)


# ── per-scenario obs install (swap-and-restore) ──────────────────────

class _Obs:
    """A private ChangeLedger + FlightRecorder installed process-wide
    for one scenario, restored on exit."""

    def __init__(self, workdir: str, name: str) -> None:
        from routest_tpu.core.config import LedgerConfig, RecorderConfig
        from routest_tpu.obs.ledger import (ChangeLedger,
                                            configure_change_ledger)
        from routest_tpu.obs.recorder import (FlightRecorder,
                                              configure_recorder)
        from routest_tpu.obs.registry import MetricsRegistry

        self._configure_ledger = configure_change_ledger
        self._configure_recorder = configure_recorder
        self.dir = os.path.join(workdir, name)
        self.ledger = ChangeLedger(
            config=LedgerConfig(enabled=True, capacity=512,
                                window_s=900.0, max_suspects=5,
                                publish=False, channel="rtpu.changes",
                                incidents_kept=64, region=""),
            registry=MetricsRegistry())
        self.recorder = FlightRecorder(RecorderConfig(
            dir=self.dir, min_interval_s=0.0, followup_s=0.0))
        self.recorder.register_change_ledger(self.ledger)

    def __enter__(self):
        self._prev_ledger = self._configure_ledger(self.ledger)
        self._configure_recorder(self.recorder)
        return self

    def __exit__(self, *exc):
        self._configure_ledger(self._prev_ledger)
        self._configure_recorder(None)

    def incident(self, reason: str):
        """Newest incident with ``reason`` → (incident, suspects from
        the bundle's suspects.json) or (None, [])."""
        incs = [i for i in self.recorder.incidents_snapshot()
                if i.get("reason") == reason]
        if not incs:
            return None, []
        inc = incs[-1]
        path = os.path.join(self.dir, inc["bundle"], "suspects.json")
        try:
            with open(path) as f:
                return inc, json.load(f)["suspects"]
        except OSError:
            return inc, []


def _thin_suspects(suspects, n=3):
    return [{"kind": s["event"]["kind"], "score": s["score"],
             "matched": s["matched"], "mismatched": s["mismatched"],
             "age_s": s["age_s"],
             "labels": {k: s["event"][k]
                        for k in ("replica", "version", "region",
                                  "bucket") if s["event"].get(k)}}
            for s in suspects[:n]]


def _flip_noise(count: int) -> None:
    """Legitimate fleet-wide flips recorded beside the incident — the
    ranker must keep them below the true cause."""
    from routest_tpu.obs.ledger import record_change

    for i in range(count):
        record_change("live.flip", detail={"epoch": 1000 + i,
                                           "obs_edges": 12})


# ── a minimal real customize loop (jam + clean-window scenarios) ─────

class _TinyRouter:
    """The slice of the router surface MetricCustomizer touches:
    ``edge_time_s`` + ``install_live_metric``. The live.flip ledger
    record comes from the REAL customizer path; only the metric
    install is stubbed (the full path is proven in
    tests/test_live_traffic.py and bench_live_traffic.py)."""

    def __init__(self, n_edges: int = 16) -> None:
        import numpy as np

        self._base = np.full(n_edges, 5.0, dtype=np.float32)
        self.installs = 0

    def edge_time_s(self, hour):
        return self._base

    def install_live_metric(self, metric, epoch, route=True):
        self.installs += 1
        return {"epoch": epoch}


def _customizer():
    import numpy as np

    from routest_tpu.live.customize import MetricCustomizer
    from routest_tpu.live.state import CongestionState

    state = CongestionState(np.full(16, 5.0, dtype=np.float32),
                            half_life_s=30, stale_s=600)
    return MetricCustomizer(_TinyRouter(), state, interval_s=1,
                            min_obs_edges=0)


def _engine(target: float = 0.99):
    """A real SloEngine with tight windows so the bench ticks through
    a synthetic clock instead of sleeping."""
    from routest_tpu.core.config import SloConfig
    from routest_tpu.obs.registry import MetricsRegistry
    from routest_tpu.obs.slo import SloEngine

    return SloEngine(SloConfig(tick_s=1.0, fast_window_s=10.0,
                               slow_window_s=30.0, page_burn=2.0,
                               warn_burn=1.0), component="bench",
                     metrics_registry=MetricsRegistry())


# ── scenario: bad deploy via rollout ─────────────────────────────────

def scenario_bad_deploy(args, workdir: str) -> dict:
    """A version serving 500s canaries out through the real rollout
    state machine; the canary_error_rate rollback bundle must open
    with the rollout's own phase transition as suspect #1."""
    from routest_tpu.core.config import FleetConfig, RolloutConfig
    from routest_tpu.serve.fleet.gateway import Gateway
    from routest_tpu.serve.fleet.rollout import RolloutController
    from routest_tpu.serve.fleet.supervisor import ReplicaSupervisor

    with _Obs(workdir, "bad_deploy") as obs:
        ports = [_free_port() for _ in range(2)]
        sup = ReplicaSupervisor(
            ports, command=lambda p: [sys.executable, "-c", _STUB_WORKER],
            probe_interval_s=0.15, backoff_base_s=0.2, backoff_cap_s=1.0)
        sup.start()
        if not sup.ready(timeout=30):
            sup.drain(timeout=10)
            raise RuntimeError("stub fleet never became ready")
        gw = Gateway([("127.0.0.1", p) for p in ports],
                     FleetConfig(hedge=False), supervisor=sup)
        httpd = gw.serve("127.0.0.1", 0)
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        try:
            _flip_noise(3)
            ctl = RolloutController(sup, gw, RolloutConfig(
                canary_fraction=0.25, canary_replicas=1, bake_s=30.0,
                tick_s=0.1, max_unavailable=1, min_canary_requests=5,
                max_error_rate=0.05, max_error_ratio=3.0,
                latency_threshold_ms=1500.0,
                max_latency_regression=0.25, crash_restarts=2,
                boot_timeout_s=20.0, health_timeout_s=5.0,
                drain_timeout_s=5.0))
            with _Pump(base, interval_s=0.002):
                assert ctl.start("v2-err", env={
                    "RTPU_VERSION": "v2-err", "STUB_FAIL": "1"})
                final = ctl.wait(timeout=90)
            inc, suspects = obs.incident("rollout_rollback")
            rollback = next((h for h in ctl.snapshot()["history"]
                             if h.get("event") == "rollback"), None)
            top = suspects[0] if suspects else None
            out = {
                "final_state": final,
                "rollback_trigger": (rollback or {}).get("trigger"),
                "ledger": obs.ledger.snapshot()["kinds"],
                "page_scope": (inc or {}).get("detail"),
                "suspects": _thin_suspects(suspects),
            }
            out["checks"] = {
                "rolled_back": final == "rolled_back",
                "paged_with_suspects": bool(inc and suspects),
                "true_cause_ranked_first": bool(
                    top and top["event"]["kind"] == "rollout.phase"),
                "offending_version_matched": bool(
                    top and top["event"].get("version") == "v2-err"
                    and "version" in top["matched"]),
                "noise_below_cause": bool(
                    top and top["event"]["kind"] != "live.flip"),
            }
            out["pass"] = all(out["checks"].values())
            return out
        finally:
            gw.drain(timeout=5)
            sup.drain(timeout=10)


# ── scenario: chaos-jammed customize cycle ───────────────────────────

def scenario_jammed_customize(args, workdir: str) -> dict:
    """Healthy customize cycles, then chaos jams the refresh point;
    the cycle-availability SLO burns into a real page whose bundle
    must blame the jam, not the legitimate flips before it."""
    from routest_tpu import chaos
    from routest_tpu.obs.slo import SloObjective

    with _Obs(workdir, "jammed_customize") as obs:
        cust = _customizer()
        cycles = {"total": 0, "bad": 0}
        engine = _engine()
        engine.add_objective(SloObjective(
            "availability:customize", "availability", 0.99,
            lambda: (cycles["total"], cycles["bad"]),
            detail={"surface": "live.customize"}))
        engine.on_page.append(obs.recorder.on_slo_page)
        now = 1000.0
        # Healthy window first: real flips, burn stays zero.
        for _ in range(args.clean_ticks):
            cycles["total"] += 1
            if not cust.run_once(now=now)["flipped"]:
                cycles["bad"] += 1
            engine.tick(now=now)
            now += 1.0
        flips_before = cust.flips
        paged_clean = bool(obs.recorder.incidents_snapshot())
        # Jam: every cycle now dies at the chaos point (recorded as
        # chaos.arm + chaos.fire + live.customize_failed).
        chaos.configure(chaos.ChaosEngine(
            spec="live.customize:error=1.0", seed=args.seed))
        try:
            ticks_to_page = None
            for i in range(60):
                cycles["total"] += 1
                if not cust.run_once(now=now)["flipped"]:
                    cycles["bad"] += 1
                engine.tick(now=now)
                now += 1.0
                if obs.recorder.incidents_snapshot():
                    ticks_to_page = i + 1
                    break
        finally:
            chaos.configure(None)
        inc, suspects = obs.incident("slo_page")
        top = suspects[0] if suspects else None
        jam_kinds = {"live.customize_failed", "chaos.fire", "chaos.arm"}
        out = {
            "clean_flips": flips_before,
            "ticks_to_page": ticks_to_page,
            "ledger": obs.ledger.snapshot()["kinds"],
            "page_scope": (inc or {}).get("detail"),
            "suspects": _thin_suspects(suspects),
        }
        out["checks"] = {
            "clean_window_quiet": not paged_clean and flips_before > 0,
            "paged_with_suspects": bool(inc and suspects),
            "true_cause_ranked_first": bool(
                top and top["event"]["kind"] in jam_kinds),
            "no_flip_blamed": bool(
                top and top["event"]["kind"] != "live.flip"),
        }
        out["pass"] = all(out["checks"].values())
        return out


# ── scenario: region kill at the geo-front ───────────────────────────

class _StubRegion:
    """One region as the front's health poll sees it: /up + /api/live."""

    def __init__(self) -> None:
        class H(http.server.BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def do_GET(self):
                body = json.dumps({"ok": True, "enabled": False}).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self.httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.httpd.daemon_threads = True
        self.base = f"http://127.0.0.1:{self.httpd.server_address[1]}"
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def stop(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()


def scenario_region_kill(args, workdir: str) -> dict:
    """kill_region("east") on a two-region geo-front; a reachability
    SLO pages naming the dead region and region.kill must rank first,
    matched on the region label, above fleet-wide flip noise."""
    from routest_tpu.obs.slo import SloObjective
    from routest_tpu.serve.fleet.geofront import GeoFront, RegionHandle

    with _Obs(workdir, "region_kill") as obs:
        east, west = _StubRegion(), _StubRegion()
        front = GeoFront([RegionHandle("east", east.base,
                                       kill=east.stop),
                          RegionHandle("west", west.base)])
        front.serve("127.0.0.1", 0)
        try:
            polls = {"total": 0, "bad": 0}

            def sample():
                regions = front.snapshot()["regions"]
                polls["total"] += len(regions)
                polls["bad"] += sum(1 for st in regions.values()
                                    if not st["up"])
                return polls["total"], polls["bad"]

            engine = _engine()
            engine.add_objective(SloObjective(
                "reachability:regions", "availability", 0.99, sample,
                detail={"surface": "geofront health"}))

            def page(name, detail):
                down = [n for n, st in
                        front.snapshot()["regions"].items()
                        if not st["up"]]
                obs.recorder.on_slo_page(name, {
                    **detail, "dead_region": ",".join(down) or None})

            engine.on_page.append(page)
            now = 1000.0
            for _ in range(args.clean_ticks):
                engine.tick(now=now)
                now += 1.0
            paged_clean = bool(obs.recorder.incidents_snapshot())
            _flip_noise(5)
            front.kill_region("east")
            ticks_to_page = None
            for i in range(60):
                engine.tick(now=now)
                now += 1.0
                if obs.recorder.incidents_snapshot():
                    ticks_to_page = i + 1
                    break
            inc, suspects = obs.incident("slo_page")
            top = suspects[0] if suspects else None
            out = {
                "ticks_to_page": ticks_to_page,
                "ledger": obs.ledger.snapshot()["kinds"],
                "page_scope": (inc or {}).get("detail"),
                "suspects": _thin_suspects(suspects),
            }
            out["checks"] = {
                "clean_window_quiet": not paged_clean,
                "paged_with_suspects": bool(inc and suspects),
                "dead_region_named": bool(
                    inc and (inc.get("detail") or {}).get("dead_region")
                    == "east"),
                "true_cause_ranked_first": bool(
                    top and top["event"]["kind"] == "region.kill"),
                "region_matched": bool(
                    top and top["event"].get("region") == "east"
                    and "region" in top["matched"]),
            }
            out["pass"] = all(out["checks"].values())
            return out
        finally:
            front.drain(timeout=5)
            west.stop()


# ── scenario: clean window — zero pages, zero false attributions ─────

def scenario_clean_window(args, workdir: str) -> dict:
    """≥20 legitimate metric flips (real customize cycles) and ≥2
    verified model swaps (real EtaService golden-batch gate) under a
    healthy ticking SLO engine: the ledger fills, nothing pages, and
    no incident attributes anything."""
    import jax

    from routest_tpu.core.config import ServeConfig
    from routest_tpu.core.dtypes import F32_POLICY
    from routest_tpu.models.eta_mlp import EtaMLP
    from routest_tpu.obs.slo import SloObjective
    from routest_tpu.serve.ml_service import EtaService
    from routest_tpu.train.checkpoint import save_model

    with _Obs(workdir, "clean_window") as obs:
        # Real verified swaps: each perturbed artifact passes the
        # golden-batch gate and records model.swap from the accept path.
        model = EtaMLP(hidden=(8,), policy=F32_POLICY)
        params = model.init(jax.random.PRNGKey(args.seed))
        path = os.path.join(workdir, "clean_model.msgpack")
        save_model(path, model, params)
        svc = EtaService(ServeConfig(), model_path=path)
        if not svc.available:
            raise RuntimeError("EtaService failed to load the model")
        swaps = 0
        for k in range(1, 3):
            close = jax.tree_util.tree_map(
                lambda x: x * (1.0 + 1e-4 * k), params)
            save_model(path, model, close)
            st = os.stat(path)
            os.utime(path, ns=(st.st_atime_ns,
                               st.st_mtime_ns + 1_000_000 * k))
            if svc.reload_if_changed():
                swaps += 1
        # Real flips under a healthy SLO tick.
        cust = _customizer()
        cycles = {"total": 0, "bad": 0}
        engine = _engine()
        engine.add_objective(SloObjective(
            "availability:customize", "availability", 0.99,
            lambda: (cycles["total"], cycles["bad"]),
            detail={"surface": "live.customize"}))
        engine.on_page.append(obs.recorder.on_slo_page)
        now = 1000.0
        for _ in range(max(args.clean_flips, 20)):
            cycles["total"] += 1
            if not cust.run_once(now=now)["flipped"]:
                cycles["bad"] += 1
            engine.tick(now=now)
            now += 1.0
        kinds = obs.ledger.snapshot()["kinds"]
        incidents = obs.recorder.incidents_snapshot()
        out = {
            "flips": kinds.get("live.flip", 0),
            "verified_swaps": kinds.get("model.swap", 0),
            "ledger": kinds,
            "incidents": len(incidents),
        }
        out["checks"] = {
            "enough_flips": out["flips"] >= 20,
            "enough_swaps": swaps >= 2
            and out["verified_swaps"] >= 2,
            "zero_pages": len(incidents) == 0,
            "zero_false_attributions": all(
                not i.get("suspects") for i in incidents),
        }
        out["pass"] = all(out["checks"].values())
        return out


SCENARIOS = {
    "bad_deploy": scenario_bad_deploy,
    "jammed_customize": scenario_jammed_customize,
    "region_kill": scenario_region_kill,
    "clean_window": scenario_clean_window,
}


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--quick", action="store_true")
    parser.add_argument("--seed", type=int, default=42)
    parser.add_argument("--scenarios", nargs="*", default=None,
                        choices=sorted(SCENARIOS))
    parser.add_argument("--out", default=os.path.join(
        REPO, "artifacts", "incidents.json"))
    args = parser.parse_args()
    args.clean_ticks = 8 if args.quick else 15
    args.clean_flips = 20 if args.quick else 30

    os.environ.setdefault("ROUTEST_FORCE_CPU", "1")
    from routest_tpu.core.cache import enable_compile_cache

    enable_compile_cache()
    from routest_tpu.utils.logging import get_logger

    log = get_logger("routest_tpu.bench_incidents")
    t0 = time.time()
    workdir = tempfile.mkdtemp(prefix="incidents-")
    results = {}
    try:
        plan = args.scenarios or list(SCENARIOS)
        for i, name in enumerate(plan):
            print(f"[{i + 1}/{len(plan)}] scenario {name}…", flush=True)
            t = time.perf_counter()
            try:
                results[name] = SCENARIOS[name](args, workdir)
            except Exception as e:
                results[name] = {"error": f"{type(e).__name__}: {e}",
                                 "pass": False}
                log.error("incidents_scenario_failed", scenario=name,
                          error=f"{type(e).__name__}: {e}")
            results[name]["wall_s"] = round(time.perf_counter() - t, 1)
            print(f"  {name}: "
                  f"{'PASS' if results[name].get('pass') else 'FAIL'} "
                  f"({results[name]['wall_s']}s)", flush=True)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    try:
        n_cpus = len(os.sched_getaffinity(0))
    except AttributeError:
        n_cpus = os.cpu_count() or 1
    record = {
        "generated_unix": int(t0),
        "host": {"cpus": n_cpus, "platform": sys.platform},
        # Structural caveats (skip reasons are fields, never prose in a
        # note): attribution is a pure function of the ledger + page
        # scope, so the checks are host-independent; only wall-seconds
        # (rollout convergence, ticks-to-page) are time-shared numbers.
        "host_caveat": (
            f"cpu record on {n_cpus} core(s): rollout and page "
            "latencies are time-shared-host numbers; judge the "
            "structural checks (true cause ranked #1, matched labels, "
            "quiet clean window), which are host-independent"
            if n_cpus <= 2 else None),
        "skipped": None,
        "config": {"seed": args.seed, "quick": args.quick,
                   "clean_ticks": args.clean_ticks,
                   "clean_flips": args.clean_flips},
        "scenarios": results,
        "all_pass": all(r.get("pass") for r in results.values()),
    }
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2, default=str)
    log.info("incidents_written", path=args.out,
             all_pass=record["all_pass"])
    print(json.dumps(record, indent=2, default=str))
    if not record["all_pass"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
