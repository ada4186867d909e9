"""Blackbox prober core, hermetic: verdict state machine, oracle
re-derivation across metric-epoch flips, fan-out skew detection over
stub replicas, correctness-page bundle embedding, probe-rate backoff
under a down fleet, and the tag-and-exclude plumbing (probe traffic
must never burn user SLO budget)."""

import http.server
import json
import os
import socket
import threading
import time

import numpy as np
import pytest

from routest_tpu.core.config import ProberConfig, RecorderConfig
from routest_tpu.obs.prober import (DIVERGENT, PASS, SKEW, UNREACHABLE,
                                    BlackboxProber, SubgraphOracle,
                                    eta_columns, eta_divergence,
                                    golden_probe_body)
from routest_tpu.obs.recorder import FlightRecorder
from routest_tpu.obs.registry import get_registry

pytestmark = pytest.mark.filterwarnings("ignore::ResourceWarning")


# ── stub replica: a controllable, *correct-by-construction* server ───
# Chain graph 0↔1↔2, edge metric scales with the stub's epoch; the
# stub answers route/matrix probes from its own metric (like a real
# replica, served ≡ dijkstra(exported metric)), so the oracle agrees
# unless a bias/skew knob says otherwise.

_SENDERS = [0, 1, 1, 2]
_RECEIVERS = [1, 2, 0, 1]


def _metric(epoch):
    return [10.0 * epoch, 20.0 * epoch, 10.0 * epoch, 20.0 * epoch]


def _route_s(srv):
    return 30.0 * srv.epoch + srv.route_bias


class _StubHandler(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, *a):
        pass

    def _send(self, code, payload):
        data = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def do_GET(self):
        srv = self.server
        if srv.dead:
            return self._send(500, {"error": "injected"})
        path = self.path
        if path.startswith("/api/version"):
            return self._send(200, {"model": {
                "fingerprint": srv.fingerprint,
                "generation": srv.generation}})
        if path.startswith("/api/live"):
            payload = {"enabled": srv.live_enabled, "epoch": srv.epoch}
            if "metric=1" in path and srv.live_enabled:
                payload["edge_time_s"] = _metric(srv.epoch)
            return self._send(200, payload)
        if path.startswith("/api/debug/probe_subgraph"):
            return self._send(200, {
                "nodes": 3, "edges": 4,
                "senders": _SENDERS, "receivers": _RECEIVERS,
                "snapped": [0, 2], "snap_m": [0.0, 0.0]})
        return self._send(200, {"ok": True})

    def do_POST(self):
        srv = self.server
        n = int(self.headers.get("Content-Length") or 0)
        body = json.loads(self.rfile.read(n) or b"{}")
        if srv.dead:
            return self._send(500, {"error": "injected"})
        path = self.path
        if path.startswith("/api/predict_eta_batch"):
            dist = body.get("distance_m") or []
            eta = [d / 1000.0 + srv.skew for d in dist]
            return self._send(200, {
                "count": len(dist),
                "eta_minutes_ml": [round(v, 4) for v in eta],
                "eta_minutes_ml_p10": [round(v - 1.0, 4) for v in eta],
                "eta_minutes_ml_p90": [round(v + 1.0, 4) for v in eta]})
        if path.startswith("/api/request_route"):
            return self._send(200, {"properties": {"summary": {
                "duration": _route_s(srv), "distance": 900.0}}})
        if path.startswith("/api/matrix"):
            d = _route_s(srv)
            return self._send(200, {"durations_s": [[0.0, d], [d, 0.0]]})
        if path.startswith("/api/dispatch"):
            # Correct-by-construction: solve the probe's own matrix
            # with the host oracle (srv.dispatch_skew perturbs the
            # costs the solve sees — the wrong-plan fault).
            from routest_tpu.dispatch import plan_cost
            from routest_tpu.optimize.vrp import solve_host_dispatch
            m = np.asarray(body["matrix"], np.float32)
            solved = m
            if srv.dispatch_skew:
                rng = np.random.default_rng(0)
                solved = m * (1.0 + srv.dispatch_skew
                              * rng.random(m.shape).astype(np.float32))
            plan = solve_host_dispatch(
                solved, np.asarray(body["demands"], np.float32),
                body["capacity"], body["max_distance"])
            return self._send(200, {
                "mode": "matrix", "plan": plan,
                "cost": round(float(plan_cost(m, plan)), 3)})
        return self._send(200, {"ok": True})


def _start_stub():
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _StubHandler)
    srv.daemon_threads = True
    srv.dead = False
    srv.skew = 0.0
    srv.route_bias = 0.0
    srv.fingerprint = "fp-a"
    srv.generation = 1
    srv.dispatch_skew = 0.0
    srv.epoch = 1
    srv.live_enabled = True
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


def _base(srv):
    return f"http://127.0.0.1:{srv.server_address[1]}"


def _mk_prober(tmp_path, stubs, gateway=None, **cfg_kw):
    cfg_kw.setdefault("enabled", True)
    cfg_kw.setdefault("eta_tolerance", 5.0)
    cfg_kw.setdefault("timeout_s", 5.0)
    cfg = ProberConfig(**cfg_kw)
    recorder = FlightRecorder(RecorderConfig(
        dir=str(tmp_path / "pm"), min_interval_s=0.0))
    targets = [(f"r{i}", _base(s)) for i, s in enumerate(stubs)]
    return BlackboxProber(
        cfg, gateway_base=_base(gateway or stubs[0]),
        targets_fn=lambda: targets, recorder=recorder), recorder


def _counter(probe, verdict):
    m = get_registry().get("rtpu_probe_checks_total")
    if m is None:
        return 0.0
    for key, child in m.items():
        if key == (probe, verdict):
            return child.value
    return 0.0


# ── verdict state machine ────────────────────────────────────────────


def test_golden_and_fanout_pass_and_repin(tmp_path):
    stubs = [_start_stub(), _start_stub()]
    prober, _rec = _mk_prober(tmp_path, stubs)
    assert prober.probe_round() == {"golden": PASS, "fanout": PASS,
                                    "dispatch": PASS}
    # Within-tolerance movement (a verified swap's shift) re-pins:
    for s in stubs:
        s.skew = 2.0
        s.fingerprint = "fp-b"
    assert prober.probe_round()["golden"] == PASS
    assert np.isclose(
        prober._pins["golden"]["eta_minutes_ml"][0],
        0.5 + 2.0)  # ratcheted to the new answers


def test_fanout_divergence_names_the_faulty_replica(tmp_path):
    good, bad = _start_stub(), _start_stub()
    prober, _rec = _mk_prober(tmp_path, [good, bad], gateway=good)
    assert prober.probe_round()["fanout"] == PASS  # arms the pin
    bad.skew = 50.0                                # ≫ tolerance 5
    verdicts = prober.probe_round()
    assert verdicts["golden"] == PASS              # gateway path clean
    assert verdicts["fanout"] == DIVERGENT
    ev = prober._state["fanout"]
    assert ev["replicas"] == ["r1"]
    assert ev["divergence"] > 5.0
    assert ev["served"]["r1"] is not None
    assert "expected" in ev


def test_unreachable_verdict_and_500_is_unreachable(tmp_path):
    stub = _start_stub()
    prober, _rec = _mk_prober(tmp_path, [stub])
    assert prober.probe_round()["golden"] == PASS
    stub.dead = True
    verdicts = prober.probe_round()
    assert verdicts["golden"] == UNREACHABLE
    assert verdicts["fanout"] == UNREACHABLE


# ── oracle re-derivation across metric-epoch flips ───────────────────


def _route_prober(tmp_path, stubs, **kw):
    return _mk_prober(tmp_path, stubs,
                      routes="14.5,121.0|14.6,121.1", **kw)


def test_route_oracle_rederives_on_epoch_flip_no_false_verdict(tmp_path):
    stub = _start_stub()
    prober, _rec = _route_prober(tmp_path, [stub])
    before = _counter("route", PASS)
    v = prober.probe_round()
    assert v["route"] == PASS and v["matrix"] == PASS
    assert prober.oracle.armed
    assert list(prober.oracle._by_epoch) == [1]
    # A legitimate metric flip: the metric doubles, the served answer
    # moves with it — the oracle re-derives instead of diverging.
    stub.epoch = 2
    v = prober.probe_round()
    assert v["route"] == PASS and v["matrix"] == PASS
    assert 2 in prober.oracle._by_epoch
    assert _counter("route", PASS) == before + 2
    assert _counter("route", DIVERGENT) == 0


def test_route_divergence_detected_within_epoch(tmp_path):
    stub = _start_stub()
    prober, _rec = _route_prober(tmp_path, [stub])
    assert prober.probe_round()["route"] == PASS
    stub.route_bias = 10.0     # served 40 s vs oracle 30 s at epoch 1
    v = prober.probe_round()
    assert v["route"] == DIVERGENT
    ev = prober._state["route"]
    assert ev["divergence"] > prober.config.route_tolerance_rel
    assert ev["oracle_epoch"] == 1
    assert ev["served"] == pytest.approx(40.0)
    assert ev["oracle"] == pytest.approx(30.0)


def test_oracle_candidates_cover_previous_epoch(tmp_path):
    """A probe answered by a replica one flip behind compares against
    the PREVIOUS epoch's oracle — a propagating flip is not a page."""
    stub = _start_stub()
    prober, _rec = _route_prober(tmp_path, [stub])
    assert prober.probe_round()["route"] == PASS
    stub.epoch = 2
    assert prober.probe_round()["route"] == PASS
    # Replica falls back to serving the OLD metric's answer while its
    # /api/live already reports the new epoch (mid-flip race).
    stub.route_bias = 30.0 * 1 - 30.0 * 2   # served = epoch-1 answer
    assert prober.probe_round()["route"] == PASS


def test_pinned_mode_without_road_graph(tmp_path):
    """No subgraph export (live off / no router): route probes degrade
    to pinned self-consistency, re-armed on epoch flips."""
    stub = _start_stub()
    stub.live_enabled = False
    prober, _rec = _route_prober(tmp_path, [stub])
    prober.oracle = None       # simulate arm failure
    assert prober.probe_round()["route"] == PASS   # arms the pin
    assert prober.probe_round()["route"] == PASS
    stub.route_bias = 10.0
    assert prober.probe_round()["route"] == DIVERGENT


# ── fan-out skew detection ───────────────────────────────────────────


def test_epoch_skew_needs_gap_and_persistence(tmp_path):
    lag, fresh = _start_stub(), _start_stub()
    prober, _rec = _mk_prober(tmp_path, [lag, fresh], skew_after=3)
    # Staggered timers (gap 1) are healthy forever:
    lag.epoch, fresh.epoch = 3, 4
    for _ in range(4):
        assert prober.probe_round()["fanout"] == PASS
    # A stuck replica falls ≥ epoch_gap behind and STAYS behind:
    fresh.epoch = 6
    assert prober.probe_round()["fanout"] == PASS      # round 1
    assert prober.probe_round()["fanout"] == PASS      # round 2
    v = prober.probe_round()                           # round 3: verdict
    assert v["fanout"] == SKEW
    ev = prober._state["fanout"]
    assert ev["dimensions"]["epoch"]["replicas"] == ["r0"]
    assert ev["replicas"] == ["r0"]
    m = get_registry().get("rtpu_probe_replica_skew")
    assert m is not None
    values = {key: child.value for key, child in m.items()}
    assert values[("r0", "epoch")] == 1.0
    assert values[("r1", "epoch")] == 0.0


def test_model_skew_minority_fingerprint_named(tmp_path):
    a, b, c = _start_stub(), _start_stub(), _start_stub()
    c.fingerprint = "fp-ROGUE"
    prober, _rec = _mk_prober(tmp_path, [a, b, c], skew_after=2)
    assert prober.probe_round()["fanout"] == PASS
    v = prober.probe_round()
    assert v["fanout"] == SKEW
    assert prober._state["fanout"]["dimensions"]["model"]["replicas"] \
        == ["r2"]


def test_transient_mismatch_never_skews(tmp_path):
    a, b = _start_stub(), _start_stub()
    prober, _rec = _mk_prober(tmp_path, [a, b], skew_after=3)
    b.fingerprint = "fp-new"
    assert prober.probe_round()["fanout"] == PASS   # round 1 mismatch
    a.fingerprint = "fp-new"                        # swap propagated
    for _ in range(4):
        assert prober.probe_round()["fanout"] == PASS
    assert prober._skew_rounds["model"] == 0


# ── correctness page → evidence bundle ───────────────────────────────


def test_correctness_page_writes_bundle_naming_replica(tmp_path):
    good, bad = _start_stub(), _start_stub()
    prober, recorder = _mk_prober(
        tmp_path, [good, bad], gateway=good,
        fast_window_s=2.0, slow_window_s=4.0)
    assert prober.probe_round()["fanout"] == PASS
    bad.skew = 60.0
    for _ in range(4):
        prober.probe_round()
        time.sleep(0.05)
    root = str(tmp_path / "pm")
    bundles = sorted(d for d in os.listdir(root)
                     if "correctness-page" in d or "correctness_page" in d)
    assert bundles, os.listdir(root)
    bundle = os.path.join(root, bundles[-1])
    evidence = json.load(open(os.path.join(bundle,
                                           "probe_evidence.json")))
    assert "r1" in evidence["replicas"]
    failures = evidence["failures"]
    assert failures and failures[-1]["verdict"] == DIVERGENT
    assert failures[-1]["divergence"] > 5.0
    assert failures[-1]["expected"], "oracle/pinned answer embedded"
    assert failures[-1]["served"]["r1"], "served answer embedded"
    manifest = json.load(open(os.path.join(bundle, "manifest.json")))
    assert manifest["reason"] == "correctness_page"
    assert manifest["detail"]["replicas"] == ["r1"]
    # The prober's dedicated engine rides in the manifest (component
    # "prober"), alongside whatever user engines exist.
    comps = [s.get("component") for s in manifest["slo"]]
    assert "prober" in comps


# ── bounded probe rate / backoff under a down fleet ──────────────────


def test_backoff_doubles_to_cap_and_resets(tmp_path):
    stub = _start_stub()
    prober, _rec = _mk_prober(tmp_path, [stub], interval_s=1.0,
                              backoff_cap_s=4.0)
    stub.dead = True
    prober.probe_round()
    assert prober._interval == 2.0
    prober.probe_round()
    assert prober._interval == 4.0
    prober.probe_round()
    assert prober._interval == 4.0    # capped
    stub.dead = False
    prober.probe_round()
    assert prober._interval == 1.0    # reset on first success


def test_failed_probe_is_retried_once_before_recording(tmp_path):
    """A single transient failure must not reach the verdict counters
    (a low-rate SLO pages on blips otherwise)."""
    stub = _start_stub()
    prober, _rec = _mk_prober(tmp_path, [stub])
    assert prober.probe_round()["golden"] == PASS
    calls = {"n": 0}
    real = prober._probe_golden

    def flaky():
        calls["n"] += 1
        if calls["n"] == 1:
            return UNREACHABLE, {"error": "blip"}
        return real()

    prober._probe_golden = flaky
    assert prober._checked("golden", prober._probe_golden) == PASS
    assert calls["n"] == 2


# ── tag-and-exclude: probe traffic never burns user budget ───────────


def test_probe_error_storm_leaves_replica_user_slo_ok():
    from routest_tpu.serve.wsgi import App
    from werkzeug.test import Client

    app = App()

    @app.route("/api/predict_eta", methods=("POST",))
    def boom(request):
        return {"error": "injected"}, 500

    client = Client(app)
    # Probe-only 500 storm, tagged:
    for _ in range(25):
        r = client.post("/api/predict_eta", json={},
                        headers={"X-RTPU-Probe": "golden"})
        assert r.status_code == 500
    snap = app.request_stats.snapshot()["routes"]
    assert snap.get("POST /api/predict_eta", {"count": 0})["count"] == 0
    from routest_tpu.obs.slo import build_replica_engine

    engine = build_replica_engine(app.request_stats.registry)
    engine.tick()
    time.sleep(0.02)
    engine.tick()
    assert engine.worst_state() == "ok"
    # The storm IS visible — in the probe family, not the user one.
    m = get_registry().get("rtpu_probe_replica_requests_total")
    total = sum(c.value for k, c in m.items()
                if k == ("POST /api/predict_eta",))
    assert total >= 25
    # An untagged request still counts into user stats:
    client.post("/api/predict_eta", json={})
    snap = app.request_stats.snapshot()["routes"]
    assert snap["POST /api/predict_eta"]["count"] == 1


def test_probe_traffic_excluded_from_gateway_families(tmp_path):
    from routest_tpu.core.config import FleetConfig
    from routest_tpu.serve.fleet.gateway import Gateway

    stub = _start_stub()
    stub.dead = True              # every upstream answer is a 500
    gw = Gateway([("127.0.0.1", stub.server_address[1])],
                 FleetConfig(hedge=False))
    reg = get_registry()

    def fam_count(name, route):
        m = reg.get(name)
        total = 0.0
        for key, child in (m.items() if m is not None else ()):
            if key and key[0] == route:
                total += getattr(child, "count", None) or child.value
        return total

    route = "/api/predict_eta"
    before_user = fam_count("rtpu_gateway_request_seconds", route)
    before_err = fam_count("rtpu_gateway_request_errors_total", route)
    before_probe = fam_count("rtpu_probe_gateway_requests_total", route)
    for _ in range(10):
        status, _rh, _data = gw.handle(
            "POST", route, b"{}",
            {"X-RTPU-Probe": "golden",
             "Content-Type": "application/json"}, None)
        assert status >= 500
    assert fam_count("rtpu_gateway_request_seconds", route) == before_user
    assert fam_count("rtpu_gateway_request_errors_total",
                     route) == before_err
    assert fam_count("rtpu_probe_gateway_requests_total",
                     route) == before_probe + 10
    # Untagged traffic still measures:
    gw.handle("POST", route, b"{}", {}, None)
    assert fam_count("rtpu_gateway_request_seconds",
                     route) == before_user + 1


def test_tail_sampler_retains_probe_traces():
    from routest_tpu.obs.export import TailSampler

    sampler = TailSampler(default_slow_ms=10_000.0, reservoir=0.0)
    kept = sampler.offer({"trace_id": "t1", "parent_id": None,
                          "duration_ms": 1.0, "name": "replica.request",
                          "attrs": {"probe": "golden"}})
    assert kept is not None and kept[0] == "probe"
    dropped = sampler.offer({"trace_id": "t2", "parent_id": None,
                             "duration_ms": 1.0,
                             "name": "replica.request", "attrs": {}})
    assert dropped is None


# ── chaos `skew` kind: the silently-wrong device ─────────────────────


def test_chaos_skew_perturbs_batcher_outputs_deterministically():
    from routest_tpu import chaos
    from routest_tpu.serve.ml_service import DynamicBatcher

    engine = chaos.ChaosEngine("device.compute:skew=1.0/7.5", seed=3)
    chaos.configure(engine)
    try:
        b = DynamicBatcher(lambda x: np.asarray(x)[:, 0] * 0.0,
                           buckets=(8,), max_batch=8, max_wait_ms=1.0)
        out = b.submit(np.ones((3, 12), np.float32))
        assert np.allclose(out, 7.5)
        snap = engine.snapshot()["device.compute"]
        assert snap["rules"][0]["fired"] >= 1
    finally:
        chaos.configure(None)


def test_chaos_skew_inert_without_spec():
    from routest_tpu import chaos

    engine = chaos.ChaosEngine("", seed=0)
    assert engine.inject("device.compute") == 0.0


def test_gateway_serve_arms_prober_from_env(monkeypatch, tmp_path):
    """The production wiring: RTPU_PROBER=1 arms the prober with the
    gateway's own listen address; /api/probes surfaces it; drain stops
    it."""
    import urllib.request

    from routest_tpu.core.config import FleetConfig
    from routest_tpu.serve.fleet.gateway import Gateway

    stub = _start_stub()
    monkeypatch.setenv("RTPU_PROBER", "1")
    monkeypatch.setenv("RTPU_PROBER_INTERVAL_S", "0.3")
    monkeypatch.setenv("RTPU_PROBER_ETA_TOL_MIN", "5")
    gw = Gateway([("127.0.0.1", stub.server_address[1])],
                 FleetConfig(hedge=False))
    httpd = gw.serve("127.0.0.1", 0)
    try:
        assert gw.prober is not None
        assert gw.prober.gateway_base == \
            f"http://127.0.0.1:{httpd.server_address[1]}"
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and gw.prober._rounds == 0:
            time.sleep(0.1)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{httpd.server_address[1]}"
                "/api/probes", timeout=5) as r:
            snap = json.loads(r.read())
        assert snap["rounds"] >= 1
        assert snap["probes"]["golden"]["verdict"] == PASS
    finally:
        gw.drain(timeout=5)
    assert gw.prober._stop is None   # drain stopped the loop


# ── snapshot surface ─────────────────────────────────────────────────


def test_snapshot_shape(tmp_path):
    stub = _start_stub()
    prober, _rec = _mk_prober(tmp_path, [stub])
    prober.probe_round()
    snap = prober.snapshot()
    assert snap["kinds"] == ["golden", "fanout", "dispatch"]
    assert snap["rounds"] == 1
    assert snap["probes"]["golden"]["verdict"] == PASS
    assert "served" not in snap["probes"]["golden"]
    assert snap["slo"]["component"] == "prober"
    assert "correctness:golden" in snap["slo"]["objectives"]
