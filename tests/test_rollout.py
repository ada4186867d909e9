"""Safe change delivery, hermetic: verified hot-swap at the replica,
canary routing at the gateway, and the canary → bake → promote state
machine with automatic rollback over stub multi-process workers (same
harness as ``tests/test_fleet_dynamic.py``).
"""

import json
import socket
import sys
import threading
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from routest_tpu import chaos
from routest_tpu.core.config import (FleetConfig, RolloutConfig,
                                     ServeConfig)
from routest_tpu.core.dtypes import F32_POLICY
from routest_tpu.models.eta_mlp import EtaMLP
from routest_tpu.obs.recorder import (FlightRecorder, RecorderConfig,
                                      configure_recorder)
from routest_tpu.serve.fleet.gateway import Gateway
from routest_tpu.serve.fleet.rollout import (RolloutController,
                                             rolling_restart)
from routest_tpu.serve.fleet.supervisor import ReplicaSupervisor
from routest_tpu.train.checkpoint import save_model

# ── verified hot-swap (EtaService golden-batch gate) ─────────────────


def _write_params(path, params, model):
    save_model(path, model, params)
    import os

    st = os.stat(path)
    os.utime(path, ns=(st.st_atime_ns, st.st_mtime_ns + 1_000_000))


@pytest.fixture()
def swap_service(tmp_path):
    from routest_tpu.serve.ml_service import EtaService

    model = EtaMLP(hidden=(8,), policy=F32_POLICY)
    params = model.init(jax.random.PRNGKey(0))
    path = str(tmp_path / "m.msgpack")
    _write_params(path, params, model)
    svc = EtaService(ServeConfig(), model_path=path)
    assert svc.available
    return svc, model, params, path


def test_swap_rejects_divergent_artifact_keeps_serving(swap_service):
    svc, model, params, path = swap_service
    gen0, fp0 = svc.generation, svc.fingerprint
    # Shift every parameter by 1e6 (a corrupted export): loads fine,
    # self-checks finite, but the golden batch diverges far beyond any
    # plausible retrain.
    garbage = jax.tree_util.tree_map(lambda x: x + 1.0e6, params)
    _write_params(path, garbage, model)
    assert svc.reload_if_changed() is False
    assert svc.available and svc.generation == gen0
    assert svc.fingerprint == fp0          # the live identity is the OLD bytes
    eta, _ = svc.predict_eta_minutes(weather="Sunny", traffic="Low",
                                     distance_m=10_000, pickup_time=None)
    assert eta is not None and np.isfinite(eta)


def test_swap_accepts_close_artifact_and_bumps_generation(swap_service):
    svc, model, params, path = swap_service
    gen0, fp0 = svc.generation, svc.fingerprint
    close = jax.tree_util.tree_map(lambda x: x * (1.0 + 1e-4), params)
    _write_params(path, close, model)
    assert svc.reload_if_changed() is True
    assert svc.generation > gen0
    assert svc.fingerprint != fp0          # new bytes, new identity
    assert svc.stats["generation"] == svc.generation
    assert svc.stats["fingerprint"] == svc.fingerprint


def test_swap_rejects_nan_artifact(swap_service):
    svc, model, params, path = swap_service
    gen0 = svc.generation
    broken = jax.tree_util.tree_map(lambda x: np.full_like(x, np.nan),
                                    params)
    _write_params(path, broken, model)
    assert svc.reload_if_changed() is False
    assert svc.available and svc.generation == gen0


def test_swap_divergence_bound_is_configurable(tmp_path):
    from routest_tpu.serve.ml_service import EtaService

    model = EtaMLP(hidden=(8,), policy=F32_POLICY)
    params = model.init(jax.random.PRNGKey(0))
    path = str(tmp_path / "m.msgpack")
    _write_params(path, params, model)
    # Divergence bound off (0): ANY finite replacement is accepted.
    svc = EtaService(ServeConfig(swap_max_divergence=0.0),
                     model_path=path)
    garbage = jax.tree_util.tree_map(lambda x: x + 1.0e6, params)
    _write_params(path, garbage, model)
    assert svc.reload_if_changed() is True


def test_model_load_chaos_rejects_swap_deterministically(swap_service):
    svc, model, params, path = swap_service
    gen0 = svc.generation
    engine = chaos.ChaosEngine(spec="model.load:error=1.0@1", seed=3)
    chaos.configure(engine)
    try:
        close = jax.tree_util.tree_map(lambda x: x * (1.0 + 1e-4), params)
        _write_params(path, close, model)
        # First load eats the injected fault → swap rejected, old model
        # keeps serving.
        assert svc.reload_if_changed() is False
        assert svc.available and svc.generation == gen0
        # The rule is exhausted (@1): the next change swaps cleanly.
        _write_params(path, close, model)
        assert svc.reload_if_changed() is True
        assert svc.generation > gen0
    finally:
        chaos.configure(None)


# ── stub fleet harness ───────────────────────────────────────────────

_STUB_WORKER = """
import http.server, json, os, time
VERSION = os.environ.get("RTPU_VERSION") or None
MODEL_STATUS = os.environ.get("STUB_MODEL_STATUS", "ok")
FAIL = os.environ.get("STUB_FAIL") == "1"
SLOW_S = float(os.environ.get("STUB_SLOW_S", "0") or 0)
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
                "status": MODEL_STATUS, "generation": 1,
                "fingerprint": "stub-" + (VERSION or "none")}},
                "status": MODEL_STATUS})
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
        if SLOW_S:
            time.sleep(SLOW_S)
        if FAIL:
            self._send(500, {"error": "stub failure", "version": VERSION})
        else:
            self._send(200, {"eta_minutes_ml": 1.0, "version": VERSION})
srv = http.server.ThreadingHTTPServer(("127.0.0.1", int(os.environ["PORT"])), H)
srv.daemon_threads = True
srv.serve_forever()
"""


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _boot_stub_fleet(n=2, **gw_cfg):
    ports = [_free_port() for _ in range(n)]
    sup = ReplicaSupervisor(
        ports, command=lambda p: [sys.executable, "-c", _STUB_WORKER],
        probe_interval_s=0.15, backoff_base_s=0.2, backoff_cap_s=1.0)
    sup.start()
    assert sup.ready(timeout=30)
    gw = Gateway([("127.0.0.1", p) for p in ports],
                 FleetConfig(**{"hedge": False, **gw_cfg}),
                 supervisor=sup)
    httpd = gw.serve("127.0.0.1", 0)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    return sup, gw, base


def _post(base, path, payload, timeout=15.0):
    req = urllib.request.Request(
        f"{base}{path}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def _get(base, path, timeout=15.0):
    with urllib.request.urlopen(f"{base}{path}", timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


class _Pump:
    """Background client: POSTs /api/predict_eta in a loop, counting
    statuses — the zero-client-errors (and blast-radius) witness."""

    def __init__(self, base, interval_s=0.005):
        self.base = base
        self.interval_s = interval_s
        self.statuses = []
        self.transport_errors = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        while not self._stop.is_set():
            try:
                status, _ = _post(self.base, "/api/predict_eta", {},
                                  timeout=10)
                self.statuses.append(status)
            except Exception as e:
                self.transport_errors.append(str(e)[:60])
            time.sleep(self.interval_s)

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=10)

    @property
    def errors_5xx(self):
        return [s for s in self.statuses if s >= 500]


def _rollout_cfg(**overrides):
    defaults = dict(canary_fraction=0.25, canary_replicas=1, bake_s=2.0,
                    tick_s=0.1, max_unavailable=1, min_canary_requests=5,
                    max_error_rate=0.05, max_error_ratio=3.0,
                    latency_threshold_ms=1500.0,
                    max_latency_regression=0.25, crash_restarts=2,
                    boot_timeout_s=20.0, health_timeout_s=5.0,
                    drain_timeout_s=5.0)
    defaults.update(overrides)
    return RolloutConfig(**defaults)


@pytest.fixture()
def recorder(tmp_path):
    rec = FlightRecorder(RecorderConfig(dir=str(tmp_path / "pm"),
                                        min_interval_s=0.0))
    configure_recorder(rec)
    yield rec
    configure_recorder(None)


# ── gateway: canary routing + version families ───────────────────────

def test_canary_split_is_exact_and_version_families_record(monkeypatch):
    monkeypatch.setenv("RTPU_SLO", "0")
    sup, gw, base = _boot_stub_fleet(n=2)
    try:
        with gw._lock:
            gw.replicas[0].version = "vbase-a"
            gw.replicas[1].version = "vcanary-a"
            gw._version_by_rid = {"r0": "vbase-a", "r1": "vcanary-a"}
        gw.set_canary({"r1"}, 0.25)
        with gw._lock:
            before = {r.id: r.requests for r in gw.replicas}
        for _ in range(40):
            status, _body = _post(base, "/api/predict_eta", {})
            assert status == 200
        with gw._lock:
            hits = {r.id: r.requests - before[r.id] for r in gw.replicas}
        # Exact credit split: 0.25 × 40 = 10 picks to the canary.
        assert hits["r1"] == 10
        assert hits["r0"] == 30
        gw.clear_canary()
        # The version-labeled families saw both cohorts.
        from routest_tpu.obs import get_registry

        fams = get_registry().snapshot()
        versions = {s["labels"]["version"]
                    for s in fams["rtpu_gateway_version_request_seconds"]
                    ["series"]}
        assert {"vbase-a", "vcanary-a"} <= versions
    finally:
        gw.drain(timeout=5)
        sup.drain(timeout=10)


# ── rolling restart ──────────────────────────────────────────────────

def test_rolling_restart_flips_every_replica_zero_errors(monkeypatch):
    monkeypatch.setenv("RTPU_SLO", "0")
    sup, gw, base = _boot_stub_fleet(n=2)
    try:
        with _Pump(base) as pump:
            time.sleep(0.3)
            out = rolling_restart(
                sup, gw, version="v2-rr", env={"RTPU_VERSION": "v2-rr"},
                max_unavailable=1, drain_timeout_s=5.0,
                boot_timeout_s=20.0, health_timeout_s=5.0)
            time.sleep(0.5)
        assert out["ok"], out
        assert len(out["replaced"]) == 2
        with gw._lock:
            assert all(r.version == "v2-rr" for r in gw.replicas)
        assert {s["version"] for s in sup.snapshot().values()} == {"v2-rr"}
        # Responses prove the new processes answer.
        status, body = _post(base, "/api/predict_eta", {})
        assert status == 200 and body["version"] == "v2-rr"
        assert not pump.errors_5xx, pump.errors_5xx[:5]
        assert not pump.transport_errors, pump.transport_errors[:5]
    finally:
        gw.drain(timeout=5)
        sup.drain(timeout=10)


# ── rollout controller ───────────────────────────────────────────────

def test_rollout_promotes_good_version(monkeypatch, recorder):
    monkeypatch.setenv("RTPU_SLO", "0")
    sup, gw, base = _boot_stub_fleet(n=2)
    ctl = RolloutController(sup, gw, _rollout_cfg(canary_fraction=0.5))
    try:
        assert gw.rollout is ctl
        with _Pump(base) as pump:
            assert ctl.start("v2-good", env={"RTPU_VERSION": "v2-good"})
            assert ctl.start("v3") is False      # one rollout at a time
            assert ctl.wait(timeout=60) == "done"
            time.sleep(0.3)
        with gw._lock:
            assert all(r.version == "v2-good" for r in gw.replicas)
            assert len(gw.replicas) == 2
        assert not pump.errors_5xx, pump.errors_5xx[:5]
        assert not pump.transport_errors, pump.transport_errors[:5]
        events = [h.get("event") for h in ctl.snapshot()["history"]]
        assert "bake_passed" in events and "promoted" in events
        # Promoted version becomes the default for future spawns
        # (autoscaler growth comes up on it).
        index, port = sup.add_replica()
        assert sup.replica_status(index)["version"] == "v2-good"
        assert sup.wait_port_ready(port, timeout=20)
        # /api/rollout reflects the terminal state.
        status, payload = _get(base, "/api/rollout")
        assert status == 200 and payload["state"] == "done"
        assert payload["version"] == "v2-good"
    finally:
        gw.drain(timeout=5)
        sup.drain(timeout=10)


def test_rollout_rolls_back_on_verify_failure(monkeypatch, recorder):
    monkeypatch.setenv("RTPU_SLO", "0")
    sup, gw, base = _boot_stub_fleet(n=2)
    ctl = RolloutController(sup, gw, _rollout_cfg())
    try:
        with _Pump(base) as pump:
            # The canary boots and answers /up, but its model check is
            # degraded (a corrupt artifact): the health gate must catch
            # it BEFORE any traffic routes there.
            assert ctl.start("v2-bad", env={
                "RTPU_VERSION": "v2-bad", "STUB_MODEL_STATUS": "degraded"})
            assert ctl.wait(timeout=60) == "rolled_back"
            time.sleep(0.3)
        with gw._lock:
            assert len(gw.replicas) == 2
            assert all(r.version is None for r in gw.replicas)
        assert not pump.errors_5xx, pump.errors_5xx[:5]
        hist = ctl.snapshot()["history"]
        rb = next(h for h in hist if h.get("event") == "rollback")
        assert rb["trigger"] == "verify_failed"
        assert rb["offending_version"] == "v2-bad"
        # The rollback decision + offending version landed in a
        # flight-recorder bundle.
        bundle = ctl.snapshot()["last_bundle"]
        assert bundle is not None
        manifest = json.loads(
            open(f"{bundle}/manifest.json").read())
        assert manifest["reason"] == "rollout_rollback"
        assert manifest["detail"]["offending_version"] == "v2-bad"
        assert manifest["detail"]["trigger"] == "verify_failed"
    finally:
        gw.drain(timeout=5)
        sup.drain(timeout=10)


def test_rollout_rolls_back_on_boot_crash_loop(monkeypatch, recorder):
    monkeypatch.setenv("RTPU_SLO", "0")
    sup, gw, base = _boot_stub_fleet(n=2)
    ctl = RolloutController(sup, gw, _rollout_cfg(boot_timeout_s=30.0))
    # Chaos dooms ONLY the new version's spawns (per-version fault
    # point): the canary crash-loops, rollback spawns (old version, no
    # label) are untouched — deterministic, no fire limits needed.
    chaos.configure(chaos.ChaosEngine(
        spec="replica.boot.v2-crash:error=1.0", seed=11))
    try:
        with _Pump(base) as pump:
            assert ctl.start("v2-crash", env={"RTPU_VERSION": "v2-crash"})
            assert ctl.wait(timeout=60) == "rolled_back"
            time.sleep(0.3)
        with gw._lock:
            assert len(gw.replicas) == 2
        assert not pump.errors_5xx, pump.errors_5xx[:5]
        hist = ctl.snapshot()["history"]
        rb = next(h for h in hist if h.get("event") == "rollback")
        assert rb["trigger"] == "boot_crash_loop"
        assert ctl.snapshot()["last_bundle"] is not None
        # The injections are on the ledger.
        from routest_tpu.obs import get_registry

        fams = get_registry().snapshot()
        points = {s["labels"]["point"]: s["value"]
                  for s in fams["rtpu_chaos_injections_total"]["series"]}
        assert points.get("replica.boot.v2-crash", 0) >= 1
    finally:
        chaos.configure(None)
        gw.drain(timeout=5)
        sup.drain(timeout=10)


def test_rollout_rolls_back_on_slo_regressing_canary(monkeypatch,
                                                     recorder):
    monkeypatch.setenv("RTPU_SLO", "0")
    sup, gw, base = _boot_stub_fleet(n=2)
    ctl = RolloutController(sup, gw, _rollout_cfg(
        canary_fraction=0.25, bake_s=30.0, min_canary_requests=5))
    try:
        with _Pump(base, interval_s=0.002) as pump:
            # The canary is healthy at boot but serves 500s: only the
            # bake comparison can catch this one.
            assert ctl.start("v2-err", env={
                "RTPU_VERSION": "v2-err", "STUB_FAIL": "1"})
            assert ctl.wait(timeout=60) == "rolled_back"
            time.sleep(0.3)
        with gw._lock:
            assert len(gw.replicas) == 2
            assert all(r.version is None for r in gw.replicas)
        hist = ctl.snapshot()["history"]
        rb = next(h for h in hist if h.get("event") == "rollback")
        assert rb["trigger"] == "canary_error_rate"
        assert rb["canary_error_rate"] > rb["baseline_error_rate"]
        # Blast radius: the bad version only ever saw the canary
        # fraction of traffic, so client 5xx stays bounded by it (plus
        # slack for the tiny sample).
        total = len(pump.statuses)
        assert total > 0
        bad = len(pump.errors_5xx)
        assert 0 < bad <= max(3, int(total * 0.35)), (bad, total)
        assert ctl.snapshot()["last_bundle"] is not None
    finally:
        gw.drain(timeout=5)
        sup.drain(timeout=10)


def test_rollout_abort_via_api_rolls_back(monkeypatch, recorder):
    monkeypatch.setenv("RTPU_SLO", "0")
    sup, gw, base = _boot_stub_fleet(n=2)
    ctl = RolloutController(sup, gw, _rollout_cfg(bake_s=30.0))
    try:
        assert ctl.start("v2-abort", env={"RTPU_VERSION": "v2-abort"})
        deadline = time.time() + 30
        while time.time() < deadline and ctl.state != "baking":
            time.sleep(0.05)
        assert ctl.state == "baking"
        req = urllib.request.Request(
            f"{base}/api/rollout",
            data=json.dumps({"action": "abort"}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert resp.status == 200
            assert json.loads(resp.read())["aborted"] is True
        assert ctl.wait(timeout=60) == "rolled_back"
        with gw._lock:
            assert all(r.version is None for r in gw.replicas)
    finally:
        gw.drain(timeout=5)
        sup.drain(timeout=10)


# ── autoscaler coordination ──────────────────────────────────────────

class _Obj:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def test_autoscaler_holds_while_rollout_active():
    from routest_tpu.core.config import AutoscaleConfig
    from routest_tpu.serve.fleet.autoscaler import Autoscaler

    rollout = _Obj(active=lambda: True)
    gw = _Obj(rollout=rollout, autoscaler=None)
    scaler = Autoscaler(_Obj(), gw, AutoscaleConfig(
        enabled=True, up_stable_ticks=1, tick_s=0.1))
    scaler._up_ticks = 99          # pre-built pressure must reset
    assert scaler.tick() is None
    assert scaler._up_ticks == 0
    holds = [h for h in scaler._history if h.get("direction") == "hold"]
    assert len(holds) == 1
    # A second tick while still active does not spam the history.
    assert scaler.tick() is None
    holds = [h for h in scaler._history if h.get("direction") == "hold"]
    assert len(holds) == 1
