"""Binary wire serving (docs/API.md "Binary wire format"): content-type
negotiation parity against the JSON path on a live app (compared
bitwise — the format's contract is exact parity, not closeness), the
415 refusal when the format is disabled, error-frame semantics, the
multiplexed gateway↔replica channel (concurrency, deadline
propagation, dead-socket recovery, HTTP fallback), the loadgen
``wire_format`` knob's byte-stability, and the prober's ``wire``
parity kind. Codec-level fuzzing lives in ``tests/test_wirecodec.py``.
"""

import datetime as dt
import http.server
import json
import os
import threading
import time

import numpy as np
import pytest
from werkzeug.test import Client

from routest_tpu.core.config import (Config, FleetConfig, ProberConfig,
                                     RecorderConfig, ServeConfig,
                                     TrainConfig)
from routest_tpu.obs.prober import (DIVERGENT, PASS, UNREACHABLE,
                                    BlackboxProber, eta_columns,
                                    golden_probe_body, golden_wire_frame)
from routest_tpu.serve import wirecodec as wc
from routest_tpu.serve.wirechannel import (WireChannelClient,
                                           WireChannelError,
                                           WireChannelServer)

WIRE_CT = "application/x-rtpu-wire"


@pytest.fixture()
def wire_env():
    """RTPU_WIRE=1 for the duration of one test (create_app and the
    prober read it at construction time)."""
    old = os.environ.get("RTPU_WIRE")
    os.environ["RTPU_WIRE"] = "1"
    yield
    if old is None:
        os.environ.pop("RTPU_WIRE", None)
    else:
        os.environ["RTPU_WIRE"] = old


@pytest.fixture(scope="module")
def model_path(tmp_path_factory):
    from routest_tpu.data.synthetic import generate_dataset, train_eval_split
    from routest_tpu.models.eta_mlp import EtaMLP
    from routest_tpu.train.checkpoint import save_model
    from routest_tpu.train.loop import fit

    train, ev = train_eval_split(generate_dataset(8_000, seed=0))
    model = EtaMLP(hidden=(16,), quantiles=(0.1, 0.5, 0.9))
    result = fit(model, train, ev, TrainConfig(epochs=2, batch_size=2048))
    path = str(tmp_path_factory.mktemp("wire") / "m.msgpack")
    save_model(path, model, result.state.params)
    return path


def _wire_app(model_path):
    from routest_tpu.serve.app import create_app
    from routest_tpu.serve.ml_service import EtaService

    svc = EtaService(ServeConfig(), model_path=model_path)
    return create_app(Config(), eta_service=svc)


# ── HTTP negotiation parity ──────────────────────────────────────────

def test_wire_parity_bitwise_with_json(wire_env, model_path):
    app = _wire_app(model_path)
    assert sorted(app.wire_handlers) == ["/api/matrix",
                                        "/api/predict_eta_batch"]
    client = Client(app)
    rj = client.post("/api/predict_eta_batch", json=golden_probe_body())
    assert rj.status_code == 200
    jcols = eta_columns(rj.get_json())
    rw = client.post("/api/predict_eta_batch", data=golden_wire_frame(),
                     content_type=WIRE_CT)
    assert rw.status_code == 200 and rw.content_type == WIRE_CT
    wire = wc.decode_eta_response(rw.get_data())
    minutes = wire["minutes"]
    finite = np.isfinite(minutes)
    assert finite.all()  # golden rows must score finitely
    got = {"eta_minutes_ml": np.round(minutes, 4)}
    for lvl, vals in wire["bands"].items():
        got[f"eta_minutes_ml_{lvl}"] = np.round(vals, 4)
    assert sorted(got) == sorted(jcols)
    for key in jcols:   # bitwise: byte-compare the float columns
        assert got[key].tobytes() == jcols[key].tobytes(), key
    iso = np.datetime_as_string(
        np.asarray(wire["completion_ms"],
                   np.int64).astype("datetime64[ms]"), unit="s")
    assert list(iso) == rj.get_json()["eta_completion_time_ml"]


def test_wire_matrix_parity(wire_env, model_path):
    client = Client(_wire_app(model_path))
    pts = np.array([[14.6, 121.0], [14.61, 121.02], [14.59, 120.98]])
    opts = {"sources": [0], "destinations": [1, 2], "vehicle_type": "car"}
    rw = client.post("/api/matrix",
                     data=wc.encode_matrix_request(pts, opts),
                     content_type=WIRE_CT)
    assert rw.status_code == 200
    wirem = wc.decode_matrix_response(rw.get_data())
    rj = client.post("/api/matrix", json={
        "points": [{"lat": a, "lon": b} for a, b in pts], **opts})
    jm = rj.get_json()
    assert wirem["durations_s"] == jm["durations_s"]
    assert wirem["distances_m"] == jm["distances_m"]


def test_wire_disabled_refuses_with_415(model_path):
    assert os.environ.get("RTPU_WIRE") != "1"
    app = _wire_app(model_path)
    assert app.wire_handlers == {}
    r = Client(app).post("/api/predict_eta_batch",
                         data=golden_wire_frame(), content_type=WIRE_CT)
    assert r.status_code == 415
    assert "RTPU_WIRE" in r.get_json()["error"]
    # the JSON path is untouched by the refusal
    rj = Client(app).post("/api/predict_eta_batch",
                          json=golden_probe_body())
    assert rj.status_code == 200


def test_wire_malformed_frame_is_400_error_frame(wire_env, model_path):
    client = Client(_wire_app(model_path))
    r = client.post("/api/predict_eta_batch", data=b"RTW1junk",
                    content_type=WIRE_CT)
    assert r.status_code == 400 and r.content_type == WIRE_CT
    status, message = wc.decode_error_frame(r.get_data())
    assert status == 400 and "malformed" in message


def test_wire_model_unavailable_is_503_error_frame(wire_env, tmp_path):
    from routest_tpu.serve.app import create_app
    from routest_tpu.serve.ml_service import EtaService

    svc = EtaService(ServeConfig(),
                     model_path=str(tmp_path / "missing.msgpack"))
    client = Client(create_app(Config(), eta_service=svc))
    r = client.post("/api/predict_eta_batch", data=golden_wire_frame(),
                    content_type=WIRE_CT)
    assert r.status_code == 503
    status, message = wc.decode_error_frame(r.get_data())
    assert status == 503 and "model unavailable" in message


# ── the multiplexed channel ──────────────────────────────────────────

def test_channel_multiplexes_on_one_connection():
    order = []

    def handler(frame):
        delay = float(frame.decode())
        time.sleep(delay)
        order.append(delay)
        return 200, frame

    srv = WireChannelServer({"/h": handler}, "127.0.0.1", 0)
    srv.start()
    try:
        cli = WireChannelClient("127.0.0.1", srv.port)
        outs = [None, None]

        def call(i, delay):
            outs[i] = cli.request("/h", str(delay).encode(), timeout=30.0)

        slow = threading.Thread(target=call, args=(0, 0.5))
        slow.start()
        time.sleep(0.05)
        fast = threading.Thread(target=call, args=(1, 0.0))
        fast.start()
        slow.join(10); fast.join(10)
        assert outs[0] == (200, b"0.5") and outs[1] == (200, b"0.0")
        # the fast request finished FIRST despite being sent second on
        # the same connection: no head-of-line blocking
        assert order == [0.0, 0.5]
        cli.close()
    finally:
        srv.stop()


def test_channel_deadline_and_error_frames():
    def slow(frame):
        from routest_tpu.serve.deadline import DeadlineExceeded, expired
        time.sleep(0.05)
        if expired():
            raise DeadlineExceeded("budget burned")
        return 200, frame

    srv = WireChannelServer({"/slow": slow}, "127.0.0.1", 0)
    srv.start()
    try:
        cli = WireChannelClient("127.0.0.1", srv.port)
        status, body = cli.request("/slow", b"x", deadline_ms=0)
        assert (status, wc.decode_error_frame(body)[0]) == (504, 504)
        status, body = cli.request("/slow", b"x", deadline_ms=10.0)
        assert status == 504  # expired mid-handler
        status, body = cli.request("/slow", b"x", deadline_ms=5_000.0)
        assert (status, body) == (200, b"x")
        status, body = cli.request("/nope", b"x")
        assert status == 404
        assert "no wire handler" in wc.decode_error_frame(body)[1]
        cli.close()
    finally:
        srv.stop()


def test_channel_dead_socket_fails_loudly_then_reconnects():
    srv = WireChannelServer({"/e": lambda f: (200, f)}, "127.0.0.1", 0)
    srv.start()
    cli = WireChannelClient("127.0.0.1", srv.port)
    assert cli.request("/e", b"a") == (200, b"a")
    port = srv.port
    srv.stop()
    with pytest.raises(WireChannelError):
        cli.request("/e", b"b", timeout=3.0)
    srv2 = None
    deadline = time.monotonic() + 10
    while srv2 is None:
        try:
            srv2 = WireChannelServer({"/e": lambda f: (200, f)},
                                     "127.0.0.1", port)
            srv2.start()
        except OSError:
            srv2 = None
            assert time.monotonic() < deadline, "port never freed"
            time.sleep(0.1)
    try:
        assert cli.request("/e", b"c") == (200, b"c")
        cli.close()
    finally:
        srv2.stop()


def test_channel_rejects_oversized_messages():
    srv = WireChannelServer({"/e": lambda f: (200, f)}, "127.0.0.1", 0,
                            max_frame_bytes=1024)
    srv.start()
    try:
        cli = WireChannelClient("127.0.0.1", srv.port)
        with pytest.raises(WireChannelError):
            cli.request("/e", b"\x00" * (1 << 20), timeout=5.0)
        cli.close()
    finally:
        srv.stop()


# ── gateway dispatch + fallback ──────────────────────────────────────

class _HttpStub(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, *a):
        pass

    def _send(self, payload):
        data = json.dumps(payload).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def do_GET(self):
        self._send({"ok": True})

    def do_POST(self):
        n = int(self.headers.get("Content-Length") or 0)
        self.rfile.read(n)
        self.server.hits += 1
        self._send({"via": "http"})


def test_gateway_prefers_channel_and_falls_back_to_http(wire_env):
    import urllib.request

    from routest_tpu.serve.fleet.gateway import Gateway

    def handler(frame):
        fr = wc.decode_eta_request(frame, max_bytes=1 << 20,
                                   max_rows=4096)
        n = len(fr.columns["features"])
        return 200, wc.encode_eta_response(
            np.full(n, 7.5), np.full(n, 1, np.int64), {})

    chan = WireChannelServer({"/api/predict_eta_batch": handler},
                             "127.0.0.1", 0)
    chan.start()
    os.environ["RTPU_WIRE_PORT"] = str(chan.port)
    stub = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _HttpStub)
    stub.daemon_threads = True
    stub.hits = 0
    threading.Thread(target=stub.serve_forever, daemon=True).start()
    gw = None
    try:
        gw = Gateway([("127.0.0.1", stub.server_port)],
                     FleetConfig(hedge=False))
        httpd = gw.serve("127.0.0.1", 0)
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        frame = wc.encode_eta_request(np.zeros((4, 12), np.float32),
                                      np.zeros(4, np.int64))

        def post():
            req = urllib.request.Request(
                f"{base}/api/predict_eta_batch", data=frame,
                headers={"Content-Type": WIRE_CT}, method="POST")
            with urllib.request.urlopen(req, timeout=10) as r:
                return r.status, r.headers.get("Content-Type"), r.read()

        status, ctype, body = post()
        assert (status, ctype) == (200, WIRE_CT)
        out = wc.decode_eta_response(body)
        np.testing.assert_array_equal(out["minutes"], np.full(4, 7.5))
        assert stub.hits == 0  # the channel carried it, not HTTP
        # replica tagging survives the wire path
        chan.stop()
        time.sleep(0.1)
        status, ctype, body = post()   # channel dead → HTTP fallback
        assert status == 200 and json.loads(body) == {"via": "http"}
        assert stub.hits == 1
    finally:
        os.environ.pop("RTPU_WIRE_PORT", None)
        if gw is not None:
            gw.drain()
        chan.stop()
        stub.shutdown()


# ── loadgen wire format ──────────────────────────────────────────────

def test_loadgen_wire_format_byte_stable_and_faithful():
    from routest_tpu.data.features import encode_requests
    from routest_tpu.loadgen.workload import MixedWorkload

    def mk():
        return MixedWorkload(mix={"predict_eta_batch": 1.0}, seed=5,
                             batch_rows=16, wire_format="binary")

    a, b = mk().sequence(3), mk().sequence(3)
    assert all(x.body == y.body for x, y in zip(a, b))  # byte-stable
    assert all(x.content_type == WIRE_CT for x in a)
    # the frame carries EXACTLY the featurization of the JSON twin
    jreq = MixedWorkload(mix={"predict_eta_batch": 1.0}, seed=5,
                         batch_rows=16).sequence(3)[0]
    frame = wc.decode_eta_request(a[0].body, max_bytes=1 << 20,
                                  max_rows=1024)
    items = jreq.body["items"]
    pickups = [dt.datetime.fromisoformat(it["pickup_time"])
               for it in items]
    expected = encode_requests(
        weather=[it["weather"] for it in items],
        traffic=[it["traffic"] for it in items],
        weekday=[p.weekday() for p in pickups],
        hour=[p.hour for p in pickups],
        distance_km=[it["summary"]["distance"] / 1000.0 for it in items],
        driver_age=[it["driver_age"] for it in items])
    assert frame.columns["features"].tobytes() == \
        np.asarray(expected, np.float32).tobytes()
    # json mode is untouched
    assert isinstance(jreq.body, dict)
    assert jreq.content_type == "application/json"
    with pytest.raises(ValueError, match="wire_format"):
        MixedWorkload(wire_format="msgpack")


# ── prober wire parity kind ──────────────────────────────────────────

class _ParityStub(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, *a):
        pass

    def _reply(self, code, data, ctype):
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def do_POST(self):
        n = int(self.headers.get("Content-Length") or 0)
        self.rfile.read(n)
        srv = self.server
        minutes = np.round(1.0 + 0.25 * np.arange(srv.rows), 4)
        comp = (1_767_571_200_000
                + (minutes * 60_000.0).astype(np.int64))
        bands = {"p10": minutes - 1.0, "p90": minutes + 1.0}
        if "x-rtpu-wire" in (self.headers.get("Content-Type") or ""):
            if srv.wire_skew:
                minutes = minutes + srv.wire_skew
            data = wc.encode_eta_response(minutes, comp, bands)
            return self._reply(200, data, "application/x-rtpu-wire")
        iso = np.datetime_as_string(comp.astype("datetime64[ms]"),
                                    unit="s")
        payload = {"count": srv.rows,
                   "eta_minutes_ml": minutes.tolist(),
                   "eta_completion_time_ml": [str(s) for s in iso]}
        for lvl, vals in bands.items():
            payload[f"eta_minutes_ml_{lvl}"] = np.round(vals, 4).tolist()
        return self._reply(200, json.dumps(payload).encode(),
                           "application/json")


def _parity_prober(tmp_path, rows=32):
    from routest_tpu.obs.recorder import FlightRecorder

    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _ParityStub)
    srv.daemon_threads = True
    srv.rows = rows
    srv.wire_skew = 0.0
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    recorder = FlightRecorder(RecorderConfig(dir=str(tmp_path / "rec"),
                                             min_interval_s=0.0))
    prober = BlackboxProber(
        ProberConfig(enabled=True, timeout_s=5.0),
        gateway_base=base, targets_fn=lambda: [("r0", base)],
        recorder=recorder)
    return srv, prober


def test_prober_wire_kind_armed_only_with_wire(tmp_path, wire_env):
    _srv, prober = _parity_prober(tmp_path)
    assert "wire" in prober.kinds
    assert "correctness:wire" in prober.slo._tracks


def test_prober_wire_kind_absent_without_wire(tmp_path):
    assert os.environ.get("RTPU_WIRE") != "1"
    _srv, prober = _parity_prober(tmp_path)
    assert "wire" not in prober.kinds


def test_prober_wire_parity_verdicts(tmp_path, wire_env):
    srv, prober = _parity_prober(tmp_path)
    verdict, evidence = prober._probe_wire()
    assert verdict == PASS, evidence
    srv.wire_skew = 0.0001          # the tiniest representable drift
    verdict, evidence = prober._probe_wire()
    assert verdict == DIVERGENT
    assert "eta_minutes_ml" in evidence["columns"]
    assert evidence["tolerance"] == 0.0
    srv.wire_skew = 0.0
    srv.rows = 31                   # shape mismatch is divergence too
    verdict, evidence = prober._probe_wire()
    assert verdict == PASS          # both paths answer 31 rows equally
    srv.shutdown()
    verdict, evidence = prober._probe_wire()
    assert verdict == UNREACHABLE
