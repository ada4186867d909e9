"""Serving-fleet tests: gateway routing/breaker/shedding/drain and the
replica supervisor, hermetic and multi-process.

Fast tests run the gateway in-process against stub replica HTTP servers
(tiny ``http.server`` apps with controllable delay/failure), following
the ``tests/test_cross_process.py`` pattern for anything that needs a
real subprocess (supervisor restart-after-crash). The full-stack fleet
(real ``python -m routest_tpu.serve`` workers behind the gateway) is
exercised by the ``slow``-marked integration test at the bottom.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import http.server

import pytest

from routest_tpu.core.config import FleetConfig
from routest_tpu.serve.fleet.gateway import Gateway, _prometheus_fleet_text
from routest_tpu.serve.fleet.supervisor import ReplicaSupervisor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ── stub replica ──────────────────────────────────────────────────────

class _StubHandler(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    disable_nagle_algorithm = True

    def log_message(self, *args):
        pass

    def _send(self, code, payload, headers=()):
        data = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        for k, v in headers:
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(data)

    def do_GET(self):
        self._send(200, {"ok": True, "port": self.server.server_port})

    def do_POST(self):
        n = int(self.headers.get("Content-Length") or 0)
        self.rfile.read(n)
        srv = self.server
        if srv.delay_s:
            time.sleep(srv.delay_s)
        with srv.counter_lock:
            srv.hits += 1
            srv.seen_headers.append({k.lower(): v
                                     for k, v in self.headers.items()})
        if srv.fail_with:
            self._send(srv.fail_with, {"error": "stub failure"})
        else:
            self._send(200, {"eta_minutes_ml": 1.0,
                             "port": srv.server_port})


def _start_stub(delay_s=0.0):
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _StubHandler)
    srv.daemon_threads = True
    srv.delay_s = delay_s
    srv.fail_with = None
    srv.hits = 0
    srv.seen_headers = []
    srv.counter_lock = threading.Lock()
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _gateway(targets, **cfg_overrides):
    cfg = FleetConfig(**{"hedge": False, **cfg_overrides})
    gw = Gateway(targets, cfg)
    httpd = gw.serve("127.0.0.1", 0)
    return gw, f"http://127.0.0.1:{httpd.server_address[1]}"


def _post(base, path, payload, timeout=15.0, headers=None):
    req = urllib.request.Request(
        f"{base}{path}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read()), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


def _get(base, path, timeout=10.0):
    with urllib.request.urlopen(f"{base}{path}", timeout=timeout) as resp:
        return resp.status, resp.read()


# ── gateway: routing ─────────────────────────────────────────────────

def test_gateway_routes_across_replicas_and_tags_response():
    s1, s2 = _start_stub(), _start_stub()
    gw, base = _gateway([("127.0.0.1", s1.server_port),
                         ("127.0.0.1", s2.server_port)])
    try:
        seen_ports, seen_tags = set(), set()
        for _ in range(8):
            status, body, headers = _post(base, "/api/predict_eta", {"x": 1})
            assert status == 200
            seen_ports.add(body["port"])
            seen_tags.add(headers.get("X-Fleet-Replica"))
        # least-outstanding + RR tie-break spreads sequential traffic
        assert seen_ports == {s1.server_port, s2.server_port}
        assert seen_tags == {"r0", "r1"}
    finally:
        gw.drain(timeout=5)


def test_gateway_prefers_least_outstanding():
    slow, fast = _start_stub(delay_s=0.5), _start_stub()
    gw, base = _gateway([("127.0.0.1", slow.server_port),
                         ("127.0.0.1", fast.server_port)])
    try:
        # Two parked requests: least-outstanding spreads them one per
        # replica, so exactly one is now stuck in the slow stub's sleep
        # holding an outstanding slot. The burst must then all go to
        # `fast` (outstanding 0 or 1 there vs 1 on slow — strictly less
        # after its parked request finishes instantly).
        threads = [threading.Thread(target=_post,
                                    args=(base, "/api/predict_eta", {}))
                   for _ in range(2)]
        for t in threads:
            t.start()
        time.sleep(0.2)  # fast's parked request done; slow's still held
        for _ in range(4):
            status, body, _ = _post(base, "/api/predict_eta", {"x": 1})
            assert status == 200
            assert body["port"] == fast.server_port
        for t in threads:
            t.join()
    finally:
        gw.drain(timeout=5)


# ── gateway: circuit breaker ─────────────────────────────────────────

def test_breaker_ejects_on_5xx_and_recovers_half_open():
    sick, ok = _start_stub(), _start_stub()
    gw, base = _gateway([("127.0.0.1", sick.server_port),
                         ("127.0.0.1", ok.server_port)],
                        eject_after=3, cooldown_s=0.3)
    try:
        sick.fail_with = 500
        # Drive enough traffic to trip the breaker on the sick replica.
        statuses = [_post(base, "/api/predict_eta", {"i": i})[0]
                    for i in range(12)]
        snap = gw.snapshot()
        assert snap["replicas"]["r0"]["state"] == "open"
        assert snap["replicas"]["r0"]["ejections"] == 1
        # Once open, traffic flows only to the healthy replica.
        for _ in range(4):
            status, body, _ = _post(base, "/api/predict_eta", {"x": 1})
            assert status == 200 and body["port"] == ok.server_port

        # Heal the replica; after cooldown ONE half-open probe closes it.
        sick.fail_with = None
        time.sleep(0.35)
        for _ in range(6):
            assert _post(base, "/api/predict_eta", {"x": 2})[0] == 200
        snap = gw.snapshot()
        assert snap["replicas"]["r0"]["state"] == "closed"
    finally:
        gw.drain(timeout=5)


def test_breaker_reopens_on_failed_probe():
    sick, ok = _start_stub(), _start_stub()
    gw, base = _gateway([("127.0.0.1", sick.server_port),
                         ("127.0.0.1", ok.server_port)],
                        eject_after=2, cooldown_s=0.2)
    try:
        sick.fail_with = 503
        for i in range(8):
            _post(base, "/api/predict_eta", {"i": i})
        assert gw.snapshot()["replicas"]["r0"]["state"] == "open"
        time.sleep(0.25)
        # Still sick: the half-open probe fails and the breaker re-opens
        # without a second ejection increment (it never closed).
        for i in range(4):
            _post(base, "/api/predict_eta", {"i": i})
        snap = gw.snapshot()["replicas"]["r0"]
        assert snap["state"] == "open"
        assert snap["ejections"] == 1
    finally:
        gw.drain(timeout=5)


def test_dead_replica_retries_to_healthy_one():
    # r0 is a port with NO listener: every connect dies at transport
    # level; idempotent requests must retry onto r1 invisibly.
    dead_port = _free_port()
    ok = _start_stub()
    gw, base = _gateway([("127.0.0.1", dead_port),
                         ("127.0.0.1", ok.server_port)],
                        eject_after=3, cooldown_s=60.0)
    try:
        for i in range(10):
            status, body, _ = _post(base, "/api/predict_eta", {"i": i})
            assert status == 200 and body["port"] == ok.server_port
        snap = gw.snapshot()
        assert snap["fleet"]["retries"] >= 1
        assert snap["replicas"]["r0"]["state"] == "open"
        # Non-idempotent traffic gets a clean 502, never a hang, when it
        # draws the dead replica — and succeeds when it draws the live
        # one (breaker is open by now, so it reliably draws live).
        status, _, _ = _post(base, "/api/optimize_route", {"x": 1})
        assert status in (200, 400)  # routed to the stub (its answer)
    finally:
        gw.drain(timeout=5)


# ── gateway: admission control ───────────────────────────────────────

def test_saturated_queue_sheds_429_with_retry_after():
    slow = _start_stub(delay_s=0.6)
    gw, base = _gateway([("127.0.0.1", slow.server_port)],
                        max_inflight=1, queue_depth=1)
    try:
        results = []
        lock = threading.Lock()

        def fire():
            status, body, headers = _post(base, "/api/predict_eta", {},
                                          timeout=30)
            with lock:
                results.append((status, headers))

        threads = [threading.Thread(target=fire) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        statuses = sorted(s for s, _ in results)
        assert statuses.count(429) >= 3  # 1 proxying + 1 queued + sheds
        assert statuses.count(200) >= 1
        for status, headers in results:
            if status == 429:
                assert headers.get("Retry-After")
        assert gw.snapshot()["fleet"]["shed"] >= 3
    finally:
        gw.drain(timeout=5)


def test_deadline_shed_is_fast():
    slow = _start_stub(delay_s=0.8)
    gw, base = _gateway([("127.0.0.1", slow.server_port)],
                        max_inflight=1, queue_depth=8)
    try:
        t = threading.Thread(target=_post,
                             args=(base, "/api/predict_eta", {}))
        t.start()
        time.sleep(0.1)  # occupy the only slot
        t0 = time.perf_counter()
        status, _, _ = _post(base, "/api/predict_eta", {},
                             headers={"X-Deadline-Ms": "100"})
        waited = time.perf_counter() - t0
        assert status == 429
        assert waited < 0.5  # shed at the deadline, not after the queue
        t.join()
    finally:
        gw.drain(timeout=5)


# ── gateway: hedging ─────────────────────────────────────────────────

def test_hedged_requests_cut_slow_replica_tail():
    slow, fast = _start_stub(delay_s=0.7), _start_stub()
    gw, base = _gateway([("127.0.0.1", slow.server_port),
                         ("127.0.0.1", fast.server_port)],
                        hedge=True, hedge_min_ms=60.0)
    try:
        lat = []
        for i in range(6):
            t0 = time.perf_counter()
            status, _, _ = _post(base, "/api/predict_eta", {"i": i})
            lat.append(time.perf_counter() - t0)
            assert status == 200
        snap = gw.snapshot()["fleet"]
        assert snap["hedges"] >= 1
        assert snap["hedge_wins"] >= 1
        # A request that drew the slow replica finished on the hedge's
        # schedule (≈ hedge delay + fast replica), not the 0.7 s sleep.
        assert min(lat) < 0.3
        assert sum(lat) < 6 * 0.7
    finally:
        gw.drain(timeout=5)


# ── gateway: trace/correlation propagation (ISSUE 2) ─────────────────

def test_gateway_mints_request_id_and_propagates_trace_context():
    """Cross-process propagation, over real HTTP: the gateway mints
    X-Request-ID when the client sent none (one hop earlier than the
    replica used to), forwards it + a ``traceparent`` to the upstream,
    and stamps X-RTPU-Replica + the ids on the response."""
    stub = _start_stub()
    gw, base = _gateway([("127.0.0.1", stub.server_port)])
    try:
        status, _, headers = _post(base, "/api/predict_eta", {"x": 1})
        assert status == 200
        rid = headers.get("X-Request-ID")
        assert rid and len(rid) == 16           # minted, well-formed
        assert headers.get("X-RTPU-Replica") == "r0"
        assert headers.get("X-Fleet-Replica") == "r0"  # PR-1 back-compat
        seen = stub.seen_headers[-1]
        assert seen.get("x-request-id") == rid  # same id, one hop down
        tp = seen.get("traceparent", "")
        from routest_tpu.obs.trace import parse_traceparent

        ctx = parse_traceparent(tp)
        assert ctx is not None, tp
        assert headers.get("X-Trace-Id") in (None, ctx.trace_id)
    finally:
        gw.drain(timeout=5)


def test_gateway_honors_client_request_id_and_trace():
    stub = _start_stub()
    gw, base = _gateway([("127.0.0.1", stub.server_port)])
    trace_id = "ab" * 16
    try:
        status, _, headers = _post(
            base, "/api/predict_eta", {"x": 1},
            headers={"X-Request-ID": "my-rid.1",
                     "traceparent": f"00-{trace_id}-{'2' * 16}-01"})
        assert status == 200
        assert headers.get("X-Request-ID") == "my-rid.1"
        seen = stub.seen_headers[-1]
        assert seen.get("x-request-id") == "my-rid.1"
        # the upstream hop carries the CLIENT's trace id with the
        # gateway's own (fresh) span id — adopted, not parroted
        tp = seen.get("traceparent", "")
        assert tp.startswith(f"00-{trace_id}-")
        assert f"-{'2' * 16}-" not in tp
        # malformed client ids are replaced, not echoed
        status, _, headers = _post(
            base, "/api/predict_eta", {"x": 1},
            headers={"X-Request-ID": "bad id!"})
        assert headers.get("X-Request-ID") != "bad id!"
        assert stub.seen_headers[-1].get("x-request-id") != "bad id!"
    finally:
        gw.drain(timeout=5)


# ── gateway: metrics ─────────────────────────────────────────────────

def test_metrics_json_and_prometheus():
    s1, s2 = _start_stub(), _start_stub()
    gw, base = _gateway([("127.0.0.1", s1.server_port),
                         ("127.0.0.1", s2.server_port)])
    try:
        for i in range(6):
            _post(base, "/api/predict_eta", {"i": i})
        status, raw = _get(base, "/api/metrics")
        assert status == 200
        snap = json.loads(raw)
        fleet = snap["fleet"]
        for key in ("inflight", "queued", "shed", "retries", "hedges",
                    "replica_count", "draining"):
            assert key in fleet
        assert set(snap["replicas"]) == {"r0", "r1"}
        for r in snap["replicas"].values():
            for key in ("state", "outstanding", "requests", "errors",
                        "ejections", "latency"):
                assert key in r
            if r["latency"]["count"]:
                assert "p95_ms" in r["latency"]

        status, raw = _get(base, "/api/metrics?format=prometheus")
        assert status == 200
        text = raw.decode()
        assert "routest_fleet_inflight 0" in text
        assert 'routest_fleet_replica_requests{replica="r0"}' in text
        assert 'routest_fleet_replica_up{replica="r1"} 1' in text
        assert "# TYPE routest_fleet_shed counter" in text
        # pure renderer is label-escape safe
        assert _prometheus_fleet_text(snap).endswith("\n")
    finally:
        gw.drain(timeout=5)


# ── gateway: graceful drain ──────────────────────────────────────────

def test_drain_finishes_inflight_then_refuses():
    slow = _start_stub(delay_s=0.6)
    gw, base = _gateway([("127.0.0.1", slow.server_port)])
    try:
        done = []

        def long_request():
            done.append(_post(base, "/api/predict_eta", {}, timeout=30))

        t = threading.Thread(target=long_request)
        t.start()
        time.sleep(0.15)  # request is inside the replica
        gw.drain(timeout=10)
        t.join(timeout=10)
        assert done and done[0][0] == 200  # inflight request completed
        # listener is down now: new connections are refused
        with pytest.raises(Exception):
            _post(base, "/api/predict_eta", {}, timeout=2)
    finally:
        pass


# ── supervisor (multi-process, stub worker) ──────────────────────────

_STUB_WORKER = """
import http.server, json, os
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
        self._send(200, {"ok": True, "pid": os.getpid()})
    def do_POST(self):
        n = int(self.headers.get("Content-Length") or 0)
        self.rfile.read(n)
        self._send(200, {"eta_minutes_ml": 1.0, "pid": os.getpid()})
srv = http.server.ThreadingHTTPServer(("127.0.0.1", int(os.environ["PORT"])), H)
srv.daemon_threads = True
srv.serve_forever()
"""


def _stub_supervisor(n=1, **kw):
    ports = [_free_port() for _ in range(n)]
    sup = ReplicaSupervisor(
        ports, command=lambda p: [sys.executable, "-c", _STUB_WORKER],
        probe_interval_s=0.15, backoff_base_s=0.2, backoff_cap_s=1.0, **kw)
    return sup, ports


def _worker_pid(port):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/up",
                                timeout=2) as resp:
        return json.loads(resp.read())["pid"]


def test_supervisor_restarts_crashed_worker():
    sup, ports = _stub_supervisor()
    try:
        sup.start()
        assert sup.ready(timeout=30)
        pid1 = _worker_pid(ports[0])
        os.kill(pid1, signal.SIGKILL)
        deadline = time.time() + 30
        pid2 = None
        while time.time() < deadline:
            try:
                pid2 = _worker_pid(ports[0])
                if pid2 != pid1:
                    break
            except Exception:
                pass
            time.sleep(0.1)
        assert pid2 is not None and pid2 != pid1
        snap = sup.snapshot()["r0"]
        assert snap["alive"] and snap["restarts"] == 1
    finally:
        sup.drain(timeout=10)


def test_supervisor_backoff_is_capped_exponential():
    sup, _ = _stub_supervisor()
    r = sup._replicas[0]
    delays = []
    for crash in range(1, 12):
        r.consecutive_crashes = crash
        delays.append(sup._backoff_s(r))
    assert delays[0] == pytest.approx(0.2)
    assert delays[1] == pytest.approx(0.4)   # doubles …
    assert max(delays) == pytest.approx(1.0)  # … until the cap
    assert delays == sorted(delays)


def test_supervisor_drain_terminates_children():
    sup, ports = _stub_supervisor(n=2)
    try:
        sup.start()
        assert sup.ready(timeout=30)
        pids = [_worker_pid(p) for p in ports]
        sup.drain(timeout=10)
        for pid in pids:
            # ESRCH means gone; a zombie parented to us has been waited
            with pytest.raises(ProcessLookupError):
                os.kill(pid, 0)
        assert all(not s["alive"] for s in sup.snapshot().values())
    finally:
        sup.drain(timeout=5)


def test_gateway_plus_supervisor_ride_through_worker_kill():
    """Fault injection, hermetic: kill one stub worker mid-traffic. The
    gateway retries idempotent requests onto the survivor (zero client
    errors) and the supervisor brings the victim back."""
    sup, ports = _stub_supervisor(n=2)
    gw = None
    try:
        sup.start()
        assert sup.ready(timeout=30)
        gw = Gateway([("127.0.0.1", p) for p in ports],
                     FleetConfig(hedge=False, eject_after=2, cooldown_s=0.3),
                     supervisor=sup)
        httpd = gw.serve("127.0.0.1", 0)
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        victim = _worker_pid(ports[0])

        errors = []
        stop = threading.Event()

        def pump():
            while not stop.is_set():
                try:
                    status, _, _ = _post(base, "/api/predict_eta", {},
                                         timeout=10)
                    if status != 200:
                        errors.append(status)
                except Exception as e:
                    errors.append(str(e)[:60])
                time.sleep(0.01)

        t = threading.Thread(target=pump)
        t.start()
        time.sleep(0.3)
        os.kill(victim, signal.SIGKILL)
        # ride through the outage + restart window
        deadline = time.time() + 30
        recovered = False
        while time.time() < deadline:
            try:
                if _worker_pid(ports[0]) != victim:
                    recovered = True
                    break
            except Exception:
                pass
            time.sleep(0.1)
        stop.set()
        t.join(timeout=10)
        assert recovered, "supervisor never restarted the killed worker"
        assert not errors, f"client-visible errors during kill: {errors[:5]}"
        snap = gw.snapshot()
        assert snap["fleet"]["restarts"] >= 1
        assert snap["replicas"]["r0"]["supervisor"]["alive"]
    finally:
        if gw is not None:
            gw.drain(timeout=5)
        sup.drain(timeout=10)


# ── full stack (real serving workers) ────────────────────────────────

@pytest.mark.slow
def test_full_fleet_real_workers_end_to_end():
    """Two real ``python -m routest_tpu.serve`` replicas behind the
    gateway: predictions flow, metrics aggregate, and killing one
    replica mid-traffic stays client-invisible. >30 s (two server
    boots), hence slow-marked."""
    ports = [_free_port() for _ in range(2)]
    env = dict(os.environ)
    env.update({
        "ROUTEST_FORCE_CPU": "1",
        "ROUTEST_WARM_BUCKETS": "0",
        "ETA_MODEL_PATH": os.path.join(REPO, "artifacts",
                                       "eta_mlp.msgpack"),
    })
    sup = ReplicaSupervisor(
        ports, env=env, cwd=REPO, probe_interval_s=0.5,
        backoff_base_s=0.2, backoff_cap_s=2.0)
    gw = None
    try:
        sup.start()
        assert sup.ready(timeout=240), "serving workers never became ready"
        gw = Gateway([("127.0.0.1", p) for p in ports],
                     FleetConfig(hedge=True, hedge_min_ms=80.0,
                                 eject_after=2, cooldown_s=0.5),
                     supervisor=sup)
        httpd = gw.serve("127.0.0.1", 0)
        base = f"http://127.0.0.1:{httpd.server_address[1]}"

        payload = {"summary": {"distance": 12_000}, "weather": "Sunny",
                   "traffic": "Medium", "driver_age": 35,
                   "pickup_time": "2026-07-29T18:00:00"}
        for _ in range(8):
            status, body, _ = _post(base, "/api/predict_eta", payload,
                                    timeout=60)
            assert status == 200 and body["eta_minutes_ml"] > 0

        # fleet metrics over real workers
        status, raw = _get(base, "/api/metrics")
        snap = json.loads(raw)
        assert status == 200 and snap["fleet"]["replica_count"] == 2

        # kill one replica mid-traffic; requests keep succeeding
        victim_proc = sup._replicas[0].proc
        victim_proc.kill()
        for _ in range(8):
            status, body, _ = _post(base, "/api/predict_eta", payload,
                                    timeout=60)
            assert status == 200
    finally:
        if gw is not None:
            gw.drain(timeout=5)
        sup.drain(timeout=15)
