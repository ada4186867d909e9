"""Dynamic fleet membership + the autoscaler control loop, hermetic.

The ISSUE-6 contract for elastic fleets: replicas can join and leave a
LIVE gateway without a single client-visible error — joins enter
through the half-open probe path (one probe request, then rotation),
leaves drain outstanding work before the upstream is dropped. The
policy tests drive ``Autoscaler.decide`` with synthetic ``Signals`` so
hysteresis/cooldown/bounds are pinned without any processes; the
integration tests run the real control loop over stub multi-process
workers (same harness as ``tests/test_fleet.py``).
"""

import http.server
import json
import socket
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from routest_tpu.core.config import AutoscaleConfig, FleetConfig
from routest_tpu.serve.fleet.autoscaler import Autoscaler, Signals
from routest_tpu.serve.fleet.gateway import Gateway
from routest_tpu.serve.fleet.supervisor import ReplicaSupervisor

# ── stub replica (in-process, controllable) ──────────────────────────


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
        self._send(200, {"ok": True, "port": self.server.server_port})

    def do_POST(self):
        n = int(self.headers.get("Content-Length") or 0)
        self.rfile.read(n)
        srv = self.server
        if srv.delay_s:
            time.sleep(srv.delay_s)
        with srv.counter_lock:
            srv.hits += 1
        self._send(200, {"eta_minutes_ml": 1.0, "port": srv.server_port})


def _start_stub(delay_s=0.0):
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _StubHandler)
    srv.daemon_threads = True
    srv.delay_s = delay_s
    srv.hits = 0
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


def _post(base, path, payload, timeout=15.0):
    req = urllib.request.Request(
        f"{base}{path}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


# ── gateway: dynamic registration ────────────────────────────────────

def test_add_replica_enters_half_open_then_joins_rotation():
    s1, s2 = _start_stub(), _start_stub()
    gw, base = _gateway([("127.0.0.1", s1.server_port)])
    try:
        rid = gw.add_replica("127.0.0.1", s2.server_port)
        assert rid == "r1"
        snap = gw.snapshot()["replicas"][rid]
        assert snap["state"] == "half_open"     # probation, not trusted
        # Traffic: the newcomer gets exactly one probe, a success
        # admits it, and then both replicas serve.
        for _ in range(20):
            status, _ = _post(base, "/api/predict_eta", {})
            assert status == 200
        assert gw.snapshot()["replicas"][rid]["state"] == "closed"
        assert s2.hits > 0 and s1.hits > 0
    finally:
        gw.drain(timeout=5)


def test_add_replica_rejects_duplicate_id_and_mints_monotonic():
    s1 = _start_stub()
    gw, _ = _gateway([("127.0.0.1", s1.server_port)])
    try:
        with pytest.raises(ValueError, match="already registered"):
            gw.add_replica("127.0.0.1", 1, rid="r0")
        assert gw.add_replica("127.0.0.1", 2, rid="r7") == "r7"
        # the fallback namer never reuses an id seen via explicit rid
        assert gw.add_replica("127.0.0.1", 3) == "r8"
    finally:
        gw.drain(timeout=5)


def test_remove_replica_drains_outstanding_before_dropping():
    slow = _start_stub(delay_s=0.6)
    fast = _start_stub()
    gw, base = _gateway([("127.0.0.1", slow.server_port),
                         ("127.0.0.1", fast.server_port)])
    try:
        results = []

        def one():
            results.append(_post(base, "/api/predict_eta", {}, timeout=10))

        # Land one request on the slow replica, then remove it while
        # that request is inflight: the drain must let it finish.
        t = threading.Thread(target=one)
        t.start()
        deadline = time.time() + 3
        while time.time() < deadline:
            with gw._lock:
                if any(r.outstanding > 0 and r.port == slow.server_port
                       for r in gw.replicas):
                    break
            time.sleep(0.01)
        assert gw.remove_replica("r0", timeout=5.0)
        t.join(timeout=10)
        assert results and results[0][0] == 200
        ids = {r.id for r in gw.replicas}
        assert ids == {"r1"}
        # removed id is unknown now
        assert gw.remove_replica("r0") is False
        # remaining traffic flows on the survivor only
        status, _ = _post(base, "/api/predict_eta", {})
        assert status == 200
    finally:
        gw.drain(timeout=5)


def test_draining_replica_receives_no_new_picks():
    s1, s2 = _start_stub(), _start_stub()
    gw, base = _gateway([("127.0.0.1", s1.server_port),
                         ("127.0.0.1", s2.server_port)])
    try:
        with gw._lock:
            gw.replicas[0].draining = True
        before = s1.hits
        for _ in range(10):
            status, _ = _post(base, "/api/predict_eta", {})
            assert status == 200
        assert s1.hits == before        # all 10 went to r1
        assert s2.hits >= 10
    finally:
        gw.drain(timeout=5)


# ── supervisor: elastic membership (multi-process) ───────────────────

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


def test_supervisor_add_then_remove_replica():
    sup, _ = _stub_supervisor(n=1)
    try:
        sup.start()
        assert sup.ready(timeout=30)
        index, port = sup.add_replica()
        assert index == 1                       # monotonic, not reused
        assert sup.wait_port_ready(port, timeout=30)
        assert sup.replica_count() == 2
        assert sup.remove_replica(index, timeout=10)
        assert sup.replica_count() == 1
        # the retired worker is actually gone (connection refused)
        with pytest.raises(Exception):
            urllib.request.urlopen(f"http://127.0.0.1:{port}/up",
                                   timeout=2)
        # unknown/already-retired index → False, not an exception
        assert sup.remove_replica(index) is False
        # indices keep advancing after a removal
        index2, port2 = sup.add_replica()
        assert index2 == 2
        assert sup.wait_port_ready(port2, timeout=30)
    finally:
        sup.drain(timeout=10)


def test_supervisor_scale_to_grows_and_shrinks_lifo():
    sup, _ = _stub_supervisor(n=1)
    try:
        sup.start()
        assert sup.ready(timeout=30)
        out = sup.scale_to(3)
        assert [i for i, _ in out["added"]] == [1, 2]
        for _, port in out["added"]:
            assert sup.wait_port_ready(port, timeout=30)
        assert sup.replica_count() == 3
        out = sup.scale_to(1)
        # newest first: r2 retired before r1, r0 untouched
        assert [i for i, _ in out["removed"]] == [2, 1]
        assert sup.replica_count() == 1
        assert "r0" in sup.snapshot()
    finally:
        sup.drain(timeout=10)


def test_add_remove_replica_under_live_traffic_zero_errors():
    """THE membership contract: grow the fleet, then shrink it, while a
    client pumps requests the whole time — zero client-visible
    errors. Hermetic multi-process (stub workers), real gateway."""
    sup, ports = _stub_supervisor(n=1)
    gw = None
    try:
        sup.start()
        assert sup.ready(timeout=30)
        gw = Gateway([("127.0.0.1", ports[0])],
                     FleetConfig(hedge=False, eject_after=2,
                                 cooldown_s=0.3),
                     supervisor=sup)
        httpd = gw.serve("127.0.0.1", 0)
        base = f"http://127.0.0.1:{httpd.server_address[1]}"

        errors = []
        stop = threading.Event()

        def pump():
            while not stop.is_set():
                try:
                    status, _ = _post(base, "/api/predict_eta", {},
                                      timeout=10)
                    if status != 200:
                        errors.append(status)
                except Exception as e:
                    errors.append(str(e)[:60])
                time.sleep(0.005)

        t = threading.Thread(target=pump)
        t.start()
        time.sleep(0.3)
        # grow: spawn → startup probe → register (half-open entry)
        index, port = sup.add_replica()
        assert sup.wait_port_ready(port, timeout=30)
        rid = gw.add_replica("127.0.0.1", port, rid=f"r{index}")
        time.sleep(0.7)             # both serve for a beat
        with gw._lock:
            new_up = next(r for r in gw.replicas if r.id == rid)
            assert new_up.requests > 0      # it actually took traffic
        # shrink: deregister (drain) FIRST, then stop the process
        assert gw.remove_replica(rid, timeout=10)
        assert sup.remove_replica(index, timeout=10)
        time.sleep(0.5)             # survivor carries on alone
        stop.set()
        t.join(timeout=10)
        assert not errors, f"client errors during scale events: {errors[:5]}"
        assert [r.id for r in gw.replicas] == ["r0"]
    finally:
        if gw is not None:
            gw.drain(timeout=5)
        sup.drain(timeout=10)


# ── autoscaler: policy (synthetic signals, no processes) ─────────────

class _Obj:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def _policy_scaler(**cfg):
    defaults = dict(enabled=True, min_replicas=1, max_replicas=4,
                    tick_s=0.1, up_queue_frac=0.25, up_outstanding=8.0,
                    up_burn=6.0, up_stable_ticks=2, up_step=1,
                    up_cooldown_s=10.0, down_outstanding=1.0,
                    down_stable_ticks=3, down_step=1,
                    down_cooldown_s=30.0)
    defaults.update(cfg)
    return Autoscaler(_Obj(), _Obj(), AutoscaleConfig(**defaults))


def _sig(replicas=1, pending=0, queued=0, queue_depth=64, inflight=0,
         max_inflight=32, outstanding=0, burn_fast=0.0):
    return Signals(replicas=replicas, pending=pending, queued=queued,
                   queue_depth=queue_depth, inflight=inflight,
                   max_inflight=max_inflight, outstanding=outstanding,
                   burn_fast=burn_fast)


def test_policy_up_requires_stable_ticks():
    sc = _policy_scaler(up_stable_ticks=3)
    hot = _sig(queued=32)                       # queue half full
    assert sc.decide(hot, now=0.0) is None      # tick 1
    assert sc.decide(hot, now=1.0) is None      # tick 2
    assert sc.decide(hot, now=2.0) == "up"      # tick 3: stable
    # one quiet tick resets the streak
    sc2 = _policy_scaler(up_stable_ticks=3)
    sc2.decide(hot, now=0.0)
    sc2.decide(_sig(), now=1.0)
    sc2.decide(hot, now=2.0)
    assert sc2.decide(hot, now=3.0) is None     # streak restarted


def test_policy_pressure_is_or_quiet_is_and():
    sc = _policy_scaler()
    assert sc.pressure(_sig(queued=32))                       # queue
    assert sc.pressure(_sig(outstanding=9))                   # outstanding
    assert sc.pressure(_sig(burn_fast=7.0))                   # burn
    assert not sc.pressure(_sig(queued=1, outstanding=2))
    assert sc.quiet(_sig())
    # ANY lingering signal blocks quiet (AND-semantics)
    assert not sc.quiet(_sig(queued=1))
    assert not sc.quiet(_sig(outstanding=2))
    assert not sc.quiet(_sig(burn_fast=6.5))


def test_policy_bounds_and_pending_count_toward_max():
    sc = _policy_scaler(max_replicas=2, up_stable_ticks=1)
    assert sc.decide(_sig(replicas=2, queued=32), now=0.0) is None
    # a booting (pending) replica is capacity already ordered
    sc2 = _policy_scaler(max_replicas=2, up_stable_ticks=1)
    assert sc2.decide(_sig(replicas=1, pending=1, queued=32),
                      now=0.0) is None
    sc3 = _policy_scaler(max_replicas=2, up_stable_ticks=1)
    assert sc3.decide(_sig(replicas=1, queued=32), now=0.0) == "up"


def test_policy_down_needs_quiet_streak_min_bound_and_no_pending():
    sc = _policy_scaler(down_stable_ticks=2, min_replicas=1)
    calm = _sig(replicas=3)
    assert sc.decide(calm, now=0.0) is None
    assert sc.decide(calm, now=1.0) == "down"
    # at min_replicas: never down
    sc2 = _policy_scaler(down_stable_ticks=1, min_replicas=1)
    assert sc2.decide(_sig(replicas=1), now=0.0) is None
    # a pending join blocks down (do not retire while growing)
    sc3 = _policy_scaler(down_stable_ticks=1)
    assert sc3.decide(_sig(replicas=3, pending=1), now=0.0) is None


def test_policy_cooldowns_gate_each_direction():
    sc = _policy_scaler(up_stable_ticks=1, up_cooldown_s=10.0)
    hot = _sig(queued=32)
    assert sc.decide(hot, now=0.0) == "up"
    sc._last_up = 0.0               # as _scale_up would stamp
    sc._up_ticks = 0
    assert sc.decide(hot, now=5.0) is None      # inside cooldown
    assert sc.decide(hot, now=10.0) == "up"     # cooldown lapsed


# ── autoscaler: end-to-end over stub workers ─────────────────────────

def test_autoscaler_scales_stub_fleet_up_and_down():
    """The full loop, hermetic: pressure (slow upstream + queued
    clients) → scale-up decision → stub worker spawned, probed, and
    registered half-open → quiet → drain-then-stop back to min."""
    sup, ports = _stub_supervisor(n=1)
    gw = None
    scaler = None
    try:
        sup.start()
        assert sup.ready(timeout=30)
        gw = Gateway([("127.0.0.1", ports[0])],
                     FleetConfig(hedge=False, max_inflight=2,
                                 queue_depth=8),
                     supervisor=sup)
        httpd = gw.serve("127.0.0.1", 0)
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        scaler = Autoscaler(sup, gw, AutoscaleConfig(
            enabled=True, min_replicas=1, max_replicas=2, tick_s=0.1,
            up_queue_frac=0.25, up_outstanding=4.0, up_burn=999.0,
            up_stable_ticks=1, up_step=1, up_cooldown_s=0.5,
            down_outstanding=1.0, down_stable_ticks=3,
            down_cooldown_s=0.5, startup_timeout_s=60.0,
            drain_timeout_s=5.0))
        assert gw.autoscaler is scaler

        # Occupy the fleet: burst of concurrent posts against
        # max_inflight=2 queues the rest → queue_frac pressure.
        stop = threading.Event()

        def pump():
            while not stop.is_set():
                try:
                    _post(base, "/api/predict_eta", {}, timeout=10)
                except Exception:
                    pass

        pumps = [threading.Thread(target=pump) for _ in range(6)]
        for t in pumps:
            t.start()
        try:
            # Tick synchronously (deterministic): pressure must decide
            # "up", then the pending worker boots and joins.
            deadline = time.time() + 30
            joined = False
            while time.time() < deadline and not joined:
                scaler.tick()
                with gw._lock:
                    joined = len(gw.replicas) == 2
                time.sleep(0.05)
            assert joined, "autoscaler never grew the stub fleet"
            assert any(h.get("phase") == "joined"
                       for h in scaler.snapshot()["history"])
        finally:
            stop.set()
            for t in pumps:
                t.join(timeout=10)
        # Quiet: outstanding drains to zero → down decision retires
        # the newcomer (drain-then-stop) back to min_replicas.
        deadline = time.time() + 30
        shrunk = False
        while time.time() < deadline and not shrunk:
            scaler.tick()
            with gw._lock:
                shrunk = len(gw.replicas) == 1
            time.sleep(0.05)
        assert shrunk, "autoscaler never scaled back down"
        assert sup.replica_count() == 1
        hist = scaler.snapshot()["history"]
        assert any(h.get("direction") == "down"
                   and h.get("phase") == "stopped" for h in hist)
        # the metrics families recorded both directions
        from routest_tpu.obs import get_registry

        fams = get_registry().snapshot()
        decisions = {s["labels"]["direction"]: s["value"]
                     for s in fams["rtpu_autoscale_decisions_total"]
                     ["series"]}
        assert decisions.get("up", 0) >= 1
        assert decisions.get("down", 0) >= 1
    finally:
        if scaler is not None:
            scaler.stop()
        if gw is not None:
            gw.drain(timeout=5)
        sup.drain(timeout=10)
