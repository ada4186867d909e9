"""Multi-region active-active: bridge loop suppression, geo-front
routing / failover / write journal, region-labelled rollups, and the
cross-region fan-out prober's ``reach`` dimension."""

import http.server
import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from routest_tpu.chaos import ChaosEngine, configure
from routest_tpu.core.config import (FleetConfig, ProberConfig,
                                     RegionConfig, load_region_config)
from routest_tpu.live.bridge import ProbeBridge
from routest_tpu.serve.bus import InMemoryBus
from routest_tpu.serve.fleet.geofront import (GeoFront, RegionHandle,
                                              REPLICATED_POSTS)


def _get(url, timeout=5.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read()), dict(r.headers.items())


def _post(url, body, timeout=5.0):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read()), dict(r.headers.items())


def _wait(pred, timeout=10.0, step=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(step)
    return False


# ── probe-bus bridge ─────────────────────────────────────────────────


def _frame(i=0):
    return {"t": time.time(), "driver": f"d{i}", "obs": [[i, 5.0]]}


def test_bridge_stamps_origin_and_suppresses_return():
    bus_a, bus_b = InMemoryBus(), InMemoryBus()
    ab = ProbeBridge("a", "b", bus_a, bus_b)
    ba = ProbeBridge("b", "a", bus_b, bus_a)
    sub_b = bus_b.subscribe(ab.channel)
    assert ab.handle(_frame()) is True
    bridged = sub_b.get(timeout=1.0)
    assert bridged["origin_region"] == "a"
    # the return leg drops the frame: A→B→A cannot amplify
    assert ba.handle(bridged) is False
    assert ba.dropped == 1
    sub_b.close()


def test_bridge_three_ring_forwards_transitively_then_terminates():
    buses = {n: InMemoryBus() for n in "abc"}
    ab = ProbeBridge("a", "b", buses["a"], buses["b"])
    bc = ProbeBridge("b", "c", buses["b"], buses["c"])
    ca = ProbeBridge("c", "a", buses["c"], buses["a"])
    sub_b = buses["b"].subscribe(ab.channel)
    sub_c = buses["c"].subscribe(ab.channel)
    assert ab.handle(_frame()) is True          # a → b (stamped a)
    hop1 = sub_b.get(timeout=1.0)
    assert hop1["origin_region"] == "a"
    assert bc.handle(hop1) is True              # b → c (foreign origin)
    hop2 = sub_c.get(timeout=1.0)
    assert hop2["origin_region"] == "a"
    assert ca.handle(hop2) is False             # back where it began
    sub_b.close()
    sub_c.close()


def test_bridge_ring_regression_no_amplification():
    """Satellite regression: two LIVE bridges in a ring, N frames in,
    exactly N bridged frames out, nothing re-enters the source bus."""
    bus_a, bus_b = InMemoryBus(), InMemoryBus()
    ab = ProbeBridge("a", "b", bus_a, bus_b)
    ba = ProbeBridge("b", "a", bus_b, bus_a)
    sub_a = bus_a.subscribe(ab.channel)
    sub_b = bus_b.subscribe(ab.channel)
    ab.start()
    ba.start()
    try:
        n = 5
        for i in range(n):
            bus_a.publish(ab.channel, _frame(i))
        assert _wait(lambda: ab.forwarded == n)
        assert _wait(lambda: ba.dropped == n)
        time.sleep(0.2)                # amplification would show here
        assert ab.forwarded == n
        assert ba.forwarded == 0
        got_a = got_b = 0
        while sub_a.get(timeout=0.05) is not None:
            got_a += 1
        while sub_b.get(timeout=0.05) is not None:
            got_b += 1
        assert got_a == n              # originals only: nothing came back
        assert got_b == n              # each frame bridged exactly once
    finally:
        ab.stop()
        ba.stop()
        sub_a.close()
        sub_b.close()


def test_bridge_rejects_same_region_and_malformed():
    bus = InMemoryBus()
    with pytest.raises(ValueError):
        ProbeBridge("a", "a", bus, bus)
    ab = ProbeBridge("a", "b", InMemoryBus(), InMemoryBus())
    assert ab.handle("not a dict") is False
    assert ab.handle({"t": 1.0}) is False      # no obs
    assert ab.dropped == 2


def test_bridge_chaos_point_drops_one_frame():
    configure(ChaosEngine(spec="region.bridge:error=1.0@1", seed=7))
    try:
        ab = ProbeBridge("a", "b", InMemoryBus(), InMemoryBus())
        assert ab.handle(_frame(0)) is False   # injected drop
        assert ab.handle(_frame(1)) is True    # rule exhausted (@1)
    finally:
        configure(None)


# ── geo-front over stub regions ──────────────────────────────────────


class _StubRegion:
    """A minimal 'fleet gateway': /up, rollup surfaces, mutation
    capture, and the prober's fan-out endpoints."""

    def __init__(self, name: str, port: int = 0):
        self.name = name
        self.posts = []
        self.slo_state = "ok"
        stub = self

        class H(http.server.BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def _json(self, payload, status=200):
                data = json.dumps(payload).encode()
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self):
                bare = self.path.split("?", 1)[0]
                if bare == "/up":
                    return self._json({"status": "ok"})
                if bare == "/api/ping":
                    return self._json({"pong": True, "who": stub.name})
                if bare == "/api/live":
                    return self._json({"enabled": False})
                if bare == "/api/version":
                    return self._json(
                        {"model": {"fingerprint": "fp0", "generation": 1}})
                if bare == "/api/efficiency":
                    return self._json({
                        "region": stub.name,
                        "fleet": {"programs": {
                            "eta": {"rows": 10, "padded_rows": 12}}},
                        "replicas": {}})
                if bare == "/api/slo":
                    return self._json({"objectives": {
                        "availability": {"state": stub.slo_state}}})
                if bare == "/api/timeline":
                    return self._json({"scope": "fleet",
                                       "region": stub.name,
                                       "frames": [{"t": 1.0, "v": 1}]})
                self._json({"error": "not found"}, 404)

            def do_POST(self):
                length = int(self.headers.get("Content-Length") or 0)
                body = json.loads(self.rfile.read(length) or b"{}")
                bare = self.path.split("?", 1)[0]
                stub.posts.append((bare, body))
                if bare == "/api/predict_eta_batch":
                    n = len(body.get("weather") or [])
                    return self._json({"eta_minutes_ml": [10.0] * n})
                self._json({"status": "ok", "who": stub.name})

        self.httpd = http.server.ThreadingHTTPServer(
            ("127.0.0.1", port), H)
        self.httpd.daemon_threads = True
        self.port = self.httpd.server_address[1]
        self.base = f"http://127.0.0.1:{self.port}"
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, daemon=True)
        self._thread.start()

    def stop(self):
        if self.httpd is not None:
            self.httpd.shutdown()
            self.httpd.server_close()
            self.httpd = None


def _region_config(**over):
    kw = dict(enabled=True, regions=("east", "west"), default="east",
              health_s=0.05, unhealthy_after=2, failover=True,
              journal_limit=8, replay_s=0.05)
    kw.update(over)
    return RegionConfig(**kw)


@pytest.fixture()
def geo():
    east, west = _StubRegion("east"), _StubRegion("west")
    front = GeoFront(
        [RegionHandle("east", east.base), RegionHandle("west", west.base)],
        _region_config())
    front.serve("127.0.0.1", 0)
    assert _wait(lambda: front.healthy("east") and front.healthy("west"))
    yield front, east, west
    front.drain(timeout=2.0)
    east.stop()
    west.stop()


def test_front_routes_by_query_and_header(geo):
    front, east, west = geo
    payload, headers = _get(f"{front.base}/api/ping?region=west")
    assert payload["who"] == "west"
    assert headers["X-RTPU-Served-Region"] == "west"
    req = urllib.request.Request(f"{front.base}/api/ping",
                                 headers={"X-RTPU-Region": "west"})
    with urllib.request.urlopen(req, timeout=5.0) as r:
        assert json.loads(r.read())["who"] == "west"
    # no hint → default region
    payload, headers = _get(f"{front.base}/api/ping")
    assert headers["X-RTPU-Served-Region"] == "east"


def test_front_fails_over_and_503s_when_nothing_is_healthy(geo):
    front, east, west = geo
    west.stop()
    assert _wait(lambda: not front.healthy("west"))
    payload, headers = _get(f"{front.base}/api/ping?region=west")
    assert payload["who"] == "east"            # hinted-down → survivor
    assert headers["X-RTPU-Served-Region"] == "east"
    from routest_tpu.serve.fleet.geofront import _front_metrics

    m = _front_metrics()
    assert m["failover"].labels(src="west", dst="east").value >= 1
    east.stop()
    assert _wait(lambda: not front.healthy("east"))
    with pytest.raises(urllib.error.HTTPError) as exc:
        _get(f"{front.base}/api/ping")
    assert exc.value.code == 503


def test_front_journal_replays_into_rejoined_region(geo):
    front, east, west = geo
    port = west.port
    west.stop()
    assert _wait(lambda: not front.healthy("west"))
    body = {"route_id": "r1", "driver_name": "x"}
    payload, _ = _post(f"{front.base}/api/update_tracker?region=east",
                       body)
    assert payload["who"] == "east"
    assert front.journal_depth("west") == 1
    assert front.journal_depth("east") == 0    # home region not queued
    # region rejoins at the same address → journal drains, zero lost
    west2 = _StubRegion("west", port=port)
    try:
        assert _wait(lambda: front.healthy("west"))
        assert _wait(lambda: ("/api/update_tracker", body) in west2.posts)
        # the stub records the post before its answer is back, and the
        # replayer pops the journal's head only on the answer
        assert _wait(lambda: front.journal_depth("west") == 0)
    finally:
        west2.stop()


def test_front_journal_bounded_and_drops_counted(geo):
    front, east, west = geo
    west.stop()
    assert _wait(lambda: not front.healthy("west"))
    from routest_tpu.serve.fleet.geofront import _front_metrics

    dropped0 = _front_metrics()["journal_dropped"] \
        .labels(region="west").value
    n = front.config.journal_limit + 3
    for i in range(n):
        _post(f"{front.base}/api/confirm_route", {"i": i})
    assert front.journal_depth("west") == front.config.journal_limit
    assert _front_metrics()["journal_dropped"] \
        .labels(region="west").value == dropped0 + 3


def test_front_probe_posts_are_not_journaled(geo):
    front, east, west = geo
    assert "/api/probe" not in REPLICATED_POSTS
    _post(f"{front.base}/api/probe", {"driver": "d", "obs": [[1, 5.0]]})
    assert front.journal_depth("west") == 0


def test_front_merged_rollups_carry_region_labels(geo):
    front, east, west = geo
    eff, _ = _get(f"{front.base}/api/efficiency")
    assert set(eff["regions"]) == {"east", "west"}
    rows = eff["programs"]["eta"]
    assert sorted(r["region"] for r in rows) == ["east", "west"]
    only, _ = _get(f"{front.base}/api/efficiency?region=west")
    assert set(only["regions"]) == {"west"}
    tl, _ = _get(f"{front.base}/api/timeline?scope=region")
    assert {f["region"] for f in tl["frames"]} == {"east", "west"}
    west.slo_state = "page"
    slo, _ = _get(f"{front.base}/api/slo")
    assert slo["worst"] == "page"
    assert slo["worst_region"] == "west"


def test_front_up_and_regions_snapshot(geo):
    front, east, west = geo
    up, _ = _get(f"{front.base}/up")
    assert sorted(up["healthy_regions"]) == ["east", "west"]
    snap, _ = _get(f"{front.base}/api/regions")
    assert snap["component"] == "geofront"
    assert snap["regions"]["east"]["up"] is True
    assert snap["default"] == "east"


def test_kill_region_records_chaos_and_flips_health(geo):
    front, east, west = geo
    killed = []

    def _kill():
        killed.append("west")
        west.stop()                            # a real region loss

    front.by_name["west"].kill = _kill
    from routest_tpu.chaos import _INJECTIONS

    child = _INJECTIONS.labels(point="region.kill", kind="kill")
    before = child.value
    front.kill_region("west")
    assert killed == ["west"]
    assert child.value == before + 1
    assert not front.healthy("west")           # no poller round needed
    payload, _ = _get(f"{front.base}/api/ping?region=west")
    assert payload["who"] == "east"


# ── cross-region fan-out prober: the reach dimension ─────────────────


def test_prober_reach_dimension_names_dead_region():
    from routest_tpu.obs.prober import PASS, SKEW, BlackboxProber

    east = _StubRegion("east")
    try:
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            dead = f"http://127.0.0.1:{s.getsockname()[1]}"
        targets = [("east", east.base), ("west", dead)]
        cfg = ProberConfig(enabled=True, fanout_reach=True,
                           skew_after=1, timeout_s=2.0, interval_s=60.0)
        prober = BlackboxProber(cfg, gateway_base=east.base,
                                targets_fn=lambda: targets)
        verdict, evidence = prober._probe_fanout(targets)
        assert verdict == SKEW
        reach = evidence["dimensions"]["reach"]
        assert reach["replicas"] == ["west"]
        assert "west" in reach["errors"]
        # default mode (fanout_reach off): same topology stays PASS —
        # single-fleet fan-out must not page on one unreachable replica
        legacy = BlackboxProber(
            ProberConfig(enabled=True, skew_after=1, timeout_s=2.0,
                         interval_s=60.0),
            gateway_base=east.base, targets_fn=lambda: targets)
        verdict, _ = legacy._probe_fanout(targets)
        assert verdict == PASS
    finally:
        east.stop()


# ── region labels + config plumbing ──────────────────────────────────


def test_gateway_snapshot_carries_region_label():
    from routest_tpu.serve.fleet.gateway import Gateway

    gw = Gateway([("127.0.0.1", 9)], FleetConfig(region="east"))
    assert gw.snapshot()["fleet"]["region"] == "east"
    bare = Gateway([("127.0.0.1", 9)], FleetConfig())
    assert "region" not in bare.snapshot()["fleet"]


def test_load_region_config_parses_and_dedupes():
    rc = load_region_config({"RTPU_REGIONS": " east, west ,east ",
                             "RTPU_REGION_STALE_BOUND_S": "45"})
    assert rc.enabled
    assert rc.regions == ("east", "west")
    assert rc.default == "east"
    assert rc.stale_bound_s == 45.0
    assert not load_region_config({"RTPU_REGIONS": "solo"}).enabled
    assert not load_region_config({}).enabled


def test_geofront_requires_two_distinct_regions():
    with pytest.raises(ValueError):
        GeoFront([RegionHandle("a", "http://127.0.0.1:1")])
    with pytest.raises(ValueError):
        GeoFront([RegionHandle("a", "http://127.0.0.1:1"),
                  RegionHandle("a", "http://127.0.0.1:2")])


# ── loadgen region affinity ──────────────────────────────────────────


def test_loadgen_region_affinity_skewed_and_deterministic():
    from collections import Counter

    from routest_tpu.loadgen.workload import MixedWorkload

    wl = MixedWorkload(seed=3, regions=("east", "west", "south"))
    seq = wl.sequence(400)
    assert all("region=" in r.path for r in seq)
    counts = Counter(r.path.rsplit("region=", 1)[1] for r in seq)
    # Zipf skew: the hot region carries strictly more than the tail
    assert counts["east"] > counts["west"] > 0
    assert counts["east"] > counts["south"] > 0
    # report labels stay query-free
    assert all("region=" not in r.route for r in seq)
    # deterministic per (params, seed)
    again = MixedWorkload(seed=3, regions=("east", "west", "south"))
    assert [r.path for r in again.sequence(400)] == \
        [r.path for r in seq]
    # existing query strings extend with '&', not a second '?'
    history = [r for r in seq if r.route == "/api/history"]
    assert all("?limit=10&region=" in r.path for r in history)
    assert wl.describe()["regions"] == ["east", "west", "south"]
    # no regions configured → paths untouched
    plain = MixedWorkload(seed=3).sequence(50)
    assert all("region=" not in r.path for r in plain)
