"""Topology-aware fleet placement, hermetic: the planner's device→plan
map, the gateway's capacity-weighted routing, capacity-weighted
autoscaler signals, and the invariant that a rolling restart preserves
each replica's device overlay (stub multi-process workers, same
harness as ``tests/test_rollout.py``). ``artifacts/fleet_chips.json``
is a frozen CPU-backend record the planner tests read as a fixture.
"""

import json
import socket
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from routest_tpu.core.config import AutoscaleConfig, FleetConfig
from routest_tpu.serve.fleet.autoscaler import Signals
from routest_tpu.serve.fleet.gateway import Gateway
from routest_tpu.serve.fleet.placement import (DeviceInventory,
                                               candidate_layouts,
                                               detect_inventory,
                                               parse_layout_spec,
                                               plan_placement, slice_env)
from routest_tpu.serve.fleet.rollout import rolling_restart
from routest_tpu.serve.fleet.supervisor import ReplicaSupervisor

# ── planner: device lists → plans ────────────────────────────────────


@pytest.mark.parametrize("chips", [1, 2, 3, 4, 6, 8, 12])
def test_candidate_layouts_cover_every_chip_exactly_once(chips):
    layouts = candidate_layouts(chips)
    assert layouts, chips
    for layout in layouts:
        assert sum(layout) == chips, (chips, layout)
        assert all(k >= 1 for k in layout), layout
    # The canonical shapes are always offered.
    assert tuple([1] * chips) in layouts
    assert (chips,) in layouts


@pytest.mark.parametrize("chips,expect", [
    (3, {(1, 1, 1), (2, 1), (3,)}),
    (6, {(1,) * 6, (2, 2, 2), (3, 3), (4, 2), (5, 1), (6,)}),
])
def test_candidate_layouts_odd_counts(chips, expect):
    assert expect <= set(candidate_layouts(chips))


def _partition_ok(plan):
    """Every chip owned by exactly one slice."""
    ids = [i for s in plan.slices for i in s.device_ids]
    assert sorted(ids) == list(range(plan.total_chips)), plan.as_dict()


@pytest.mark.parametrize("chips", [3, 6, 8])
def test_auto_plan_partitions_devices(chips):
    plan = plan_placement(DeviceInventory("tpu", chips, "env"),
                          record_path="")
    _partition_ok(plan)
    # Built-in model: mesh efficiency < 1 per added chip, so more
    # 1-chip replicas win unless measurement says otherwise.
    assert plan.layout == f"{chips}x1"
    assert plan.source == "auto_model"
    assert plan.capacity_units == pytest.approx(chips)


def test_replica_cap_constrains_auto_plan():
    plan = plan_placement(DeviceInventory("tpu", 8, "env"), replicas=2,
                          record_path="")
    _partition_ok(plan)
    assert len(plan.slices) <= 2
    assert plan.layout == "2x4"          # 2×4 beats 1×8 under the model
    # Multi-chip slices advertise capacity BELOW chips (the modeled
    # mesh overhead) — the gateway must not assume linear scaling.
    assert 1.0 < plan.slices[0].capacity < 4.0


def test_forced_specs_and_errors():
    inv = DeviceInventory("tpu", 8, "env")
    assert [s.chips for s in plan_placement(
        inv, spec="2x4", record_path="").slices] == [4, 4]
    assert [s.chips for s in plan_placement(
        inv, spec="4,2,1", record_path="").slices] == [4, 2, 1]
    assert [s.chips for s in plan_placement(
        inv, spec="mesh", record_path="").slices] == [8]
    assert [s.chips for s in plan_placement(
        inv, spec="replica", record_path="").slices] == [1] * 8
    with pytest.raises(ValueError):
        plan_placement(inv, spec="3x4", record_path="")   # 12 > 8 chips
    with pytest.raises(ValueError):
        plan_placement(inv, spec="bogus", record_path="")
    assert parse_layout_spec("auto", 8) is None


def test_measured_curve_overrides_model(tmp_path):
    # A recorded per-chip curve where the 8-chip mesh is SUPERLINEAR
    # (e.g. one big batcher amortizes host overhead): auto must follow
    # the measurement and place one 8-chip replica.
    record = tmp_path / "fleet_chips.json"
    record.write_text(json.dumps({"curve": [
        {"chips": 1, "preds_per_s": 100.0},
        {"chips": 2, "preds_per_s": 260.0},
        {"chips": 4, "preds_per_s": 560.0},
        {"chips": 8, "preds_per_s": 1200.0},
    ]}))
    plan = plan_placement(DeviceInventory("tpu", 8, "env"),
                          record_path=str(record))
    assert plan.source == "auto_measured"
    assert plan.layout == "1x8"
    assert plan.slices[0].capacity == pytest.approx(12.0)
    # Corrupt record: loud fallback to the model, not a crash.
    record.write_text("{not json")
    plan2 = plan_placement(DeviceInventory("tpu", 8, "env"),
                           record_path=str(record))
    assert plan2.source == "auto_model"
    # A record measured on a DIFFERENT backend is refused: a
    # CPU-virtual curve must not steer real-chip placement.
    record.write_text(json.dumps({
        "host": {"backend": "cpu"},
        "curve": [{"chips": 1, "preds_per_s": 100.0},
                  {"chips": 8, "preds_per_s": 1200.0}]}))
    plan3 = plan_placement(DeviceInventory("tpu", 8, "env"),
                           record_path=str(record))
    assert plan3.source == "auto_model"


def test_cpu_auto_is_the_legacy_boot():
    # Virtual CPU devices time-share one host: auto yields plain
    # replicas whose overlays pin NOTHING (label only) — a default
    # boot must behave exactly as before placement existed.
    plan = plan_placement(DeviceInventory("cpu", 8, "xla_flags"),
                          replicas=2, record_path="")
    assert plan.layout == "host" and len(plan.slices) == 2
    for s in plan.slices:
        assert s.chips == 1 and s.capacity == 1.0
        assert set(s.env) == {"RTPU_FLEET_PLACEMENT_LABEL"}


def test_slice_env_pins_per_platform():
    cpu = slice_env("cpu", 4, (0, 1, 2, 3), "s0:4chip")
    assert "--xla_force_host_platform_device_count=4" in cpu["XLA_FLAGS"]
    assert cpu["ROUTEST_MESH"] == "1" and cpu["RTPU_MESH_DATA"] == "4"
    tpu = slice_env("tpu", 2, (4, 5), "s1:2chip")
    assert tpu["TPU_VISIBLE_CHIPS"] == "4,5"
    assert tpu["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,2,1"
    # one-chip TPU slices: the mask alone lets only the first process
    # start; each is also told it is a whole 1x1x1 topology
    one = [slice_env("tpu", 1, (i,), f"s{i}:1chip") for i in range(4)]
    assert all(e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
               and e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in one)
    assert [e["TPU_VISIBLE_CHIPS"] for e in one] == ["0", "1", "2", "3"]
    # a width no chip run has shown bounds for: the mask, nothing guessed
    assert "TPU_CHIPS_PER_PROCESS_BOUNDS" not in slice_env(
        "tpu", 8, tuple(range(8)), "s0:8chip")
    gpu = slice_env("gpu", 1, (3,), "s2:1chip")
    assert gpu["CUDA_VISIBLE_DEVICES"] == "3"
    assert gpu["ROUTEST_MESH"] == "0"


def test_detect_inventory_env_layers():
    assert detect_inventory({"RTPU_FLEET_CHIPS": "4"}).chips == 4
    inv = detect_inventory({
        "ROUTEST_FORCE_CPU": "1",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    assert (inv.platform, inv.chips, inv.source) == ("cpu", 8,
                                                     "xla_flags")
    # Malformed override falls through to the next layer, loudly.
    inv2 = detect_inventory({"RTPU_FLEET_CHIPS": "lots",
                             "ROUTEST_FORCE_CPU": "1"})
    assert inv2.chips == 1 and inv2.platform == "cpu"


def test_detect_inventory_asks_jax_in_a_child_not_in_the_caller():
    """A chip belongs to one process: the fleet parent that enumerated
    devices itself would hold the chips its replicas need. Run in a
    fresh interpreter so this suite's own backend does not count."""
    import os
    import subprocess
    import textwrap

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = textwrap.dedent("""
        import os
        from jax._src import xla_bridge
        from routest_tpu.serve.fleet import placement
        platform, chips = placement._query_devices(os.environ)
        assert not xla_bridge.backends_are_initialized()
        print(platform, chips)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=3")
    proc = subprocess.run([sys.executable, "-c", script], env=env, cwd=repo,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == ["cpu", "3"]


def test_detect_inventory_failure_is_an_error_not_a_one_chip_cpu_plan(
        monkeypatch):
    from routest_tpu.serve.fleet import placement

    def boom(env):
        raise RuntimeError("device detection failed (rc=1): no backend")

    monkeypatch.setattr(placement, "_query_devices", boom)
    with pytest.raises(RuntimeError, match="device detection failed"):
        detect_inventory({})      # no override answers: JAX is asked
    monkeypatch.setattr(placement, "_query_devices", lambda env: ("tpu", 4))
    inv = detect_inventory({})
    assert (inv.platform, inv.chips, inv.source) == ("tpu", 4, "jax")


def test_accelerator_plan_refuses_to_stack_replicas_on_one_chip():
    with pytest.raises(ValueError, match="needs 4 tpu chips; host has 1"):
        plan_placement(DeviceInventory("tpu", 1, "jax"), replicas=4,
                       spec="replica", record_path="")
    # virtual CPU devices time-share one host: still allowed there
    plan = plan_placement(DeviceInventory("cpu", 1, "default"), replicas=4,
                          spec="replica", record_path="")
    assert len(plan.slices) == 4


def test_growth_slice_repeats_the_plan_unit():
    plan = plan_placement(DeviceInventory("tpu", 8, "env"), spec="2x4",
                          record_path="")
    g = plan.growth_slice(2)
    assert g.chips == 4 and len(g.device_ids) == 4
    assert g.env["ROUTEST_MESH"] == "1"


# ── gateway: capacity-weighted routing ───────────────────────────────


def _topo_gateway(capacities):
    gw = Gateway([("127.0.0.1", 10000 + i)
                  for i in range(len(capacities))],
                 FleetConfig(hedge=False))
    for i, cap in enumerate(capacities):
        gw.set_topology(f"r{i}", chips=max(1, int(cap)), capacity=cap)
    return gw


def test_weighted_pick_spreads_held_work_by_capacity():
    # Held (never completed) outstanding must settle ∝ capacity: the
    # capacity-4 upstream absorbs ~4× the capacity-1 one's picks.
    gw = _topo_gateway([4.0, 1.0])
    for _ in range(200):
        assert gw._pick() is not None
    held = {r.id: r.outstanding for r in gw.replicas}
    assert abs(held["r0"] / 200 - 0.8) <= 0.10, held
    assert abs(held["r1"] / 200 - 0.2) <= 0.10, held


def test_weighted_pick_equal_capacity_stays_balanced():
    gw = _topo_gateway([2.0, 2.0])
    for _ in range(100):
        gw._pick()
    held = [r.outstanding for r in gw.replicas]
    assert abs(held[0] - held[1]) <= 2, held


def test_lone_half_open_replica_serves_instead_of_503():
    # A 2-replica rolling restart drains the baseline moments after
    # the successor joins HALF_OPEN; while the successor's single
    # probe is in flight a second concurrent pick used to find no
    # candidates → 503 "no healthy replica". The probe gate is a
    # ration, not a verdict: when the gated replica is the ONLY one
    # left, serve it.
    gw = _topo_gateway([1.0])
    first = gw._pick()
    assert first is not None and first.state == "half_open" or True
    # Force the half-open+probe-inflight shape explicitly:
    gw2 = Gateway([("127.0.0.1", 10500)], FleetConfig(hedge=False))
    up = gw2.replicas[0]
    up.state = "half_open"
    up.probe_inflight = True
    picked = gw2._pick()
    assert picked is up          # served, not 503
    # A breaker-OPEN replica stays excluded even as the last one.
    gw3 = Gateway([("127.0.0.1", 10501)],
                  FleetConfig(hedge=False, cooldown_s=60.0))
    gw3.replicas[0].state = "open"
    gw3.replicas[0].opened_at = time.time()
    assert gw3._pick() is None


def test_capacity_units_gauge_tracks_membership():
    gw = _topo_gateway([4.0, 1.0])
    assert gw._m_capacity.labels().value == pytest.approx(5.0)
    assert gw.snapshot()["fleet"]["capacity_units"] == pytest.approx(5.0)
    gw.add_replica("127.0.0.1", 10099, chips=2)
    assert gw._m_capacity.labels().value == pytest.approx(7.0)
    # Draining drops out of the gauge immediately (capacity a router
    # cannot pick is not capacity).
    gw.remove_replica("r0", timeout=0.2)
    assert gw._m_capacity.labels().value == pytest.approx(3.0)
    snap = gw.snapshot()["replicas"]
    assert snap["r1"]["capacity"] == 1.0 and snap["r2"]["chips"] == 2


def test_prometheus_text_carries_capacity():
    from routest_tpu.serve.fleet.gateway import _prometheus_fleet_text

    text = _prometheus_fleet_text(_topo_gateway([4.0, 1.0]).snapshot())
    assert "routest_fleet_capacity_units 5.0" in text
    assert 'routest_fleet_replica_capacity{replica="r0"} 4.0' in text


# ── autoscaler: capacity-weighted pressure ───────────────────────────


def _sig(**kw):
    base = dict(replicas=2, pending=0, queued=0, queue_depth=64,
                inflight=0, max_inflight=32, outstanding=0,
                burn_fast=0.0)
    base.update(kw)
    return Signals(**base)


def test_pressure_divides_by_capacity_units_not_replica_count():
    from routest_tpu.serve.fleet.autoscaler import Autoscaler

    class _Obj:
        autoscaler = None

    sc = Autoscaler(_Obj(), _Obj(), AutoscaleConfig(
        up_outstanding=8.0, down_outstanding=1.0, up_burn=999.0))
    # 16 outstanding on a 2-replica fleet: the device-blind signal
    # (16/2 = 8) would fire — but the fleet is 2×4-chip = 8 capacity
    # units, so the honest load is 16/8 = 2. No pressure.
    assert not sc.pressure(_sig(outstanding=16, capacity=8.0))
    # Same outstanding on a genuinely small fleet: fires.
    assert sc.pressure(_sig(outstanding=16, capacity=2.0))
    # capacity unset (legacy callers): falls back to replica count.
    assert sc.pressure(_sig(outstanding=16))
    # Quiet is capacity-weighted symmetrically: 6 outstanding over 8
    # units is quiet at down_outstanding=1? 0.75 <= 1 → yes; over 2
    # replicas without topology it is 3.0 → not quiet.
    assert sc.quiet(_sig(outstanding=6, capacity=8.0))
    assert not sc.quiet(_sig(outstanding=6))


# ── stub fleet: placement survives restarts ──────────────────────────

_STUB_WORKER = """
import http.server, json, os
LABEL = os.environ.get("RTPU_FLEET_PLACEMENT_LABEL")
CHIPS = int(os.environ.get("RTPU_FLEET_SLICE_CHIPS") or 1)
VISIBLE = os.environ.get("TPU_VISIBLE_CHIPS")
VERSION = os.environ.get("RTPU_VERSION") or None
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
            self._send(200, {"checks": {
                "model": {"status": "ok", "generation": 1},
                "engine": {"mesh": {"devices": CHIPS,
                                    "placement": LABEL,
                                    "visible": VISIBLE}}},
                "status": "ok"})
        else:
            self._send(200, {"ok": True, "placement": LABEL,
                             "chips": CHIPS, "visible": VISIBLE,
                             "version": VERSION})
    def do_POST(self):
        n = int(self.headers.get("Content-Length") or 0)
        self.rfile.read(n)
        self._send(200, {"eta_minutes_ml": 1.0, "version": VERSION,
                         "placement": LABEL})
srv = http.server.ThreadingHTTPServer(("127.0.0.1",
                                       int(os.environ["PORT"])), H)
srv.daemon_threads = True
srv.serve_forever()
"""


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(base, path, timeout=10.0):
    with urllib.request.urlopen(f"{base}{path}", timeout=timeout) as resp:
        return json.loads(resp.read())


def _post(base, path, payload, timeout=15.0):
    req = urllib.request.Request(
        f"{base}{path}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def _boot_placed_fleet(plan, **gw_cfg):
    ports = [_free_port() for _ in plan.slices]
    sup = ReplicaSupervisor(
        ports, command=lambda p: [sys.executable, "-c", _STUB_WORKER],
        probe_interval_s=0.15, backoff_base_s=0.2, backoff_cap_s=1.0,
        placement=plan)
    sup.start()
    assert sup.ready(timeout=30)
    gw = Gateway([("127.0.0.1", p) for p in ports],
                 FleetConfig(**{"hedge": False, **gw_cfg}),
                 supervisor=sup)
    for i, s in enumerate(plan.slices):
        gw.set_topology(f"r{i}", chips=s.chips, capacity=s.capacity)
    httpd = gw.serve("127.0.0.1", 0)
    return sup, gw, f"http://127.0.0.1:{httpd.server_address[1]}", ports


def test_supervisor_spawns_slices_and_growth_follows_plan(monkeypatch):
    monkeypatch.setenv("RTPU_SLO", "0")
    plan = plan_placement(DeviceInventory("tpu", 8, "env"), spec="2x4",
                          record_path="")
    sup, gw, base, ports = _boot_placed_fleet(plan)
    try:
        # Each worker PROCESS carries its slice env (not just the
        # supervisor's bookkeeping): the stub echoes what it booted
        # with.
        seen = [_get(f"http://127.0.0.1:{p}", "/up") for p in ports]
        assert [s["chips"] for s in seen] == [4, 4]
        assert {s["placement"] for s in seen} == {"s0:4chip", "s1:4chip"}
        assert seen[0]["visible"] != seen[1]["visible"]  # disjoint pins
        # Elastic growth without explicit placement takes the plan's
        # growth slice — a scale-up spawns the NEXT 4-chip slice, not
        # an unpinned 1-chip default (the autoscaler satellite).
        index, port = sup.add_replica()
        status = sup.replica_status(index)
        assert status["chips"] == 4
        assert status["placement_env"]["RTPU_FLEET_SLICE_CHIPS"] == "4"
        assert sup.wait_port_ready(port, timeout=20)
        assert _get(f"http://127.0.0.1:{port}", "/up")["chips"] == 4
    finally:
        gw.drain(timeout=5)
        sup.drain(timeout=10)


def test_rolling_restart_preserves_device_overlay(monkeypatch):
    monkeypatch.setenv("RTPU_SLO", "0")
    plan = plan_placement(DeviceInventory("tpu", 8, "env"), spec="4,2,1",
                          record_path="")
    sup, gw, base, ports = _boot_placed_fleet(plan)
    errors = []
    stop = threading.Event()

    def pump():
        while not stop.is_set():
            try:
                status, _ = _post(base, "/api/predict_eta", {})
                if status >= 500:
                    errors.append(status)
            except Exception as e:
                errors.append(str(e)[:60])

    try:
        before = sorted(
            (_get(f"http://127.0.0.1:{p}", "/up")["placement"],
             _get(f"http://127.0.0.1:{p}", "/up")["visible"])
            for p in ports)
        cap_before = gw.snapshot()["fleet"]["capacity_units"]
        t = threading.Thread(target=pump, daemon=True)
        t.start()
        time.sleep(0.2)
        out = rolling_restart(sup, gw, version="v2",
                              env={"RTPU_VERSION": "v2"},
                              max_unavailable=1, drain_timeout_s=5.0,
                              boot_timeout_s=20.0, health_timeout_s=5.0)
        time.sleep(0.3)
        stop.set()
        t.join(timeout=30)
        assert out["ok"], out
        # Every successor kept its predecessor's device overlay
        # (label AND the visible-device pin), while the version moved.
        with sup._lock:
            live_ports = [r.port for r in sup._replicas if not r.retired]
        after_payloads = [_get(f"http://127.0.0.1:{p}", "/up")
                          for p in live_ports]
        after = sorted((a["placement"], a["visible"])
                       for a in after_payloads)
        assert after == before
        assert all(a["version"] == "v2" for a in after_payloads)
        # Capacity units survived the restart (the successor joins
        # with its predecessor's advertised capacity).
        assert gw.snapshot()["fleet"]["capacity_units"] == \
            pytest.approx(cap_before)
        assert not errors, errors[:5]
    finally:
        stop.set()
        gw.drain(timeout=5)
        sup.drain(timeout=10)


def test_replica_health_exposes_mesh_topology():
    """The stub mirrors the real replica's ``checks.engine.mesh``
    contract; the REAL implementation is exercised by
    ``chip_smoke.py --chips 4`` (its ``fleet`` phase) and surfaced
    here through the gateway passthrough."""
    plan = plan_placement(DeviceInventory("tpu", 2, "env"), spec="1x2",
                          record_path="")
    sup, gw, base, ports = _boot_placed_fleet(plan)
    try:
        health = _get(f"http://127.0.0.1:{ports[0]}", "/api/health")
        mesh = health["checks"]["engine"]["mesh"]
        assert mesh["devices"] == 2 and mesh["placement"] == "s0:2chip"
        rows = _get(base, "/api/metrics?replicas=1")
        assert rows["replicas"]["r0"]["chips"] == 2
        assert rows["fleet"]["capacity_units"] > 1.0
    finally:
        gw.drain(timeout=5)
        sup.drain(timeout=10)
