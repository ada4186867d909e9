"""Dispatch subsystem, hermetic: the fixed-shape time-window /
demand-spillover VRP kernel against its host oracles, cross-request
batch merging, the /api/dispatch serving surface, the re-optimization
loop's coherency rules (one epoch one pass, exactly the degraded,
chaos degrade-don't-fail), SSE plan_update delivery, the loadgen
``dispatch`` component's determinism, and the prober's ``dispatch``
kind."""

import dataclasses
import json
import threading

import jax
import numpy as np
import pytest
from werkzeug.test import Client

from routest_tpu import chaos
from routest_tpu.core.config import (Config, DispatchConfig, ServeConfig,
                                     load_dispatch_config)
from routest_tpu.core.dtypes import F32_POLICY
from routest_tpu.data.locations import SEED_LOCATIONS
from routest_tpu.dispatch import (DispatchBatcher, DispatchProblem,
                                  DispatchRegistry, ReoptLoop, plan_cost)
from routest_tpu.models.eta_mlp import EtaMLP
from routest_tpu.optimize.vrp import (NO_WINDOW, solve_host,
                                      solve_host_dispatch,
                                      solve_host_dispatch_batch)
from routest_tpu.serve.app import create_app
from routest_tpu.serve.bus import InMemoryBus
from routest_tpu.serve.ml_service import EtaService
from routest_tpu.train.checkpoint import save_model

def _matrix(n, seed=0, scale=60.0):
    """(n+1, n+1) random symmetric cost matrix, zero diagonal."""
    rng = np.random.default_rng(seed)
    pts = rng.random((n + 1, 2)) * scale
    m = np.sqrt(((pts[:, None] - pts[None]) ** 2).sum(-1))
    return np.round(m, 3).astype(np.float32)


# ── kernel: host-oracle parity & fixed-shape lanes ───────────────────


def test_window_free_feasible_matches_solve_host():
    """No windows + demands that fit: the dispatch kernel IS the
    reference greedy — trips match solve_host exactly."""
    for seed in range(5):
        m = _matrix(7, seed=seed)
        rng = np.random.default_rng(seed)
        dem = rng.integers(1, 3, 7).astype(np.float32)
        plan = solve_host_dispatch(m, dem, 6.0, 1e6)
        ref = solve_host(m, dem, 6.0, 1e6)
        assert plan["trips"] == ref["trips"], seed
        assert plan["spill_lane"] == [] and plan["penalty"] == 0.0
        assert plan["spilled"] == [] and plan["unroutable"] == []


def test_generous_windows_are_a_noop():
    m = _matrix(6, seed=3)
    dem = np.ones(6, np.float32)
    free = solve_host_dispatch(m, dem, 4.0, 1e6)
    wide = solve_host_dispatch(
        m, dem, 4.0, 1e6,
        tw_open=np.zeros(6, np.float32),
        tw_close=np.full(6, NO_WINDOW, np.float32))
    assert wide["trips"] == free["trips"]
    assert wide["penalty"] == 0.0 and wide["spill_lane"] == []


def test_tight_window_spills_with_lateness_penalty():
    """A stop whose window closes before any vehicle can reach it
    lands in the spill lane (fixed shape — not an error), and the
    penalty is its accumulated lateness."""
    m = _matrix(5, seed=1)
    dem = np.ones(5, np.float32)
    tw_open = np.zeros(5, np.float32)
    tw_close = np.full(5, NO_WINDOW, np.float32)
    tw_close[2] = 0.5   # unreachable deadline: every leg costs more
    plan = solve_host_dispatch(m, dem, 10.0, 1e6,
                               tw_open=tw_open, tw_close=tw_close)
    assert plan["spill_lane"] == [2]
    assert 2 in plan["spilled"]
    assert plan["penalty"] > 0.0
    assert 2 not in plan["optimized_order"]
    # Stop-set partition: routed + spilled covers every stop once.
    assert sorted(plan["optimized_order"] + plan["spill_lane"]) \
        == list(range(5))


def test_overweight_stop_spills_to_next_trip_lane():
    """Demand spillover: a stop no trip can carry degrades into the
    spill lane (the next-trip penalty lane), never an error — and with
    no window to violate its lateness penalty is zero."""
    m = _matrix(4, seed=2)
    dem = np.asarray([1.0, 9.0, 1.0, 1.0], np.float32)  # 9 > cap 5
    plan = solve_host_dispatch(m, dem, 5.0, 1e6)
    assert plan["spill_lane"] == [1] and plan["spilled"] == [1]
    assert plan["penalty"] == 0.0
    assert plan["unroutable"] == []
    assert sorted(plan["optimized_order"]) == [0, 2, 3]


def test_batch_solve_matches_singles():
    """The batcher's device program (padded/bucketed batch) is bitwise
    the per-problem solve — including mixed sizes and windows; padded
    stops never leak into any lane."""
    sizes = [3, 5, 8, 4]
    dists, dems, caps, maxds, opens, closes = [], [], [], [], [], []
    for i, n in enumerate(sizes):
        dists.append(_matrix(n, seed=10 + i))
        rng = np.random.default_rng(100 + i)
        dems.append(rng.integers(1, 3, n).astype(np.float32))
        caps.append(5.0)
        maxds.append(500.0)
        if i == 1:
            o = np.zeros(n, np.float32)
            c = np.full(n, NO_WINDOW, np.float32)
            c[0] = 0.5
            opens.append(o)
            closes.append(c)
        else:
            opens.append(None)
            closes.append(None)
    batch = solve_host_dispatch_batch(dists, dems, caps, maxds,
                                      tw_opens=opens, tw_closes=closes)
    for i in range(len(sizes)):
        single = solve_host_dispatch(dists[i], dems[i], caps[i],
                                     maxds[i], opens[i], closes[i])
        assert batch[i] == single, i
        lanes = (batch[i]["optimized_order"] + batch[i]["spill_lane"]
                 + batch[i]["unroutable"])
        assert all(0 <= s < sizes[i] for s in lanes), i


def test_nonfinite_constraints_rejected():
    m = _matrix(3)
    dem = np.ones(3, np.float32)
    with pytest.raises(ValueError):
        solve_host_dispatch(m, dem, float("inf"), 100.0)
    with pytest.raises(ValueError):
        solve_host_dispatch_batch([m], [dem], [6.0], [float("nan")])


# ── batcher: leader/follower merge ───────────────────────────────────


def test_batcher_merges_concurrent_requests():
    batcher = DispatchBatcher(max_rows=16, window_s=0.15)
    problems = []
    for i in range(4):
        n = 4 + i
        rng = np.random.default_rng(i)
        problems.append(DispatchProblem(
            _matrix(n, seed=i), rng.integers(1, 3, n).astype(np.float32),
            5.0, 1e6))
    results = [None] * 4

    def worker(i):
        results[i] = batcher.solve([problems[i]])[0]

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i, p in enumerate(problems):
        expect = solve_host_dispatch(p.dist, p.demands, p.capacity,
                                     p.max_cost)
        assert results[i] == expect, i
    st = batcher.stats()
    assert st["requests"] == 4 and st["rows"] == 4
    # The 0.15 s leader window merges the stragglers into one drain.
    assert st["dispatches"] < 4
    assert st["merged_requests"] >= 2
    assert st["max_occupancy"] >= 2


def test_batcher_epoch_groups_never_share_a_drain():
    """Problems priced under different live-metric epochs disagree
    about the world — the leader drains one epoch group per round."""
    # Thread-local epoch: each caller's entry keys under ITS metric
    # generation deterministically, whatever the arrival interleaving
    # (a shared mutable epoch would race the other threads' key reads).
    local = threading.local()
    batcher = DispatchBatcher(max_rows=16, window_s=0.2,
                              epoch_fn=lambda: local.e)
    m = _matrix(3)
    dem = np.ones(3, np.float32)
    barrier = threading.Barrier(3)
    out = []

    def worker(e):
        local.e = e   # the straggler keys under the flipped epoch
        barrier.wait()
        out.append(batcher.solve(
            [DispatchProblem(m, dem, 5.0, 1e6)])[0])

    threads = [threading.Thread(target=worker, args=(e,))
               for e in (0, 0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(out) == 3
    st = batcher.stats()
    assert st["requests"] == 3
    # At least two drains: the epoch-1 entry cannot ride an epoch-0
    # batch (exact count depends on arrival interleaving).
    assert st["dispatches"] >= 2


def test_batcher_oversized_entry_dispatches_alone():
    """An entry carrying more problems than max_rows rides as its own
    oversized batch (the solver pads to any batch size). It used to be
    requeued on every round — the leader spinning on empty drains
    forever while its caller hung."""
    batcher = DispatchBatcher(max_rows=2)
    m = _matrix(3)
    dem = np.ones(3, np.float32)
    probs = [DispatchProblem(m, dem, 5.0, 1e6) for _ in range(5)]
    out = {}
    t = threading.Thread(target=lambda: out.update(r=batcher.solve(probs)),
                         daemon=True)
    t.start()
    t.join(30.0)
    assert "r" in out, "oversized entry wedged the batcher"
    expect = solve_host_dispatch(m, dem, 5.0, 1e6)
    assert len(out["r"]) == 5
    assert all(r == expect for r in out["r"])
    st = batcher.stats()
    assert st["dispatches"] == 1 and st["rows"] == 5
    assert st["max_occupancy"] == 5


# ── serving surface ──────────────────────────────────────────────────


@pytest.fixture(scope="module")
def model_artifact(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("model") / "eta.msgpack")
    model = EtaMLP(hidden=(16, 16), policy=F32_POLICY)
    params = model.init(jax.random.PRNGKey(0))
    save_model(path, model, params)
    return path


@pytest.fixture(scope="module")
def bus():
    return InMemoryBus()


@pytest.fixture(scope="module")
def app(model_artifact, bus):
    # reopt_poll_s=0: the loop object exists but ticks are manual —
    # no background thread racing the assertions.
    cfg = dataclasses.replace(
        Config(), dispatch=DispatchConfig(reopt_poll_s=0.0))
    eta = EtaService(ServeConfig(), model_path=model_artifact)
    return create_app(cfg, eta_service=eta, bus=bus,
                      sim_tick_range=(0.001, 0.002))


@pytest.fixture(scope="module")
def client(app):
    return Client(app)


def _geo_body(n=4, confirm=False, windows=None, seed=None):
    dests = [{"lat": SEED_LOCATIONS[i + 1][1],
              "lon": SEED_LOCATIONS[i + 1][2], "payload": 1}
             for i in range(n)]
    body = {
        "source_point": {"lat": SEED_LOCATIONS[0][1],
                         "lon": SEED_LOCATIONS[0][2]},
        "destination_points": dests,
        "driver_details": {"driver_name": "dina", "vehicle_type": "car",
                           "vehicle_capacity": 10,
                           "maximum_distance": 300_000},
    }
    if windows is not None:
        body["time_windows"] = windows
    if confirm:
        body["confirm"] = True
    if seed is not None:
        body["sim_seed"] = seed
    return body


def test_api_dispatch_matrix_host_parity(client):
    m = _matrix(6, seed=4)
    dem = [1.0, 2.0, 1.0, 2.0, 1.0, 2.0]
    r = client.post("/api/dispatch", json={
        "matrix": m.tolist(), "demands": dem,
        "capacity": 5.0, "max_distance": 400.0})
    assert r.status_code == 200, r.get_data()
    out = r.get_json()
    expect = solve_host_dispatch(m, np.asarray(dem, np.float32),
                                 5.0, 400.0)
    assert out["mode"] == "matrix"
    assert out["plan"] == expect
    assert out["cost"] == pytest.approx(plan_cost(m, expect), rel=1e-4)
    assert out["epoch"] == 0


def test_api_dispatch_geographic_window_spill(client):
    # Stop 4's one-second deadline is unmeetable at road speeds: it
    # spills; the other stops route normally.
    windows = [[0, None]] * 3 + [[0, 1.0]]
    r = client.post("/api/dispatch", json=_geo_body(4, windows=windows))
    assert r.status_code == 200, r.get_data()
    out = r.get_json()
    assert out["mode"] == "geographic"
    assert out["plan"]["spill_lane"] == [3]
    assert out["plan"]["penalty"] > 0
    assert sorted(out["plan"]["optimized_order"]) == [0, 1, 2]


def test_api_dispatch_validation(client):
    assert client.post("/api/dispatch", json={
        "matrix": [[0, 1], [1, 0]], "demands": [1],
        "capacity": float("nan")}).status_code == 400
    assert client.post("/api/dispatch", json={
        "matrix": [[0]], "demands": []}).status_code == 400
    assert client.post("/api/dispatch", json=_geo_body(
        4, windows=[[0, None]] * 3)).status_code == 400  # wrong length
    assert client.post("/api/dispatch", json={
        "complete": 7}).status_code == 400
    assert client.post("/api/dispatch", json={
        "complete": "missing"}).status_code == 404


def test_api_dispatch_confirm_register_complete(app, client):
    r = client.post("/api/dispatch", json=_geo_body(3, confirm=True,
                                                    seed=11))
    assert r.status_code == 200, r.get_data()
    out = r.get_json()
    did = out["dispatch_id"]
    assert out["channel"] == "dina"
    rec = app.dispatch.registry.get(did)
    assert rec is not None and rec.sim_seed == 11
    assert rec.source == "dispatch"
    assert rec.baseline_cost == pytest.approx(out["cost"], rel=1e-4)
    snap = client.get("/api/dispatch").get_json()
    assert snap["enabled"] and snap["registry"]["active"] >= 1
    assert any(d["dispatch_id"] == did
               for d in snap["registry"]["dispatches"])
    done = client.post("/api/dispatch", json={"complete": did})
    assert done.status_code == 200
    assert app.dispatch.registry.get(did) is None


def test_confirm_route_sim_seed_flows_to_dispatch(app, client):
    dests = [{"lat": SEED_LOCATIONS[i + 1][1],
              "lon": SEED_LOCATIONS[i + 1][2], "payload": 1}
             for i in range(3)]
    coords = [[SEED_LOCATIONS[0][2], SEED_LOCATIONS[0][1]]] \
        + [[d["lon"], d["lat"]] for d in dests] \
        + [[SEED_LOCATIONS[0][2], SEED_LOCATIONS[0][1]]]
    r = client.post("/api/confirm_route", json={
        "route_details": {
            "geometry": {"coordinates": coords},
            "properties": {
                "summary": {"duration": 900, "distance": 8000,
                            "trips": 1},
                "destinations": dests,
            },
        },
        "driver_details": {"driver_name": "marco",
                           "vehicle_type": "motorcycle",
                           "vehicle_capacity": 10,
                           "maximum_distance": 50_000},
        "sim_seed": 7,
    })
    assert r.status_code == 200, r.get_data()
    out = r.get_json()
    assert out["status"] == "route simulation initialized."
    rec = app.dispatch.registry.get(out["dispatch_id"])
    assert rec is not None
    assert rec.sim_seed == 7
    assert rec.source == "confirm_route"
    assert rec.channel == "marco"
    # The confirmed stop ORDER is the baseline plan.
    assert rec.plan["trips"] == [[0, 1, 2]]
    client.post("/api/dispatch", json={"complete": rec.id})


def test_confirm_route_without_structure_keeps_reference_shape(client):
    """A body the re-solver can't use (no per-stop lat/lon) still 200s
    with the reference response — registration is best-effort."""
    r = client.post("/api/confirm_route", json={
        "route_details": {
            "geometry": {"coordinates": [[121.0, 14.6], [121.1, 14.7]]},
            "properties": {"summary": {"duration": 60, "distance": 500,
                                       "trips": 1},
                           "destinations": [{"label": "x"}]},
        },
        "driver_details": {"driver_name": "nolat",
                           "vehicle_type": "car"},
    })
    assert r.status_code == 200
    assert "dispatch_id" not in r.get_json()


# ── re-optimization loop ─────────────────────────────────────────────


def _mk_reopt(jam_ids, degrade_ratio=1.2):
    """Registry with two active dispatches over the same 3-stop
    corridor shape; matrix_fn prices any dispatch whose id is in
    ``jam_ids`` at 3× (a corridor jam), everyone else at baseline."""
    base = _matrix(3, seed=6)
    registry = DispatchRegistry()
    epoch = {"v": 0}
    published = []

    def matrix_fn(latlon):
        rec_key = int(round(float(latlon[0][0]) * 10))
        return base * 3.0 if rec_key in jam_ids else base

    recs = {}
    for key, name in ((1, "veh-a"), (2, "veh-b")):
        latlon = np.full((4, 2), key / 10.0, np.float32)
        plan = solve_host_dispatch(base, np.ones(3, np.float32),
                                   5.0, 1e6)
        recs[key] = registry.register(
            channel=name, latlon=latlon,
            demands=np.ones(3, np.float32), capacity=5.0, max_cost=1e6,
            plan=plan, baseline_cost=plan_cost(base, plan), epoch=0,
            sim_seed=42)
    restarted = []
    loop = ReoptLoop(
        registry, DispatchBatcher(),
        lambda ch, ev: published.append((ch, ev)),
        lambda: epoch["v"], matrix_fn,
        degrade_ratio=degrade_ratio, poll_s=0.0,
        sim_restart=lambda rec: restarted.append(rec.id))
    return loop, recs, epoch, published, restarted


def test_reopt_resolves_exactly_the_degraded():
    loop, recs, epoch, published, restarted = _mk_reopt(jam_ids={1})
    assert loop.tick()["result"] == "armed"
    assert loop.tick()["result"] == "idle"
    epoch["v"] = 1
    out = loop.tick()
    assert out["result"] == "resolved"
    assert out["checked"] == 2
    assert out["degraded"] == [recs[1].id]
    assert out["resolved"] == [recs[1].id]
    # SSE delivery: exactly one plan_update, on the jammed dispatch's
    # channel, with the degradation spelled out.
    assert len(published) == 1
    ch, ev = published[0]
    assert ch == "veh-a"
    assert ev["event"] == "plan_update"
    assert ev["dispatch_id"] == recs[1].id and ev["epoch"] == 1
    assert ev["reason"]["previous_cost"] >= ev["reason"]["new_cost"]
    assert ev["reason"]["degrade_ratio"] == pytest.approx(1.2)
    # The healthy plan: untouched but re-stamped under the new epoch.
    assert recs[2].updates == 0 and recs[2].epoch == 1
    assert recs[1].updates == 1 and recs[1].epoch == 1
    assert restarted == [recs[1].id]
    # Consumed: the same epoch never re-triggers.
    assert loop.tick()["result"] == "idle"


def test_reopt_mass_degradation_chunks_to_batcher_drains():
    """More degraded dispatches than the batcher's max_rows: the tick
    chunks its re-solve into drain-sized solve() calls (one oversized
    entry used to wedge the batcher fleet-wide) and still resolves
    every degraded plan."""
    base = _matrix(3, seed=6)
    registry = DispatchRegistry()
    epoch = {"v": 0}
    published = []
    jam = {"on": False}
    plan = solve_host_dispatch(base, np.ones(3, np.float32), 5.0, 1e6)
    recs = [registry.register(
        channel=f"veh-{i}", latlon=np.full((4, 2), 0.1, np.float32),
        demands=np.ones(3, np.float32), capacity=5.0, max_cost=1e6,
        plan=plan, baseline_cost=plan_cost(base, plan), epoch=0)
        for i in range(5)]
    batcher = DispatchBatcher(max_rows=2)
    loop = ReoptLoop(
        registry, batcher,
        lambda ch, ev: published.append((ch, ev)),
        lambda: epoch["v"],
        lambda latlon: base * 3.0 if jam["on"] else base,
        poll_s=0.0)
    loop.tick()          # arm
    jam["on"] = True
    epoch["v"] = 1
    out = loop.tick()
    assert out["result"] == "resolved"
    assert sorted(out["resolved"]) == sorted(r.id for r in recs)
    assert len(published) == 5
    st = batcher.stats()
    assert st["dispatches"] >= 3            # ceil(5 / max_rows=2)
    assert st["max_occupancy"] <= 2


def test_reopt_skips_matrix_mode_dispatches():
    loop, recs, epoch, published, _ = _mk_reopt(jam_ids=set())
    m = _matrix(3, seed=9)
    plan = solve_host_dispatch(m, np.ones(3, np.float32), 5.0, 1e6)
    loop.registry.register(
        channel="mx", latlon=None, demands=np.ones(3, np.float32),
        capacity=5.0, max_cost=1e6, plan=plan,
        baseline_cost=plan_cost(m, plan), epoch=0)
    loop.tick()
    epoch["v"] = 1
    out = loop.tick()
    assert out["result"] == "clean"
    assert out["skipped"] == 1 and out["checked"] == 3
    assert published == []


def test_reopt_chaos_drop_leaves_previous_plan_serving():
    loop, recs, epoch, published, restarted = _mk_reopt(jam_ids={1})
    loop.tick()          # arm
    old_plan = recs[1].plan
    old_baseline = recs[1].baseline_cost
    epoch["v"] = 1
    chaos.configure(chaos.ChaosEngine(
        "dispatch.resolve:error=1.0@1", seed=3))
    try:
        out = loop.tick()
        assert out["result"] == "chaos"
        # Previous plan keeps serving; nothing published or restarted.
        assert recs[1].plan is old_plan
        assert recs[1].baseline_cost == old_baseline
        assert recs[1].updates == 0
        assert published == [] and restarted == []
        # Per-record epoch coherency: the healthy record must not
        # advertise the new epoch while the degraded one stays behind.
        assert recs[1].epoch == 0 and recs[2].epoch == 0
        # The epoch stays unconsumed → the next tick retries (the
        # single-fire rule is exhausted) and resolves.
        out = loop.tick()
        assert out["result"] == "resolved"
        assert out["resolved"] == [recs[1].id]
        assert recs[1].updates == 1
        assert recs[1].epoch == 1 and recs[2].epoch == 1
        assert len(published) == 1
    finally:
        chaos.configure(None)


# ── chaos wrong-plan fault + the prober kind that catches it ─────────


def test_chaos_dispatch_solve_skews_plan_not_shape(client):
    body = {"matrix": _matrix(8, seed=20).tolist(),
            "demands": [1.0, 2.0, 1.0, 2.0, 1.0, 2.0, 1.0, 2.0],
            "capacity": 6.0, "max_distance": 400.0}
    honest = client.post("/api/dispatch", json=body).get_json()
    chaos.configure(chaos.ChaosEngine("dispatch.solve:skew=1.0/40",
                                      seed=5))
    try:
        r = client.post("/api/dispatch", json=body)
        assert r.status_code == 200          # confidently wrong: a 200
        skewed = r.get_json()
    finally:
        chaos.configure(None)
    # Same stop set, well-formed shape — only the answer moved.
    assert sorted(skewed["plan"]["optimized_order"]
                  + skewed["plan"]["spill_lane"]) \
        == sorted(honest["plan"]["optimized_order"]
                  + honest["plan"]["spill_lane"])
    assert skewed["plan"] != honest["plan"]


def test_prober_dispatch_kind_pass_and_divergent(client, tmp_path,
                                                 monkeypatch):
    from routest_tpu.core.config import ProberConfig, RecorderConfig
    from routest_tpu.obs import prober as prober_mod
    from routest_tpu.obs.prober import DIVERGENT, PASS, BlackboxProber
    from routest_tpu.obs.recorder import FlightRecorder

    def fake_http(method, url, body, timeout, probe=None):
        path = url.split("http://gw", 1)[1]
        r = client.post(path, json=body) if method == "POST" \
            else client.get(path)
        return r.get_json(), {}

    monkeypatch.setattr(prober_mod, "_http_json", fake_http)
    prober = BlackboxProber(
        ProberConfig(enabled=True, timeout_s=5.0),
        gateway_base="http://gw", targets_fn=lambda: [],
        recorder=FlightRecorder(RecorderConfig(
            dir=str(tmp_path / "rec"), min_interval_s=0.0)))
    # Dispatch serving is on here, so the kind is armed.
    assert prober._dispatch_armed() is True
    verdict, ev = prober._probe_dispatch()
    assert verdict == PASS, ev
    assert ev["divergence"] <= ev["tolerance"]
    # The silently-wrong-plan fault: same probe, skewed device costs.
    # (At 40% the skewed instance happens to yield an equal-cost
    # alternative ordering — correctly a PASS; 80% prices the plan
    # measurably worse under the true matrix.)
    chaos.configure(chaos.ChaosEngine("dispatch.solve:skew=1.0/80",
                                      seed=5))
    try:
        verdict, ev = prober._probe_dispatch()
    finally:
        chaos.configure(None)
    assert verdict == DIVERGENT, ev
    assert ev["served_plan"] is not None
    assert ev["expected_plan"] is not None


def test_prober_dispatch_kind_stands_down_when_disabled(
        model_artifact, tmp_path, monkeypatch):
    """RTPU_DISPATCH=0 answers the state GET with enabled:false: the
    probe round must skip the dispatch kind entirely — probing a
    deliberately disabled feature would feed sustained UNREACHABLE
    verdicts into the correctness SLO and page on a config knob."""
    from routest_tpu.core.config import ProberConfig, RecorderConfig
    from routest_tpu.obs import prober as prober_mod
    from routest_tpu.obs.prober import BlackboxProber
    from routest_tpu.obs.recorder import FlightRecorder

    cfg = dataclasses.replace(
        Config(), dispatch=DispatchConfig(enabled=False))
    eta = EtaService(ServeConfig(), model_path=model_artifact)
    disabled = Client(create_app(cfg, eta_service=eta,
                                 bus=InMemoryBus()))
    assert disabled.get("/api/dispatch").get_json() == {"enabled": False}
    assert disabled.post("/api/dispatch", json={}).status_code == 503

    def fake_http(method, url, body, timeout, probe=None):
        path = url.split("http://gw", 1)[1]
        r = disabled.post(path, json=body) if method == "POST" \
            else disabled.get(path)
        return r.get_json(), {}

    monkeypatch.setattr(prober_mod, "_http_json", fake_http)
    prober = BlackboxProber(
        ProberConfig(enabled=True, timeout_s=5.0),
        gateway_base="http://gw", targets_fn=lambda: [],
        recorder=FlightRecorder(RecorderConfig(
            dir=str(tmp_path / "rec"), min_interval_s=0.0)))
    assert prober._dispatch_armed() is False
    verdicts = prober.probe_round()
    assert "dispatch" not in verdicts


# ── config & loadgen citizenship ─────────────────────────────────────


def test_dispatch_config_env_round_trip():
    cfg = load_dispatch_config({
        "RTPU_DISPATCH": "1", "RTPU_DISPATCH_MAX_ROWS": "8",
        "RTPU_DISPATCH_WINDOW_S": "0.05", "RTPU_DISPATCH_MAX_STOPS": "9",
        "RTPU_DISPATCH_REOPT": "0", "RTPU_DISPATCH_REOPT_POLL_S": "2.5",
        "RTPU_DISPATCH_DEGRADE_RATIO": "1.5",
        "RTPU_DISPATCH_MAX_ACTIVE": "32",
        "RTPU_DISPATCH_SPEED_MPS": "7.0"})
    assert cfg.enabled and cfg.max_rows == 8
    assert cfg.window_s == 0.05 and cfg.max_stops == 9
    assert not cfg.reopt and cfg.reopt_poll_s == 2.5
    assert cfg.degrade_ratio == 1.5 and cfg.max_active == 32
    assert cfg.speed_mps == 7.0
    assert not load_dispatch_config({"RTPU_DISPATCH": "0"}).enabled


def test_loadgen_dispatch_component_deterministic(client):
    from routest_tpu.loadgen.workload import MixedWorkload

    a = MixedWorkload(mix={"dispatch": 1.0}, seed=17)
    b = MixedWorkload(mix={"dispatch": 1.0}, seed=17)
    sa, sb = a.sequence(12), b.sequence(12)
    assert [json.dumps(r.body, sort_keys=True) for r in sa] \
        == [json.dumps(r.body, sort_keys=True) for r in sb]
    assert all(r.method == "POST" and r.path == "/api/dispatch"
               for r in sa)
    # Zipf skew: hot depots repeat as byte-identical bodies (what the
    # batcher merges); and every body is servable as offered.
    r = client.post("/api/dispatch", json=sa[0].body)
    assert r.status_code == 200, r.get_data()
    assert r.get_json()["plan"]["optimized_order"] or \
        r.get_json()["plan"]["spill_lane"]
    assert "dispatch" in MixedWorkload.KINDS
    assert a.describe()["dispatch_stops"] == 4
