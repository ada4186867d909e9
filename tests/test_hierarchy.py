"""Partition-overlay routing (optimize/hierarchy.py): exactness vs the
scipy Dijkstra oracle on directed OSM-topology graphs, equivalence with
the flat solver, partition invariants, and the subdivide generator."""

import numpy as np
import pytest
import scipy.sparse as sp
from scipy.sparse.csgraph import dijkstra

from routest_tpu.data.road_graph import generate_road_graph, subdivide_graph
from routest_tpu.optimize.hierarchy import HierarchicalIndex, partition_cells
from routest_tpu.optimize.road_router import RoadRouter


def _oracle(router, sources):
    n = router.n_nodes
    adj = sp.coo_matrix(
        (router.length_m, (router.senders, router.receivers)), shape=(n, n)
    ).tocsr()
    return dijkstra(adj, directed=True, indices=np.asarray(sources, np.int64))


@pytest.fixture()
def force_hier(monkeypatch):
    """Route even tiny graphs through the overlay (cell target shrunk so
    a few hundred nodes still split into many cells)."""
    monkeypatch.setenv("ROUTEST_HIER_MIN_NODES", "1")


def test_partition_cells_bounded_and_total():
    coords = np.random.default_rng(0).uniform(0, 1, (777, 2)).astype(np.float32)
    cell, n_cells = partition_cells(coords, 50)
    assert cell.shape == (777,) and n_cells >= 777 // 50
    sizes = np.bincount(cell, minlength=n_cells)
    assert sizes.max() <= 50 and sizes.sum() == 777


def test_hierarchy_matches_dijkstra_symmetric(force_hier, rng):
    router = RoadRouter(graph=generate_road_graph(n_nodes=1500, seed=2),
                        use_gnn=False, use_transformer=False)
    assert router._hier is not None, "overlay must engage under the env knob"
    sources = rng.integers(0, router.n_nodes, 9)
    dist, pred = router.shortest(sources)
    want = _oracle(router, sources)
    finite = np.isfinite(want)
    assert finite.all()
    np.testing.assert_allclose(dist[finite], want[finite], rtol=1e-4)
    # Predecessor walks still reconstruct true-shortest paths.
    edge_len = {}
    for e, (s, r) in enumerate(zip(router.senders, router.receivers)):
        key = (int(s), int(r))
        edge_len[key] = min(edge_len.get(key, np.inf),
                            float(router.length_m[e]))
    for si, src in enumerate(sources):
        for tgt in rng.integers(0, router.n_nodes, 6):
            seq = router._walk(pred[si], int(src), int(tgt))
            if int(tgt) == int(src):
                continue
            assert seq and seq[0] == int(src) and seq[-1] == int(tgt)
            total = sum(edge_len[(a, b)] for a, b in zip(seq[:-1], seq[1:]))
            np.testing.assert_allclose(total, dist[si, tgt], rtol=1e-3)


def test_hierarchy_exact_on_directed_osm_topology(force_hier, rng):
    # One-way chains: the regime where forward/backward restricted
    # distances differ, so any direction slip in tables/cliques/stitch
    # shows up as an oracle mismatch.
    base = generate_road_graph(n_nodes=400, seed=5)
    streets = subdivide_graph(base, bends_per_edge=3, oneway_frac=0.25, seed=1)
    router = RoadRouter(graph=streets, use_gnn=False, use_transformer=False)
    assert router._hier is not None
    sources = rng.integers(0, router.n_nodes, 8)
    dist, _ = router.shortest(sources)
    want = _oracle(router, sources)
    finite = np.isfinite(want)
    assert finite.mean() > 0.5  # one-ways may strand some pockets
    np.testing.assert_allclose(dist[finite], want[finite], rtol=1e-4)
    assert (dist[~finite] > 1e37).all()  # unreachable stays unreachable


def test_hierarchy_agrees_with_flat_solver(force_hier, monkeypatch, rng):
    graph = generate_road_graph(n_nodes=900, seed=3)
    hier = RoadRouter(graph=graph, use_gnn=False, use_transformer=False)
    assert hier._hier is not None
    sources = rng.integers(0, hier.n_nodes, 5)
    d_hier, _ = hier.shortest(sources)
    monkeypatch.setenv("ROUTEST_HIER_MIN_NODES", "0")
    flat = RoadRouter(graph=graph, use_gnn=False, use_transformer=False)
    assert flat._hier is None
    d_flat, _ = flat.shortest(sources)
    np.testing.assert_allclose(d_hier, d_flat, rtol=1e-5)


def test_hierarchy_build_declines_tiny_graphs():
    # A graph that fits one cell has no overlay to build.
    g = generate_road_graph(n_nodes=64, seed=0)
    idx = HierarchicalIndex.build(g["node_coords"], g["senders"],
                                  g["receivers"], g["length_m"],
                                  cell_target=4096)
    assert idx is None


def test_subdivide_graph_shapes_and_oneway():
    base = generate_road_graph(n_nodes=300, seed=4)
    n = len(base["node_coords"])
    key = set()
    for s, r in zip(base["senders"], base["receivers"]):
        key.add((min(int(s), int(r)), max(int(s), int(r))))
    u = len(key)
    out = subdivide_graph(base, bends_per_edge=2, oneway_frac=0.3, seed=0)
    assert len(out["node_coords"]) == n + 2 * u
    # Bend nodes are degree-2 on the forward direction (one in, one out).
    fwd_deg = np.bincount(out["senders"], minlength=len(out["node_coords"]))
    assert (fwd_deg[n:] <= 2).all() and fwd_deg[n:].min() >= 1
    # One-way streets have no reverse chain.
    pairs = set(zip(out["senders"].tolist(), out["receivers"].tolist()))
    missing_rev = sum((r, s) not in pairs for s, r in pairs)
    assert missing_rev > 0
    # Roundtrips through real OSM XML unchanged in size.
    import os
    import tempfile

    from routest_tpu.data.osm import load_osm, save_osm

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "t.osm.gz")
        save_osm(path, out)
        back = load_osm(path)
    assert len(back["node_coords"]) == len(out["node_coords"])
    assert len(back["senders"]) == len(out["senders"])


def test_solver_info_shapes(force_hier, monkeypatch):
    import json

    hier = RoadRouter(graph=generate_road_graph(n_nodes=900, seed=3),
                      use_gnn=False, use_transformer=False)
    info = hier.solver_info
    assert info["solver"] == "hierarchy"
    assert info["overlay"]["n_cells"] >= 2
    assert info["overlay"]["n_overlay_edges"] > 0
    json.dumps(info)  # health serializes this verbatim
    monkeypatch.setenv("ROUTEST_HIER_MIN_NODES", "0")
    flat = RoadRouter(graph=generate_road_graph(n_nodes=300, seed=3),
                      use_gnn=False, use_transformer=False)
    flat_info = flat.solver_info
    assert flat_info["solver"] == "flat_bf"
    assert flat_info["max_iters_bound"] == flat.max_iters
    # The routing fast path's provenance rides along on every regime:
    # batcher dispatch stats + route-cache counters, JSON-serializable
    # for the health row.
    assert flat_info["batch"]["dispatches"] == 0
    assert flat_info["route_cache"]["entries"] == 0
    json.dumps(flat_info)


def test_overlay_serves_metro_extract_over_http(monkeypatch, tmp_path):
    """Full stack at metro scale: the in-repo 8,192-node OSM extract
    (above the default ROUTEST_HIER_MIN_NODES=4096) routes a road-graph
    request through HTTP with the partition overlay as the solver, and
    health reports the regime (`checks.engine.road_router.solver`).
    This is the serving configuration a real deployment gets by pointing
    ROAD_GRAPH_OSM at a city extract."""
    import os

    import jax
    from werkzeug.test import Client

    from routest_tpu.core.config import Config, ServeConfig
    from routest_tpu.core.dtypes import F32_POLICY
    from routest_tpu.models.eta_mlp import EtaMLP
    from routest_tpu.optimize import road_router as rr
    from routest_tpu.serve.app import create_app
    from routest_tpu.serve.ml_service import EtaService
    from routest_tpu.train.checkpoint import save_model

    extract = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "artifacts", "metro_8192.osm.gz")
    # monkeypatch teardown restores the pre-test singleton, so the
    # metro-sized router never leaks into other tests
    monkeypatch.setattr(rr, "_default_router", None)
    monkeypatch.setenv("ROAD_GRAPH_OSM", extract)
    # leave ROUTEST_HIER_MIN_NODES at its default: 8192 > 4096 must
    # engage the overlay without test-only knobs

    mpath = str(tmp_path / "eta.msgpack")
    model = EtaMLP(hidden=(16, 16), policy=F32_POLICY)
    save_model(mpath, model, model.init(jax.random.PRNGKey(0)))
    eta = EtaService(ServeConfig(), model_path=mpath)
    client = Client(create_app(Config(), eta_service=eta))
    res = client.post("/api/optimize_route", json={
        "source_point": {"lat": 14.5836, "lon": 121.0409},
        "destination_points": [
            {"lat": 14.5355, "lon": 121.0621, "payload": 1},
            {"lat": 14.5866, "lon": 121.0566, "payload": 1},
        ],
        "driver_details": {"driver_name": "t", "vehicle_type": "car",
                           "vehicle_capacity": 9999,
                           "maximum_distance": 1_000_000},
        "road_graph": True,
        "use_ml_eta": True,
    })
    assert res.status_code == 200, res.get_data(as_text=True)
    feat = res.get_json()
    assert feat["type"] == "Feature"
    p = feat["properties"]
    assert p["summary"]["distance"] > 0
    assert len(feat["geometry"]["coordinates"]) > 4  # street-following
    assert rr.default_router()._hier is not None
    health = client.get("/api/health").get_json()
    road = health["checks"]["engine"]["road_router"]
    assert road["solver"] == "hierarchy"
    assert road["overlay"]["n_cells"] >= 2
    assert road["nodes"] == rr.default_router().n_nodes
    # The matrix API rides the same overlay router: S x D street
    # distances/durations at metro scale through HTTP, durations from
    # the device-side table (no host walks).
    res = client.post("/api/matrix", json={
        "points": [{"lat": 14.5836, "lon": 121.0409},
                   {"lat": 14.5355, "lon": 121.0621},
                   {"lat": 14.5866, "lon": 121.0566}],
        "road_graph": True, "sources": [0],
        "pickup_time": "2026-03-02T08:30:00",
    })
    assert res.status_code == 200, res.get_data(as_text=True)
    mat = res.get_json()
    assert mat["road_graph"] is True
    assert len(mat["distances_m"]) == 1
    assert len(mat["distances_m"][0]) == 3
    assert mat["distances_m"][0][0] == 0.0
    assert all(v > 0 for v in mat["distances_m"][0][1:])
    assert all(v > 0 for v in mat["durations_s"][0][1:])


def test_overlay_disk_cache_roundtrip(force_hier, monkeypatch, tmp_path, rng):
    monkeypatch.setenv("ROUTEST_HIER_CACHE", str(tmp_path))
    graph = generate_road_graph(n_nodes=1200, seed=6)
    built = RoadRouter(graph=graph, use_gnn=False, use_transformer=False)
    assert built._hier is not None
    cached_files = list(tmp_path.glob("hier-*.npz"))
    assert len(cached_files) == 1
    # Second router rehydrates instead of rebuilding…
    loaded = RoadRouter(graph=graph, use_gnn=False, use_transformer=False)
    assert loaded._hier.stats.get("loaded_from_cache") is True
    # …and answers identically.
    sources = rng.integers(0, built.n_nodes, 5)
    d_built, _ = built.shortest(sources)
    d_loaded, _ = loaded.shortest(sources)
    np.testing.assert_allclose(d_built, d_loaded, rtol=0, atol=0)
    # A payload parked at the right filename for the WRONG graph is
    # rejected by the embedded fingerprint, not trusted by name.
    import shutil

    other = generate_road_graph(n_nodes=1100, seed=9)
    RoadRouter(graph=other, use_gnn=False, use_transformer=False)
    other_file = [f for f in tmp_path.glob("hier-*.npz")
                  if f != cached_files[0]]
    assert len(other_file) == 1
    shutil.copy(cached_files[0], other_file[0])  # tamper: wrong payload
    tampered = RoadRouter(graph=other, use_gnn=False, use_transformer=False)
    assert not tampered._hier.stats.get("loaded_from_cache")
    # Corruption degrades to a fresh build, never an error.
    cached_files[0].write_bytes(b"garbage")
    rebuilt = RoadRouter(graph=graph, use_gnn=False, use_transformer=False)
    assert rebuilt._hier is not None
    assert not rebuilt._hier.stats.get("loaded_from_cache")
    d_rebuilt, _ = rebuilt.shortest(sources)
    np.testing.assert_allclose(d_built, d_rebuilt, rtol=1e-6)


# ---------------------------------------------------------------------------
# Multi-level stack (PR 8): recursive overlay, chain contraction,
# multi-seed sources, cache format v2.
# ---------------------------------------------------------------------------


def test_multi_level_stack_matches_oracle(force_hier, monkeypatch, rng):
    """≥2 levels on a directed OSM-topology graph (bend chains force
    the contraction path; one-ways force direction handling): random,
    BOUNDARY-NODE and chain-interior sources all match the oracle, and
    oracle-unreachable stays unreachable."""
    monkeypatch.setenv("ROUTEST_HIER_RATIO", "4")
    monkeypatch.setenv("ROUTEST_HIER_CELL_TARGET", "24")
    base = generate_road_graph(n_nodes=600, seed=11)
    streets = subdivide_graph(base, bends_per_edge=2, oneway_frac=0.2,
                              seed=2)
    router = RoadRouter(graph=streets, use_gnn=False, use_transformer=False)
    h = router._hier
    assert h is not None and h.stats["n_levels"] >= 2, h and h.stats
    assert h.stats["contraction"]["n_contracted"] < h.n_nodes
    # Source mix: random nodes, level-1 boundary nodes (kept), and
    # chain interiors (contracted away — the multi-seed path).
    kept_full = np.flatnonzero(np.asarray(h._expand_idx) >= 0)
    interior_full = np.flatnonzero(np.asarray(h._expand_idx) < 0)
    cid_to_full = np.full(h.n_contracted, -1, np.int64)
    cid_to_full[np.asarray(h._expand_idx)[kept_full]] = kept_full
    boundary_full = cid_to_full[np.asarray(h.levels[0].b_global)]
    sources = np.concatenate([
        rng.integers(0, router.n_nodes, 3),
        rng.choice(boundary_full, 3, replace=False),
        rng.choice(interior_full, 3, replace=False),
    ]).astype(np.int64)
    dist, pred = router.shortest(sources)
    want = _oracle(router, sources)
    finite = np.isfinite(want)
    assert finite.mean() > 0.5
    np.testing.assert_allclose(dist[finite], want[finite], rtol=1e-4)
    assert (dist[~finite] > 1e37).all()
    # Walks reconstruct through contracted chains.
    for si in range(len(sources)):
        for tgt in rng.integers(0, router.n_nodes, 4):
            if not np.isfinite(want[si, tgt]) or int(tgt) == int(sources[si]):
                continue
            seq = router._walk(pred[si], int(sources[si]), int(tgt))
            assert seq and seq[0] == int(sources[si]) and seq[-1] == int(tgt)


def test_deep_stack_explicit_targets_exact(monkeypatch, rng):
    """Three explicit levels on a small graph: the recursion is exact
    at every depth, not just the tuned two-level default."""
    monkeypatch.setenv("ROUTEST_HIER_CONTRACT", "0")
    base = generate_road_graph(n_nodes=410, seed=13)
    g = subdivide_graph(base, bends_per_edge=2, oneway_frac=0.1, seed=0)
    idx = HierarchicalIndex.build(g["node_coords"], g["senders"],
                                  g["receivers"], g["length_m"],
                                  cell_targets=[24, 96, 384])
    assert idx is not None and idx.n_levels == 3
    sources = rng.integers(0, len(g["node_coords"]), 6)
    p_cells, seed_pos, seed_val = idx.prep_sources(sources)
    dist = np.asarray(idx.query_fn(p_cells, seed_pos, seed_val))
    import scipy.sparse as sp

    adj = sp.coo_matrix(
        (g["length_m"], (g["senders"], g["receivers"])),
        shape=(idx.n_nodes, idx.n_nodes)).tocsr()
    want = dijkstra(adj, directed=True, indices=np.asarray(sources, np.int64))
    finite = np.isfinite(want)
    np.testing.assert_allclose(dist[finite], want[finite], rtol=1e-4)
    assert (dist[~finite] > 1e37).all()


def test_same_cell_leave_and_reenter(monkeypatch):
    """Source and target in the SAME cell whose shortest path exits and
    re-enters: the descend stitch must beat the in-cell-only value."""
    monkeypatch.setenv("ROUTEST_HIER_CONTRACT", "0")
    # Cell A: x ∈ {0..3}, cell B: x ∈ {4..7} (median bisection on x).
    coords = np.asarray([[0.0, x] for x in range(8)], np.float32)
    s, r, w = [], [], []

    def edge(a, b, wt):
        s.extend([a, b])
        r.extend([b, a])
        w.extend([wt, wt])

    edge(0, 1, 100.0)
    edge(1, 2, 100.0)
    edge(2, 3, 100.0)   # in-cell 0→3 = 300
    edge(0, 4, 2.0)
    edge(4, 5, 2.0)
    edge(5, 6, 2.0)
    edge(6, 7, 2.0)
    edge(7, 3, 2.0)     # detour through B = 10
    idx = HierarchicalIndex.build(
        coords, np.asarray(s), np.asarray(r),
        np.asarray(w, np.float32), cell_targets=[4])
    assert idx is not None
    p_cells, seed_pos, seed_val = idx.prep_sources(np.asarray([0]))
    dist = np.asarray(idx.query_fn(p_cells, seed_pos, seed_val))
    np.testing.assert_allclose(dist[0, 3], 10.0, rtol=1e-6)
    # 0→2 also re-enters: detour to 3 (10) + back-edge 3→2 (100)
    # beats the 200 in-cell path.
    np.testing.assert_allclose(dist[0, 2], 110.0, rtol=1e-6)
    np.testing.assert_allclose(dist[0, 1], 100.0, rtol=1e-6)  # stays in A


def test_unreachable_pocket_stays_unreachable(force_hier, monkeypatch, rng):
    """A pocket with only OUTGOING edges to the main graph is
    undirected-connected (no component bridging) but directionally
    unreachable — the overlay must report INF, same as flat BF."""
    monkeypatch.setenv("ROUTEST_HIER_CELL_TARGET", "48")
    g = generate_road_graph(n_nodes=400, seed=17)
    n = len(g["node_coords"])
    pocket = 6
    coords = np.concatenate([
        g["node_coords"],
        g["node_coords"][:1] + 0.001 * (1 + np.arange(pocket))[:, None]],
        axis=0).astype(np.float32)
    ps = np.arange(n, n + pocket - 1)
    add_s = np.concatenate([ps, ps + 1, [n]])          # two-way inside…
    add_r = np.concatenate([ps + 1, ps, [0]])          # …one-way OUT only
    senders = np.concatenate([g["senders"], add_s]).astype(np.int32)
    receivers = np.concatenate([g["receivers"], add_r]).astype(np.int32)
    length = np.concatenate(
        [g["length_m"], np.full(len(add_s), 50.0)]).astype(np.float32)
    graph = {
        "node_coords": coords, "senders": senders, "receivers": receivers,
        "length_m": length,
        "road_class": np.ones(len(senders), np.int32),
        "speed_limit": np.full(len(senders), 8.3, np.float32),
    }
    router = RoadRouter(graph=graph, use_gnn=False, use_transformer=False)
    assert router._hier is not None
    sources = rng.integers(0, n, 4)
    dist, _ = router.shortest(sources)
    want = _oracle(router, sources)
    assert (dist[:, n:] > 1e37).all()                  # pocket unreachable
    finite = np.isfinite(want)
    np.testing.assert_allclose(dist[finite], want[finite], rtol=1e-4)


def test_contraction_roundabout_cycle_exact(monkeypatch):
    """An all-degree-2 cycle (roundabout) has no natural chain
    endpoint; contraction must break it, not hang or corrupt."""
    m = 24
    theta = 2 * np.pi * np.arange(m) / m
    coords = np.stack([np.sin(theta), np.cos(theta)], axis=1).astype(
        np.float32)
    s = np.concatenate([np.arange(m), (np.arange(m) + 1) % m])
    r = np.concatenate([(np.arange(m) + 1) % m, np.arange(m)])
    w = np.full(len(s), 10.0, np.float32)
    idx = HierarchicalIndex.build(coords, s, r, w, cell_targets=[3])
    assert idx is not None
    sources = np.asarray([0, 5])
    p_cells, seed_pos, seed_val = idx.prep_sources(sources)
    dist = np.asarray(idx.query_fn(p_cells, seed_pos, seed_val))
    # Contracted-away interiors come back via the router's polish; at
    # the index level only KEPT nodes are finite — check those.
    kept = np.flatnonzero(np.asarray(idx._expand_idx) >= 0)
    ring = np.minimum(np.abs(sources[:, None] - kept[None, :]),
                      m - np.abs(sources[:, None] - kept[None, :])) * 10.0
    finite = dist[:, kept] < 1e37
    np.testing.assert_allclose(dist[:, kept][finite], ring[finite],
                               rtol=1e-6)


def test_cache_wrong_version_rejected(force_hier, monkeypatch, tmp_path,
                                      rng):
    """A v(N≠current) payload at the right filename is rejected (and
    the router rebuilds) instead of being deserialized on trust."""
    import io

    monkeypatch.setenv("ROUTEST_HIER_CACHE", str(tmp_path))
    graph = generate_road_graph(n_nodes=1300, seed=21)
    built = RoadRouter(graph=graph, use_gnn=False, use_transformer=False)
    assert built._hier is not None
    cache_file = next(tmp_path.glob("hier-*.npz"))
    with np.load(cache_file, allow_pickle=False) as z:
        arrays = {k: z[k] for k in z.files}
    arrays["_version"] = np.int64(999)
    buf = io.BytesIO()
    np.savez_compressed(buf, **arrays)
    cache_file.write_bytes(buf.getvalue())
    from routest_tpu.optimize.hierarchy import HierarchicalIndex as HI

    assert HI.load(str(cache_file)) is None
    rebuilt = RoadRouter(graph=graph, use_gnn=False, use_transformer=False)
    assert rebuilt._hier is not None
    assert not rebuilt._hier.stats.get("loaded_from_cache")
    sources = rng.integers(0, built.n_nodes, 4)
    d0, _ = built.shortest(sources)
    d1, _ = rebuilt.shortest(sources)
    np.testing.assert_allclose(d0, d1, rtol=1e-6)


def test_build_params_change_cache_filename(monkeypatch):
    from routest_tpu.optimize.hierarchy import hier_cache_path

    monkeypatch.setenv("ROUTEST_HIER_CACHE", "/tmp/hier-param-test")
    fp = {"n_nodes": 10, "coords_crc32": 1, "n_edges": 9, "edges_crc32": 2}
    a = hier_cache_path(fp)
    monkeypatch.setenv("ROUTEST_HIER_PRUNE_SLACK", "1e-6")
    b = hier_cache_path(fp)
    monkeypatch.delenv("ROUTEST_HIER_PRUNE_SLACK")
    monkeypatch.setenv("ROUTEST_HIER_MAX_LEVELS", "1")
    c = hier_cache_path(fp)
    assert len({a, b, c}) == 3


def test_aot_buckets_compiled_and_used(force_hier, monkeypatch, rng):
    """AOT-compiled buckets serve solves without falling back to the
    jitted path, and answers match the jitted path bit-for-bit."""
    monkeypatch.setenv("ROUTEST_ROUTER_AOT", "2,16")
    monkeypatch.setenv("ROUTEST_HIER_CELL_TARGET", "64")
    router = RoadRouter(graph=generate_road_graph(n_nodes=900, seed=23),
                        use_gnn=False, use_transformer=False)
    assert sorted(router._aot) == [2, 16]
    assert router.solver_info["aot_buckets"] == [2, 16]
    sources = rng.integers(0, router.n_nodes, 2)  # bucket 2 → AOT
    d_aot, p_aot = router.shortest(sources)
    del router._aot[2]                            # force jitted fallback
    d_jit, p_jit = router.shortest(sources)
    np.testing.assert_array_equal(d_aot, d_jit)
    np.testing.assert_array_equal(p_aot, p_jit)
