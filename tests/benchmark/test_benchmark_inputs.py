"""What the benchmark makes from a seed: the device feature table, the
road graph and the probe windows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _toy import cell_files

from benchmark import graphgen, seeds, traffic


def test_od_rows_encode_as_the_program_encodes_them():
    from routest_tpu.data.features import N_FEATURES, encode_features

    _, cfg, _ = cell_files("od-score")
    stops = jnp.asarray(traffic.draw_stops(7, cfg["n_stops"], cfg["bbox"]))
    raw = traffic.od_raw_block(jax.random.PRNGKey(3), stops, 32, 32,
                               cfg["context"])
    ours = np.asarray(traffic.od_encode(*raw))
    theirs = np.asarray(encode_features(*raw))
    assert ours.shape == (32 * cfg["n_stops"], N_FEATURES)
    np.testing.assert_array_equal(ours, theirs)


def test_od_distance_is_the_haversine_of_the_pair():
    from routest_tpu.data.road_graph import haversine_np

    _, cfg, _ = cell_files("od-score")
    stops = traffic.draw_stops(7, cfg["n_stops"], cfg["bbox"])
    raw = traffic.od_raw_block(jax.random.PRNGKey(3), jnp.asarray(stops),
                               0, 32, cfg["context"])
    dist = np.asarray(raw[4]).reshape(32, cfg["n_stops"])
    want = haversine_np(stops[:32, None, 0], stops[:32, None, 1],
                        stops[None, :, 0], stops[None, :, 1]) / 1000.0
    np.testing.assert_allclose(dist, want, rtol=2e-3, atol=2e-3)


def test_od_table_is_the_blocks_in_order_and_the_same_for_a_seed():
    _, cfg, _ = cell_files("od-score")
    table = np.asarray(traffic.od_table(11, cfg))
    assert table.shape == (cfg["n_stops"] ** 2, traffic.N_FEATURES)
    np.testing.assert_array_equal(table,
                                  np.asarray(traffic.od_table(11, cfg)))
    assert not np.array_equal(table, np.asarray(traffic.od_table(12, cfg)))
    # one-hot groups, ranges and the diagonal's zero distance
    assert set(np.unique(table[:, :8])) <= {0.0, 1.0}
    np.testing.assert_array_equal(table[:, :4].sum(1), 1.0)
    np.testing.assert_array_equal(table[:, 4:8].sum(1), 1.0)
    assert table[:, 8].max() <= 6 and table[:, 9].max() <= 23
    n = cfg["n_stops"]
    np.testing.assert_allclose(table[::n + 1, 10], 0.0, atol=1e-3)
    assert 20 <= table[:, 11].min() and table[:, 11].max() <= 60


@pytest.mark.parametrize("n_nodes,n_arcs", [(2000, 5068), (5000, 12400),
                                            (1070376, 2712798)])
def test_graph_plan_gives_the_exact_counts(n_nodes, n_arcs):
    w, h, streets, bends = graphgen.plan(n_nodes, n_arcs)
    assert w * h + bends == n_nodes
    assert 2 * (streets + bends) == n_arcs
    assert streets <= w * (h - 1) + h * (w - 1)


def test_graph_has_the_configured_counts_and_repeats_for_a_seed():
    _, cfg, _ = cell_files("gnn-refit")
    g = graphgen.road_graph(cfg["n_nodes"], cfg["n_arcs"], 5, cfg["bbox"])
    assert g["node_coords"].shape == (cfg["n_nodes"], 2)
    for key in ("senders", "receivers", "length_m", "road_class",
                "speed_limit"):
        assert g[key].shape == (cfg["n_arcs"],)
    assert g["senders"].max() < cfg["n_nodes"]
    assert (g["senders"] != g["receivers"]).all()
    # symmetric: every arc has its reverse
    fwd = set(zip(g["senders"].tolist(), g["receivers"].tolist()))
    assert all((b, a) in fwd for a, b in fwd)
    again = graphgen.road_graph(cfg["n_nodes"], cfg["n_arcs"], 5,
                                cfg["bbox"])
    assert all(np.array_equal(g[k], again[k]) for k in g)
    other = graphgen.road_graph(cfg["n_nodes"], cfg["n_arcs"], 6,
                                cfg["bbox"])
    assert not np.array_equal(g["senders"], other["senders"])


def test_every_seed_draws_a_graph_with_the_same_degree_counts():
    """The trainer's step program holds the number of nodes of each
    degree in its shapes: were they drawn from the seed, the seed would
    choose the program that is timed."""
    _, cfg, _ = cell_files("gnn-refit")
    counts = []
    for seed in (5, 6, 2 ** 31 + 11):
        g = graphgen.road_graph(cfg["n_nodes"], cfg["n_arcs"], seed,
                                cfg["bbox"])
        degree = np.bincount(g["receivers"], minlength=cfg["n_nodes"])
        counts.append(np.bincount(degree, minlength=5).tolist())
    assert counts[0] == counts[1] == counts[2]
    assert len(counts[0]) == 5 and min(counts[0][1:]) > 0


def test_graph_refuses_counts_no_grid_gives():
    with pytest.raises(ValueError):
        graphgen.plan(1000, 1999)
    with pytest.raises(ValueError):
        graphgen.plan(1000, 1800)


def test_probe_windows_have_one_size_and_follow_the_seed():
    _, cfg, mix = cell_files("gnn-refit")
    g = graphgen.road_graph(cfg["n_nodes"], cfg["n_arcs"], 5, cfg["bbox"])
    a = traffic.ProbeSource(21, g, mix)
    b = traffic.ProbeSource(21, g, mix)
    c = traffic.ProbeSource(22, g, mix)
    wa, wb, wc = a.window(), b.window(), c.window()
    for x, y in zip(wa, wb):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(wa[0], wc[0])
    assert all(len(x) == mix["probes_per_window"] for x in wa + wc)
    edge, hour, seconds = wa
    assert edge.max() < cfg["n_arcs"] and (seconds > 0).all()
    per = mix["probes_per_batch"]
    assert (hour.reshape(-1, per) == hour[::per, None]).all()
    # skewed towards arterials: class 0 is a fifth of the arcs
    assert (g["road_class"][edge] == 0).mean() > 0.4


@pytest.mark.parametrize("seed", [0, 1, 2 ** 31 + 7, 2 ** 32 + 5])
def test_sub_seeds_fit_31_bits_and_differ_by_name(seed):
    a, b = seeds.sub_seed(seed, "graph"), seeds.sub_seed(seed, "probes")
    assert 0 <= a < 2 ** 31 and 0 <= b < 2 ** 31 and a != b
    assert a == seeds.sub_seed(seed, "graph")
    jax.random.PRNGKey(a)
