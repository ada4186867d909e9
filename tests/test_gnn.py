"""Road-graph GNN: forward, edge-sharded parity, training convergence."""

import jax
import numpy as np
import optax
import pytest

from routest_tpu.core.dtypes import F32_POLICY
from routest_tpu.data.road_graph import generate_road_graph
from routest_tpu.models.gnn import (GraphBatch, RoadGNN, edge_feature_array,
                                    graph_batch)


def _small_graph(n=256, seed=0):
    return generate_road_graph(n_nodes=n, k=3, seed=seed)


def test_graph_generator_shapes():
    g = _small_graph()
    assert g["node_coords"].shape == (256, 2)
    e = len(g["senders"])
    assert len(g["receivers"]) == e == len(g["time_s"])
    # symmetrized: every edge appears in both directions
    fwd = set(zip(g["senders"].tolist(), g["receivers"].tolist()))
    assert all((r, s) in fwd for s, r in list(fwd)[:50])
    assert (g["time_s"] > 0).all()


def test_forward_shapes():
    g = _small_graph()
    model = RoadGNN(n_nodes=256, hidden=32, n_rounds=2, policy=F32_POLICY)
    params = model.init(jax.random.PRNGKey(0))
    batch = graph_batch(g)
    pred = model.apply(params, g["node_coords"], batch)
    assert pred.shape == (len(g["senders"]),)
    assert bool((pred > 0).all())


def test_sharded_loss_matches_dense(mesh_runtime):
    g = _small_graph()
    model = RoadGNN(n_nodes=256, hidden=32, n_rounds=2, policy=F32_POLICY)
    params = model.init(jax.random.PRNGKey(1))
    coords = g["node_coords"]

    dense_batch = graph_batch(g)
    dense = float(model.loss(params, coords, dense_batch))

    padded = graph_batch(g, pad_to=mesh_runtime.n_data)
    sharded_loss = model.make_sharded_loss(mesh_runtime.mesh)
    shard = float(jax.jit(sharded_loss)(params, coords, padded))

    assert abs(dense - shard) < 1e-2 * max(1.0, dense)


def test_sharded_training_reduces_loss(mesh_runtime):
    g = _small_graph()
    model = RoadGNN(n_nodes=256, hidden=32, n_rounds=2, policy=F32_POLICY)
    params = model.init(jax.random.PRNGKey(2))
    optimizer = optax.adam(3e-3)
    opt_state = optimizer.init(params)
    step = model.make_sharded_train_step(mesh_runtime.mesh, optimizer)
    batch = graph_batch(g, pad_to=mesh_runtime.n_data)
    coords = g["node_coords"]

    losses = []
    for _ in range(30):
        params, opt_state, loss = step(params, opt_state, coords, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.5, losses[::10]


def test_padding_does_not_change_loss():
    g = _small_graph()
    model = RoadGNN(n_nodes=256, hidden=16, n_rounds=1, policy=F32_POLICY)
    params = model.init(jax.random.PRNGKey(3))
    a = float(model.loss(params, g["node_coords"], graph_batch(g)))
    b = float(model.loss(params, g["node_coords"], graph_batch(g, pad_to=64)))
    assert abs(a - b) < 1e-3 * max(1.0, a)


@pytest.mark.parametrize("hours", [*range(24), "mixed"])
def test_the_hour_table_indexed_by_hours_is_the_hosts_own_hour_features(
        hours):
    """``live/trainer.py`` writes a batch's hour columns on the device
    by row lookup in ``hour_table()``: bit for bit what
    ``_hour_features`` computes on the host for the same hours."""
    from routest_tpu.models.gnn import (_hour_features, hour_table,
                                        set_hour_columns)

    if hours == "mixed":
        hours = np.random.default_rng(3).integers(0, 24, 4096)
    hours = np.broadcast_to(np.asarray(hours, np.int32), (4096,))
    want = _hour_features(hours)
    table = hour_table()
    assert table.shape == (24, 8) and table.dtype == np.float32
    assert table[hours].tobytes() == want.tobytes()
    # and through the program the trainer jits, into a table whose
    # hour columns held another hour's
    rng = np.random.default_rng(5)
    args = (rng.uniform(5.0, 900.0, 4096), rng.uniform(3.0, 30.0, 4096),
            rng.integers(0, 3, 4096))
    got = jax.jit(set_hour_columns, donate_argnums=0)(
        jax.numpy.asarray(edge_feature_array(*args, 0)),
        jax.numpy.asarray(hours), jax.numpy.asarray(table))
    assert np.asarray(got).tobytes() == edge_feature_array(
        *args, hours).tobytes()
