"""The once-per-graph arc layout of the road GNN: under it every sum
over a node's arcs adds contiguous slabs, so the train step holds no
scatter-add; losses, gradients and predictions are those of the graph as
given, up to the order of f32 additions inside a node's sum."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import graphgen
from routest_tpu.core.dtypes import F32_POLICY
from routest_tpu.data.road_graph import generate_road_graph
from routest_tpu.models import gnn
from routest_tpu.models.gnn import (GraphBatch, RoadGNN, graph_batch,
                                    graph_layout)


def _benchmark_style():
    """Symmetric, degrees 1-4, as the benchmark's cell; one more node
    that no arc touches."""
    g = graphgen.road_graph(1500, 3800, 5, [14.04, 15.04, 120.53, 121.53])
    g["node_coords"] = np.concatenate(
        [g["node_coords"], [[14.5, 121.0]]]).astype(np.float32)
    free = g["length_m"] / np.maximum(g["speed_limit"], 0.1)
    rng = np.random.default_rng(0)
    g["time_s"] = (free * rng.uniform(1.0, 2.0, len(free))).astype(np.float32)
    g["hour"] = rng.integers(0, 24, len(free))
    return g, graph_batch(g)


def _generated():
    g = generate_road_graph(n_nodes=300, seed=2)
    return g, graph_batch(g)


def _one_way():
    g = generate_road_graph(n_nodes=300, seed=3)
    keep = np.random.default_rng(1).random(len(g["senders"])) < 0.8
    g = {k: (v[keep] if len(v) == len(keep) else v) for k, v in g.items()}
    return g, graph_batch(g)


def _padded():
    g = generate_road_graph(n_nodes=200, seed=4)
    batch = graph_batch(g, pad_to=8)    # zero-weight self-loops on node 0
    assert len(batch.senders) == len(g["senders"]) + 6
    return g, batch


GRAPHS = {"benchmark-style-with-isolated-node": _benchmark_style,
          "generate_road_graph": _generated,
          "one-way-arcs": _one_way,
          "zero-weight-padding-arcs": _padded}


def _laid_out(g, batch, lay):
    """The same batch and coordinates in the layout's order."""
    arrays = [jnp.asarray(lay.senders), jnp.asarray(lay.receivers)] + [
        jnp.asarray(np.asarray(x)[lay.arc_order]) for x in batch[2:7]]
    return (GraphBatch(*arrays, layout=jax.tree_util.tree_map(
        jnp.asarray, lay.slabs)),
        jnp.asarray(g["node_coords"][lay.node_order]))


@pytest.fixture(scope="module", params=list(GRAPHS))
def case(request):
    g, batch = GRAPHS[request.param]()
    n = len(g["node_coords"])
    lay = graph_layout(np.asarray(batch.senders), np.asarray(batch.receivers),
                       n)
    assert lay is not None
    model = RoadGNN(n_nodes=n, hidden=16, n_rounds=2, policy=F32_POLICY)
    params = model.init(jax.random.PRNGKey(0))
    laid, laid_coords = _laid_out(g, batch, lay)
    return dict(name=request.param, model=model, params=params, lay=lay,
                plain=(jnp.asarray(g["node_coords"]), batch),
                laid=(laid_coords, laid))


def test_layout_is_the_graph_renumbered(case):
    lay = case["lay"]
    _, batch = case["plain"]
    s, r = np.asarray(batch.senders), np.asarray(batch.receivers)
    assert (lay.node_order[lay.senders] == s[lay.arc_order]).all()
    assert (lay.node_order[lay.receivers] == r[lay.arc_order]).all()
    assert (lay.arc_order[lay.arc_rank] == np.arange(len(s))).all()
    runs = lay.slabs
    # every slab of a class holds one arc of each of the class's nodes,
    # in node order: into them by receiver, out of them through perm_s
    by_sender = lay.senders[runs.perm_s]
    if runs.out_unperm is not None:
        by_sender = runs.out_unperm[by_sender]
    for classes, owner in ((runs.in_classes, lay.receivers),
                           (runs.out_classes, by_sender)):
        assert sum(n for _, n in classes) == case["model"].n_nodes
        assert sum(d * n for d, n in classes) == len(s)
        node = arc = 0
        for d, n in classes:
            want = np.tile(np.arange(node, node + n), d)
            assert (owner[arc:arc + n * d] == want).all()
            node, arc = node + n, arc + n * d
    if case["name"] == "one-way-arcs":
        assert runs.out_unperm is not None
    if case["name"] == "benchmark-style-with-isolated-node":
        assert runs.in_classes[0] == (0, 1) and runs.out_unperm is None


def test_loss_and_every_gradient_leaf_agree(case):
    f = jax.jit(jax.value_and_grad(case["model"].loss))
    want_loss, want = f(case["params"], *case["plain"])
    got_loss, got = f(case["params"], *case["laid"])
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree_util.tree_leaves(got)):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_allclose(b, a, rtol=1e-6,
                                   atol=1e-6 * np.abs(a).max(),
                                   err_msg=jax.tree_util.keystr(path))


def test_apply_gives_the_same_seconds_per_arc(case):
    model, params = case["model"], case["params"]
    want = np.asarray(model.apply(params, *case["plain"]))
    got = np.asarray(model.apply(params, *case["laid"]))
    np.testing.assert_allclose(got[case["lay"].arc_rank], want, rtol=1e-5)


def _train_step(loss_fn):
    opt = optax.adamw(1e-3, weight_decay=1e-4)

    def step(params, opt_state, coords, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, coords, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return jax.jit(step), opt


def test_the_dense_train_step_lowers_to_no_scatter(case):
    step, opt = _train_step(case["model"].loss)
    state = opt.init(case["params"])
    dense = step.lower(case["params"], state, *case["laid"]).as_text()
    plain = step.lower(case["params"], state, *case["plain"]).as_text()
    assert "stablehlo.scatter" not in dense
    assert plain.count("stablehlo.scatter") >= 9    # 3 sums + 6 transposes


def _forward_before_the_layout(model, params, node_coords, batch):
    """``RoadGNN._forward`` as it stood before a batch could carry a
    layout: segment sums and gathers by index."""
    c = model.policy.compute_dtype
    coords_n = ((node_coords - jnp.asarray([14.54, 121.03],
                                           node_coords.dtype))
                * 50.0).astype(c)
    h = jax.nn.gelu(model._mlp(params["embed"], coords_n))
    ef = batch.edge_feats.astype(c)
    w = batch.weights.astype(c)
    degree = jax.ops.segment_sum(w, batch.receivers,
                                 num_segments=model.n_nodes)
    inv_deg = (1.0 / jnp.maximum(degree, 1.0))[:, None]
    for _ in range(model.n_rounds):
        m_in = jnp.concatenate(
            [h[batch.senders], h[batch.receivers], ef], axis=-1)
        messages = model._mlp(params["msg"], m_in) * w[:, None]
        agg = jax.ops.segment_sum(messages, batch.receivers,
                                  num_segments=model.n_nodes)
        agg = agg * inv_deg
        h = h + jax.nn.gelu(
            model._mlp(params["upd"], jnp.concatenate([h, agg], axis=-1)))
        h = (h - h.mean(-1, keepdims=True)) / jnp.sqrt(
            h.var(-1, keepdims=True) + 1e-6)
    r_in = jnp.concatenate(
        [h[batch.senders], h[batch.receivers], ef], axis=-1)
    out = model._mlp(params["readout"], r_in).astype(
        model.policy.output_dtype)
    freeflow = batch.length_m / jnp.maximum(batch.speed_limit, 0.1)
    return (freeflow * jax.nn.softplus(out[..., 0])
            + jax.nn.softplus(out[..., 1]))


def test_a_batch_without_a_layout_lowers_to_the_program_it_was():
    g, batch = _generated()
    model = RoadGNN(n_nodes=300, hidden=16, n_rounds=2, policy=F32_POLICY)
    params = model.init(jax.random.PRNGKey(0))

    def loss_before(params, coords, batch):
        pred = _forward_before_the_layout(model, params, coords, batch)
        err = (pred - batch.targets) ** 2 * batch.weights
        return err.sum() / jnp.maximum(batch.weights.sum(), 1.0)

    coords = jnp.asarray(g["node_coords"])
    now, opt = _train_step(model.loss)
    before, _ = _train_step(loss_before)
    args = (params, opt.init(params), coords, batch)
    assert now.lower(*args).as_text() == before.lower(*args).as_text()
    assert (jax.jit(model.apply).lower(params, coords, batch).as_text()
            == jax.jit(lambda p, c, b: _forward_before_the_layout(
                model, p, c, b)).lower(params, coords, batch).as_text()
            .replace("jit__lambda", "jit_apply"))


def test_a_graph_with_a_hub_gets_no_layout():
    # every other node sends one arc to node 0
    for spokes, laid_out in ((gnn.MAX_DEGREE, True),
                             (gnn.MAX_DEGREE + 1, False)):
        senders = np.arange(1, spokes + 1, dtype=np.int32)
        receivers = np.zeros(spokes, np.int32)
        lay = graph_layout(senders, receivers, spokes + 1)
        assert (lay is not None) == laid_out
        # and as many arcs out of one node
        assert (graph_layout(receivers, senders, spokes + 1)
                is not None) == laid_out
