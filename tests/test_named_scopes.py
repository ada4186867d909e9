"""Named scopes where the device time goes: every scope reaches the
lowered programs' debug info (an xplane's ``tf_op``), and none changes
a value."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from routest_tpu.core.dtypes import F32_POLICY
from routest_tpu.data.road_graph import generate_road_graph
from routest_tpu.models.eta_mlp import EtaMLP
from routest_tpu.models.gnn import (GraphBatch, RoadGNN, graph_batch,
                                    graph_layout)

GNN_SCOPES = (["gnn.embed", "gnn.degree", "gnn.readout.gather",
               "gnn.readout"]
              + [f"gnn.round{i}.{part}" for i in range(2)
                 for part in ("gather", "message", "scatter", "update",
                              "norm")])
ETA_SCOPES = ["eta.expand", "eta.layer0", "eta.layer1", "eta.layer2",
              "eta.heads"]


def _gnn_step():
    g = generate_road_graph(n_nodes=96, seed=1)
    model = RoadGNN(n_nodes=96, hidden=16, n_rounds=2, policy=F32_POLICY)
    params = model.init(jax.random.PRNGKey(0))
    opt = optax.adamw(1e-3, weight_decay=1e-4)

    def step(params, opt_state, coords, batch):
        loss, grads = jax.value_and_grad(model.loss)(params, coords, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return step, (params, opt.init(params),
                  jnp.asarray(g["node_coords"]), graph_batch(g))


def _gnn_step_laid_out():
    """The same step over a batch in a ``GraphLayout``'s order: the
    dense path of ``RoadGNN._forward``, as the live trainer runs it."""
    step, (params, opt_state, coords, batch) = _gnn_step()
    lay = graph_layout(np.asarray(batch.senders),
                       np.asarray(batch.receivers), 96)
    laid = GraphBatch(
        jnp.asarray(lay.senders), jnp.asarray(lay.receivers),
        *(x[lay.arc_order] for x in batch[2:7]),
        layout=jax.tree_util.tree_map(jnp.asarray, lay.slabs))
    return step, (params, opt_state, coords[lay.node_order], laid)


def _eta_quantiles():
    model = EtaMLP(quantiles=(0.1, 0.5, 0.9))
    params = model.init(jax.random.PRNGKey(0))
    x = jax.random.uniform(jax.random.PRNGKey(1), (64, 12)) * 5.0
    return model.apply_quantiles, (params, x)


PROGRAMS = {"gnn-train-step": (_gnn_step, GNN_SCOPES),
            "gnn-train-step-laid-out": (_gnn_step_laid_out, GNN_SCOPES),
            "eta-apply-quantiles": (_eta_quantiles, ETA_SCOPES)}


@pytest.mark.parametrize("name", PROGRAMS)
def test_lowered_text_holds_every_scope(name):
    build, scopes = PROGRAMS[name]
    fn, args = build()
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    # forward-only: ``jit(f)/eta.layer0/dot_general``; under grad the
    # scope is wrapped: ``jvp(gnn.embed)``, ``transpose(jvp(gnn.embed))``
    missing = [s for s in scopes
               if f"{s}/" not in text and f"({s})" not in text]
    assert not missing, missing
    if name.startswith("gnn-train-step"):   # the backward pass is named too
        assert "transpose(jvp(gnn.round1.scatter))" in text


@pytest.mark.parametrize("name", PROGRAMS)
def test_outputs_are_bitwise_what_they_were_without_scopes(name,
                                                           monkeypatch):
    build, _ = PROGRAMS[name]
    fn, args = build()
    with_scopes = jax.jit(fn)(*args)
    monkeypatch.setattr(jax, "named_scope",
                        lambda _name: contextlib.nullcontext())
    fn, args = build()
    lowered = jax.jit(fn).lower(*args)
    text = lowered.as_text(debug_info=True)
    assert "gnn.round" not in text and "eta.layer" not in text
    without = lowered.compile()(*args)
    for a, b in zip(jax.tree_util.tree_leaves(with_scopes),
                    jax.tree_util.tree_leaves(without)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
