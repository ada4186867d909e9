"""Road-graph GNN: learned leg costs via message passing, edge-sharded.

BASELINE.json config 4. The reference has no graph model at all (ORS owns
the road network); here a message-passing GNN learns per-edge travel
times from the road graph (``data/road_graph.py``), the on-device
replacement for "ask ORS how long this leg takes".

Distribution design (SURVEY.md §5.7 — the long-sequence analog): the
**edge set** is the long axis. Edges shard across the mesh ``data`` axis
under ``shard_map``; node states are replicated. Each round:

1. every device computes messages for its edge shard (dense matmuls —
   MXU work, fully parallel);
2. per-device ``segment_sum`` scatters messages into a full-size node
   accumulator — the *partial* aggregation over local edges;
3. one ``psum`` over the data axis combines partials into the global
   neighborhood aggregation (the halo exchange, batched into a single
   all-reduce over ICI);
4. the (replicated) node update runs identically everywhere.

Gradients flow through the psum (XLA differentiates collectives), so the
same shard_map program is the training step.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from routest_tpu.core.smap import shard_map

from routest_tpu.core.dtypes import DEFAULT_POLICY, Policy

Params = Dict

_N_CLASSES = 3
_N_HOUR_FEATURES = 8  # four Fourier harmonics of hour-of-day
# [log_length, speed_limit/10] + class one-hot + cyclical hour
N_EDGE_FEATURES = 2 + _N_CLASSES + _N_HOUR_FEATURES


# A road network's nodes have a handful of arcs (a grid 0-4, an OSM
# extract under 10). A graph in which some node has more than this many,
# in or out, is hub-and-spoke, not roads: it gets no layout and keeps
# the indexed sums (a sum under a layout is one addition a slab, so a
# hub of 500 arcs would be 500 one-row additions).
MAX_DEGREE = 16

# (degree, number of nodes of that degree), by rising degree
Classes = Tuple[Tuple[int, int], ...]


@dataclasses.dataclass(frozen=True)
class ArcSlabs:
    """What the device program reads of a ``GraphLayout``. Nodes are
    grouped by in-degree; the arcs into a class of ``n`` nodes of degree
    ``d`` are ``d`` slabs of ``n`` rows, slab ``k`` holding the ``k``-th
    arc of every node of the class, in node order. A segment sum is then
    the sum of a class's slabs and its transpose (the gather
    ``h[receivers]``) the class's rows ``d`` times over: contiguous
    slices and a concatenation, no index, no reshape."""

    perm_s: jax.Array       # (E,) int32: the arcs as slabs by sender
    # (N,) int32, or None where out-degree order is node order (a
    # symmetric graph): takes sums in out-degree order back to nodes
    out_unperm: Optional[jax.Array]
    in_classes: Classes
    out_classes: Classes


jax.tree_util.register_dataclass(
    ArcSlabs, data_fields=["perm_s", "out_unperm"],
    meta_fields=["in_classes", "out_classes"])


class GraphBatch(NamedTuple):
    senders: jax.Array     # (E,) int32
    receivers: jax.Array   # (E,) int32
    edge_feats: jax.Array  # (E, F)
    length_m: jax.Array    # (E,)
    speed_limit: jax.Array  # (E,) m/s
    targets: jax.Array     # (E,) observed seconds
    weights: jax.Array     # (E,) 0/1 (padding mask)
    # set only on a batch whose arcs and nodes are in a GraphLayout's
    # order: the forward then takes the dense path
    layout: Optional[ArcSlabs] = None


@dataclasses.dataclass(frozen=True)
class GraphLayout:
    """A static graph renumbered once, on the host, so that no sum over
    a node's arcs needs an index (``graph_layout`` builds it). The model
    has no per-node parameter, so losses and gradients are those of the
    graph as given."""

    node_order: np.ndarray  # (N,) laid-out node -> the graph's node
    arc_order: np.ndarray   # (E,) laid-out arc -> the graph's arc
    arc_rank: np.ndarray    # (E,) the graph's arc -> laid-out arc
    senders: np.ndarray     # (E,) int32, laid-out arcs and nodes
    receivers: np.ndarray   # (E,) int32
    slabs: ArcSlabs         # numpy arrays until uploaded


def _inverse(order: np.ndarray) -> np.ndarray:
    rank = np.empty(len(order), np.int32)
    rank[order] = np.arange(len(order), dtype=np.int32)
    return rank


def _slab_order(owner: np.ndarray, degree: np.ndarray):
    """Arcs as slabs. ``owner`` (E,) is each arc's node, numbered so
    that ``degree`` (N,), the arcs a node owns, rises with the number.
    Returns the arcs in slab order and the degree classes."""
    values, first, counts = np.unique(degree, return_index=True,
                                      return_counts=True)
    by_owner = np.argsort(owner, kind="stable")
    run_start = np.cumsum(degree) - degree  # of a node's arcs, by_owner
    node = owner[by_owner]
    k = np.arange(len(owner)) - run_start[node]     # which of its arcs
    class_of = np.repeat(np.arange(len(values)), counts)[node]
    slot = (run_start[first][class_of] + k * counts[class_of]
            + node - first[class_of])
    order = np.empty(len(owner), np.int64)
    order[slot] = by_owner
    return order, tuple((int(d), int(n)) for d, n in zip(values, counts))


def graph_layout(senders: np.ndarray, receivers: np.ndarray,
                 n_nodes: int) -> Optional[GraphLayout]:
    """The layout of a graph, or None where its degrees do not suit (a
    node with more than ``MAX_DEGREE`` arcs in or out). Numpy, a few
    stable argsorts over the arcs."""
    in_deg = np.bincount(receivers, minlength=n_nodes)
    out_deg = np.bincount(senders, minlength=n_nodes)
    if max(in_deg.max(initial=0), out_deg.max(initial=0)) > MAX_DEGREE:
        return None
    node_order = np.argsort(in_deg, kind="stable")
    node_rank = _inverse(node_order)
    arc_order, in_classes = _slab_order(node_rank[receivers],
                                        in_deg[node_order])
    laid_s, laid_r = (node_rank[ends][arc_order]
                      for ends in (senders, receivers))
    # the sender side: nodes by out-degree, which on a symmetric graph
    # is the order they are already in
    out_laid = out_deg[node_order]
    by_out = np.argsort(out_laid, kind="stable")
    out_rank = _inverse(by_out)
    perm_s, out_classes = _slab_order(out_rank[laid_s], out_laid[by_out])
    symmetric = bool((by_out == np.arange(n_nodes)).all())
    return GraphLayout(
        node_order=node_order, arc_order=arc_order,
        arc_rank=_inverse(arc_order), senders=laid_s, receivers=laid_r,
        slabs=ArcSlabs(perm_s=perm_s.astype(np.int32),
                       out_unperm=None if symmetric else out_rank,
                       in_classes=in_classes, out_classes=out_classes))


def _split(x: jax.Array, sizes):
    """``x`` cut into consecutive pieces of ``sizes`` rows."""
    return jnp.split(x, np.cumsum(sizes)[:-1].tolist())


def _sum_slabs(x: jax.Array, classes: Classes,
               rows: Optional[jax.Array] = None) -> jax.Array:
    """(Σ n·d, …) arc rows → (Σ n, …) node rows: each class's slabs
    summed, first to last as a scatter-add by sorted index would.
    ``rows`` (Σ n·d,) names the rows of ``x`` the slabs are made of,
    where they are not ``x``'s own in order."""
    sizes = [n for d, n in classes for _ in range(d)]
    if rows is None:
        slabs = iter(_split(x, sizes))
    else:
        slabs = (x[r] for r in _split(rows, sizes))
    out = []
    for d, n in classes:
        if d == 0:
            out.append(jnp.zeros((n,) + x.shape[1:], x.dtype))
            continue
        acc = next(slabs)
        for _ in range(d - 1):
            acc = acc + next(slabs)
        out.append(acc)
    return jnp.concatenate(out)


def _spread_slabs(h: jax.Array, classes: Classes) -> jax.Array:
    """(Σ n, …) → (Σ n·d, …): each class's rows d times over. The
    transpose of ``_sum_slabs``, and autodiff turns either into the
    other (a split transposes to a concatenation)."""
    blocks = _split(h, [n for _, n in classes])
    return jnp.concatenate([block for (d, _), block in zip(classes, blocks)
                            for _ in range(d)])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _take_senders(out_classes: Classes, h, senders, perm_s, out_unperm):
    """``h[senders]`` whose transpose sums slabs gathered through
    ``perm_s`` (each row read once) instead of autodiff's scatter-add
    by an unsorted index."""
    return h[senders]


def _take_senders_fwd(out_classes, h, senders, perm_s, out_unperm):
    return h[senders], (perm_s, out_unperm)


def _take_senders_bwd(out_classes, res, g):
    perm_s, out_unperm = res
    dh = _sum_slabs(g, out_classes, rows=perm_s)
    if out_unperm is not None:
        dh = dh[out_unperm]
    return dh, None, None, None


_take_senders.defvjp(_take_senders_fwd, _take_senders_bwd)


def _hour_features(hour: np.ndarray) -> np.ndarray:
    """(E,) hour-of-day → (E, 8) Fourier features.

    Cyclical, not one-hot: the model has to learn the *shape* of the
    congestion curve, so it can generalize to hours whose labels were
    held out of training — the non-circular evaluation regime
    (``scripts/train_gnn.py``). One-hot hours could only memorize
    per-hour offsets.

    Four harmonics, not two: real (and the generator's) congestion
    curves have ~2-hour-wide rush peaks and a sharp night shoulder —
    features a 2-harmonic basis cannot express, which left both learned
    pricers ~1.5x above their noise floors (VERDICT r3 weak #6). The
    higher harmonics stay smooth, so held-out-hour generalization is
    preserved while the representable curve family gets the needed
    sharpness.
    """
    ang = np.asarray(hour, np.float32) * np.float32(2.0 * np.pi / 24.0)
    return np.stack([np.sin(k * ang) if trig == "s" else np.cos(k * ang)
                     for k in (1, 2, 3, 4) for trig in ("s", "c")], axis=-1)


def edge_feature_array(length_m: np.ndarray, speed_limit: np.ndarray,
                       road_class: np.ndarray, hour) -> np.ndarray:
    """Edge features from raw arrays; ``hour`` is scalar or (E,).

    Public for serving: the road router builds features at the request's
    pickup hour without a full graph dict.
    """
    e = len(length_m)
    out = np.zeros((e, N_EDGE_FEATURES), np.float32)
    out[:, 0] = np.log1p(length_m)
    out[:, 1] = speed_limit / 10.0
    out[np.arange(e), 2 + road_class] = 1.0
    out[:, 2 + _N_CLASSES:] = _hour_features(np.broadcast_to(hour, (e,)))
    return out


def hour_table() -> np.ndarray:
    """(24, 8) float32: ``_hour_features`` of every hour of the day.
    Row ``h`` is bit for bit what ``_hour_features`` gives an arc whose
    hour is ``h``, so a table indexed by (E,) hours is the host's own
    hour columns wherever the lookup runs."""
    return _hour_features(np.arange(24))


def set_hour_columns(edge_feats: jax.Array, hours: jax.Array,
                     table: jax.Array) -> jax.Array:
    """An ``edge_feature_array`` table with its hour columns rewritten
    for ``hours`` (E,) int32 in 0..23, from ``hour_table()``: a row
    lookup, no arithmetic, so the values are the host's. For a caller
    that keeps the table on the device and jits this with the table
    donated, the rewrite is in place."""
    return edge_feats.at[:, 2 + _N_CLASSES:].set(table[hours])


def edge_features(graph: Dict[str, np.ndarray]) -> np.ndarray:
    return edge_feature_array(graph["length_m"], graph["speed_limit"],
                              graph["road_class"], graph["hour"])


def graph_batch(graph: Dict[str, np.ndarray], pad_to: int = 0) -> GraphBatch:
    """Pack a road-graph dict into a GraphBatch, optionally padded so the
    edge count divides the mesh data axis. Padded edges self-loop node 0
    with zero weight."""
    e = len(graph["senders"])
    target_e = max(e, pad_to) if pad_to else e
    if pad_to and target_e % pad_to:
        target_e = ((target_e + pad_to - 1) // pad_to) * pad_to

    def pad(x, fill=0):
        if len(x) == target_e:
            return x
        return np.concatenate([x, np.full((target_e - len(x),) + x.shape[1:],
                                          fill, x.dtype)])

    return GraphBatch(
        senders=jnp.asarray(pad(graph["senders"])),
        receivers=jnp.asarray(pad(graph["receivers"])),
        edge_feats=jnp.asarray(pad(edge_features(graph))),
        length_m=jnp.asarray(pad(graph["length_m"])),
        speed_limit=jnp.asarray(pad(graph["speed_limit"], 1.0)),
        targets=jnp.asarray(pad(graph["time_s"])),
        weights=jnp.asarray(pad(np.ones(e, np.float32))),
    )


@dataclasses.dataclass(frozen=True)
class RoadGNN:
    n_nodes: int
    hidden: int = 64
    n_rounds: int = 2
    policy: Policy = DEFAULT_POLICY

    def _mlp_init(self, key, dims):
        layers = []
        for d_in, d_out in zip(dims[:-1], dims[1:]):
            key, sub = jax.random.split(key)
            layers.append({
                "w": jax.random.normal(sub, (d_in, d_out),
                                       self.policy.param_dtype)
                * jnp.sqrt(2.0 / d_in),
                "b": jnp.zeros((d_out,), self.policy.param_dtype),
            })
        return key, layers

    def init(self, key: jax.Array) -> Params:
        h = self.hidden
        key, embed = self._mlp_init(key, (2, h))
        key, msg = self._mlp_init(key, (2 * h + N_EDGE_FEATURES, h, h))
        key, upd = self._mlp_init(key, (2 * h, h))
        key, readout = self._mlp_init(key, (2 * h + N_EDGE_FEATURES, h, 2))
        return {"embed": embed, "msg": msg, "upd": upd, "readout": readout}

    def _mlp(self, layers, x):
        c = self.policy.compute_dtype
        for layer in layers[:-1]:
            x = jax.nn.gelu(x @ layer["w"].astype(c) + layer["b"].astype(c))
        return x @ layers[-1]["w"].astype(c) + layers[-1]["b"].astype(c)

    def _forward(self, params: Params, node_coords: jax.Array,
                 batch: GraphBatch, combine) -> jax.Array:
        """Per-edge predicted seconds. ``combine`` merges per-shard node
        aggregations (identity on one device; psum under shard_map)."""
        c = self.policy.compute_dtype
        # The named scopes are metadata only: they land in each
        # instruction's ``op_name`` (an xplane's ``tf_op``), so that a
        # device trace tells the gathers, the segment sums and the
        # small products of each round apart.
        with jax.named_scope("gnn.embed"):
            coords_n = ((node_coords
                         - jnp.asarray([14.54, 121.03], node_coords.dtype))
                        * 50.0).astype(c)
            h = jax.nn.gelu(self._mlp(params["embed"], coords_n))
            ef = batch.edge_feats.astype(c)
            w = batch.weights.astype(c)
        lay = batch.layout
        if lay is None:
            def to_nodes(x):        # per-arc rows summed by receiver
                return jax.ops.segment_sum(x, batch.receivers,
                                           num_segments=self.n_nodes)

            def arc_ends(h):
                return h[batch.senders], h[batch.receivers]
        else:
            def to_nodes(x):
                return _sum_slabs(x, lay.in_classes)

            def arc_ends(h):
                return (_take_senders(lay.out_classes, h, batch.senders,
                                      lay.perm_s, lay.out_unperm),
                        _spread_slabs(h, lay.in_classes))
        # in-degree for mean aggregation (hub nodes would otherwise blow up
        # activations through the rounds and destabilize training)
        with jax.named_scope("gnn.degree"):
            degree = combine(to_nodes(w))
            inv_deg = (1.0 / jnp.maximum(degree, 1.0))[:, None]
        for i in range(self.n_rounds):
            with jax.named_scope(f"gnn.round{i}.gather"):
                m_in = jnp.concatenate([*arc_ends(h), ef], axis=-1)
            with jax.named_scope(f"gnn.round{i}.message"):
                # padded edges (weight 0) must not inject messages
                messages = self._mlp(params["msg"], m_in) * w[:, None]
            with jax.named_scope(f"gnn.round{i}.scatter"):
                agg = combine(to_nodes(messages)) * inv_deg
            with jax.named_scope(f"gnn.round{i}.update"):
                h = h + jax.nn.gelu(
                    self._mlp(params["upd"],
                              jnp.concatenate([h, agg], axis=-1))
                )
            with jax.named_scope(f"gnn.round{i}.norm"):
                # parameter-free layer norm keeps round-over-round scale
                # stable
                h = (h - h.mean(-1, keepdims=True)) / jnp.sqrt(
                    h.var(-1, keepdims=True) + 1e-6)
        with jax.named_scope("gnn.readout.gather"):
            r_in = jnp.concatenate([*arc_ends(h), ef], axis=-1)
        with jax.named_scope("gnn.readout"):
            out = self._mlp(params["readout"], r_in).astype(
                self.policy.output_dtype)
            # Physical decomposition, as in the ETA model: free-flow time
            # scaled by a learned congestion factor, plus learned fixed
            # overhead.
            freeflow = batch.length_m / jnp.maximum(batch.speed_limit, 0.1)
            return (freeflow * jax.nn.softplus(out[..., 0])
                    + jax.nn.softplus(out[..., 1]))

    def apply(self, params: Params, node_coords: jax.Array,
              batch: GraphBatch) -> jax.Array:
        """Single-device forward: (E,) predicted seconds."""
        return self._forward(params, node_coords, batch, combine=lambda x: x)

    def loss(self, params: Params, node_coords: jax.Array,
             batch: GraphBatch, combine=lambda x: x,
             reduce=lambda x: x, loss_weights=None) -> jax.Array:
        """Weighted MSE. ``batch.weights`` masks MESSAGES (padding must
        not inject aggregation); ``loss_weights`` (default: the same
        mask) selects which edges the LOSS reads. The live-traffic
        trainer needs the split: probes label a subset of edges, but
        every real edge must still carry messages or the aggregation
        the model serves under would differ from the one it trained
        under."""
        pred = self._forward(params, node_coords, batch, combine)
        lw = batch.weights if loss_weights is None else loss_weights
        err = (pred - batch.targets) ** 2 * lw
        total = reduce(err.sum())
        count = reduce(lw.sum())
        return total / jnp.maximum(count, 1.0)

    # ── mesh-parallel build ────────────────────────────────────────────

    def make_sharded_loss(self, mesh, data_axis: str = "data"):
        """Loss with edges sharded over the mesh data axis: senders/
        receivers/features split per device, node states replicated, one
        psum per round combining neighborhood aggregations."""
        # no layout: sharding a laid-out graph is not built yet
        batch_spec = GraphBatch(*([P(data_axis)] * 7))

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P(), P(), batch_spec),
            out_specs=P(),
        )
        def sharded_loss(params, node_coords, batch):
            combine = functools.partial(jax.lax.psum, axis_name=data_axis)
            return self.loss(params, node_coords, batch,
                             combine=combine, reduce=combine)

        return sharded_loss

    def make_sharded_train_step(self, mesh, optimizer, data_axis: str = "data"):
        loss_fn = self.make_sharded_loss(mesh, data_axis)

        @jax.jit
        def step(params, opt_state, node_coords, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, node_coords, batch)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            import optax

            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        return step
