"""Plain reference of the road GNN's live refit: host aggregation of the
probe window, forward, masked loss, gradients and AdamW, in float32 at
the matmul precision the configuration states (``matmul_precision``).

The trainer's policy is float32 at XLA's default precision, which on a
TPU rounds the operands of every product to bfloat16 and accumulates in
float32 (``highest`` does not fit the chip at this graph's size: PERF.md).
The reference states the same, so what is compared is the arithmetic and
not that rounding; on a CPU both are exact float32.

Equations (models/gnn.py, live/trainer.py docstrings):

- window → per-arc target = mean observed seconds; hour = the last
  observed hour of the arc, or the pinned clock hour where unobserved;
  the loss reads observed arcs only, every arc carries messages;
- arc features (13): log1p(length), speed/10, class one-hot(3), four
  Fourier harmonics of the hour (sin, cos per harmonic);
- h0 = gelu(W_e · 50·(coords − centre) + b_e); then per round:
  m = MLP_msg([h_s, h_r, f]) (gelu between its two layers), mean over
  incoming arcs, h ← LN(h + gelu(W_u [h, agg] + b_u)) with a
  parameter-free layer norm (eps 1e-6);
- out = MLP_ro([h_s, h_r, f]); seconds = length / max(speed, 0.1) ·
  softplus(out_0) + softplus(out_1); loss = mean squared error over
  the observed arcs;
- AdamW: m, v moments with bias correction, eps outside the root,
  decoupled weight decay added to the update before the learning rate.

Init is He-normal weights and zero biases drawn with ``jax.random``
from the seed in the order embed, message (2 layers), update, readout
(2 layers), one key split per layer: the published init of the model.

``dtype`` bfloat16 is the control: the same arithmetic with parameters,
inputs and activations cast to bfloat16, as a later PR tempted to flip
the trainer's policy would compute it.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

LEAVES = [(group, i, leaf) for group, n in
          (("embed", 1), ("msg", 2), ("upd", 1), ("readout", 2))
          for i in range(n) for leaf in ("w", "b")]


def leaf_name(group: str, i: int, leaf: str) -> str:
    return f"{group}{i}.{leaf}"


def init_params(seed: int, hidden: int, n_edge_features: int) -> Dict:
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed)
    h, f = hidden, n_edge_features
    shapes = {"embed": (2, h), "msg": (2 * h + f, h, h), "upd": (2 * h, h),
              "readout": (2 * h + f, h, 2)}
    params = {}
    for group in ("embed", "msg", "upd", "readout"):
        dims, layers = shapes[group], []
        for d_in, d_out in zip(dims[:-1], dims[1:]):
            key, sub = jax.random.split(key)
            layers.append({
                "w": jax.random.normal(sub, (d_in, d_out), jnp.float32)
                * jnp.sqrt(2.0 / d_in),
                "b": jnp.zeros((d_out,), jnp.float32)})
        params[group] = layers
    return params


def aggregate_window(n_arcs: int, edge, hour, seconds, pinned_hour: int):
    """(targets, hours, observed) per arc from one window, oldest first."""
    sums = np.zeros(n_arcs, np.float64)
    counts = np.zeros(n_arcs, np.float64)
    hours = np.full(n_arcs, pinned_hour, np.int32)
    for e, h, s in zip(edge.tolist(), hour.tolist(), seconds.tolist()):
        sums[e] += s
        counts[e] += 1.0
        hours[e] = h                      # the last occurrence stands
    observed = counts > 0
    targets = np.zeros(n_arcs, np.float32)
    targets[observed] = (sums[observed] / counts[observed]).astype(np.float32)
    return targets, hours, observed


def edge_features(length_m, speed_limit, road_class, hours) -> np.ndarray:
    ang = hours.astype(np.float32) * np.float32(2.0 * np.pi / 24.0)
    cols = [np.log1p(length_m.astype(np.float32)),
            speed_limit.astype(np.float32) / 10.0]
    cols += [(road_class == c).astype(np.float32) for c in range(3)]
    for k in (1, 2, 3, 4):
        cols += [np.sin(k * ang), np.cos(k * ang)]
    return np.stack(cols, axis=1).astype(np.float32)


def _gelu(x):
    import jax.numpy as jnp

    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _softplus(x):
    import jax.numpy as jnp

    return jnp.logaddexp(x, 0.0)


_PRECISION = {"default": "DEFAULT", "highest": "HIGHEST"}


def _dense(x, layer, dtype, precision: str):
    import jax
    import jax.numpy as jnp

    return (jnp.dot(x, layer["w"].astype(dtype),
                    precision=getattr(jax.lax.Precision,
                                      _PRECISION[precision]))
            + layer["b"].astype(dtype))


def predict(params, cfg: Dict, coords, senders, receivers, feats,
            length_m, speed_limit, dtype):
    """(A,) predicted seconds of every arc, every product at the
    configuration's matmul precision."""
    import jax.numpy as jnp

    n_nodes = coords.shape[0]
    prec = cfg["matmul_precision"]
    centre = jnp.asarray(cfg["coord_centre"], jnp.float32)
    x = ((coords - centre) * cfg["coord_scale"]).astype(dtype)
    h = _gelu(_dense(x, params["embed"][0], dtype, prec))
    f = feats.astype(dtype)
    degree = jnp.zeros((n_nodes,), dtype).at[receivers].add(
        jnp.ones(receivers.shape, dtype))
    inv_deg = (1.0 / jnp.maximum(degree, 1.0))[:, None]
    for _ in range(cfg["n_rounds"]):
        m_in = jnp.concatenate([h[senders], h[receivers], f], axis=1)
        m = _dense(_gelu(_dense(m_in, params["msg"][0], dtype, prec)),
                   params["msg"][1], dtype, prec)
        agg = jnp.zeros((n_nodes, m.shape[1]), dtype).at[receivers].add(m)
        agg = agg * inv_deg
        h = h + _gelu(_dense(jnp.concatenate([h, agg], axis=1),
                             params["upd"][0], dtype, prec))
        h = (h - h.mean(axis=1, keepdims=True)) / jnp.sqrt(
            h.var(axis=1, keepdims=True) + 1e-6)
    r_in = jnp.concatenate([h[senders], h[receivers], f], axis=1)
    out = _dense(_gelu(_dense(r_in, params["readout"][0], dtype, prec)),
                 params["readout"][1], dtype, prec).astype(jnp.float32)
    freeflow = length_m / jnp.maximum(speed_limit, 0.1)
    return freeflow * _softplus(out[:, 0]) + _softplus(out[:, 1])


def loss_fn(params, cfg, coords, senders, receivers, feats, length_m,
            speed_limit, targets, observed, dtype):
    import jax.numpy as jnp

    pred = predict(params, cfg, coords, senders, receivers, feats,
                   length_m, speed_limit, dtype)
    err = (pred - targets) ** 2 * observed
    return err.sum() / jnp.maximum(observed.sum(), 1.0)


def adamw(params, grads, m, v, t: int, cfg: Dict):
    import jax
    import jax.numpy as jnp

    b1, b2, eps = cfg["adam"]["b1"], cfg["adam"]["b2"], cfg["adam"]["eps"]
    lr, wd = cfg["learning_rate"], cfg["weight_decay"]
    tm = jax.tree_util.tree_map
    m = tm(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = tm(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)
    new = tm(lambda p, m, v: p - lr * (
        (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps) + wd * p),
        params, m, v)
    return new, m, v


def leaf_norms(tree) -> Dict[str, float]:
    return {leaf_name(g, i, l): float(np.linalg.norm(
        np.asarray(tree[g][i][l], np.float64))) for g, i, l in LEAVES}


def follow(cfg: Dict, graph: Dict, window, init_seed: int, steps: int,
           dtype_name: str = "float32",
           matmul_precision: str = "") -> Dict:
    """Follow the first ``steps`` train steps of a cycle from a fresh
    init. Returns each step's loss, the first gradient's norm by leaf
    and the norm of the parameters' change after ``steps``, by leaf."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_name)
    n_arcs = len(graph["senders"])
    targets, hours, observed = aggregate_window(
        n_arcs, *window, cfg["pinned_hour"])
    feats = edge_features(graph["length_m"], graph["speed_limit"],
                          graph["road_class"], hours)
    args = tuple(jnp.asarray(a) for a in (
        np.asarray(graph["node_coords"], np.float32),
        np.asarray(graph["senders"], np.int32),
        np.asarray(graph["receivers"], np.int32), feats,
        np.asarray(graph["length_m"], np.float32),
        np.asarray(graph["speed_limit"], np.float32),
        targets, observed.astype(np.float32)))
    static = {k: cfg[k] for k in ("coord_centre", "coord_scale", "n_rounds")}
    static["matmul_precision"] = matmul_precision or cfg["matmul_precision"]

    @jax.jit
    def value_and_grad(params, *args):
        return jax.value_and_grad(
            lambda p: loss_fn(p, static, *args, dtype))(params)

    params = init_params(init_seed, cfg["hidden"], cfg["n_edge_features"])
    first = params
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    m, v = zeros, zeros
    losses: List[float] = []
    grad_norms = {}
    for t in range(1, steps + 1):
        loss, grads = value_and_grad(params, *args)
        losses.append(float(loss))
        if t == 1:
            grad_norms = leaf_norms(grads)
        params, m, v = adamw(params, grads, m, v, t, cfg)
    change = jax.tree_util.tree_map(lambda a, b: a - b, params, first)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": leaf_norms(change)}
