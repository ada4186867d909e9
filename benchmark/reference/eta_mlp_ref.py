"""Plain reference of the quantile ETA scorer (``EtaMLP``).

Equations (models/eta_mlp.py's docstrings, SURVEY.md Appendix B): the 12
ABI features expand to 42 bases (weather and traffic one-hots as given,
weekday and hour to one-hots, distance normalised, log1p(distance), age
normalised); a gelu MLP 42→256→256→128→2Q; softplus of the 2Q outputs;
cumulative sums over the Q pace and the Q overhead columns; ETA_q =
pace_q · distance + overhead_q (minutes).

``precision`` "f32" is the reference. The controls: "int8" quantises
weights per output column and activations per row to 8-bit integers
before every product; "fp8" rounds both to float8 (e4m3); each
accumulates exactly.
"""

from __future__ import annotations

import json
from typing import Dict, Tuple

import numpy as np

MAGIC = b"RTPU1\n"


def read_artifact(path: str) -> Tuple[Dict, Dict]:
    """(header, params) of a serving artifact: magic line, one JSON
    header line, msgpack of the parameter tree (flax's encoding of
    arrays, read with flax, which is a library and not the program)."""
    from flax import serialization

    with open(path, "rb") as f:
        if f.read(len(MAGIC)) != MAGIC:
            raise ValueError(f"{path}: not a model artifact")
        header = json.loads(f.readline().decode())
        params = serialization.msgpack_restore(f.read())
    layers = params["layers"]
    if isinstance(layers, dict):     # msgpack keeps a list as {"0": …}
        layers = [layers[str(i)] for i in range(len(layers))]
    return header, {
        "layers": [{"w": np.asarray(l["w"], np.float32),
                    "b": np.asarray(l["b"], np.float32)} for l in layers],
        "mean": np.asarray(params["norm"]["mean"], np.float32),
        "std": np.asarray(params["norm"]["std"], np.float32)}


def gelu(x):
    import jax.numpy as jnp

    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def softplus(x):
    import jax.numpy as jnp

    return jnp.logaddexp(x, 0.0)


def _dense(h, w, b, precision: str):
    import jax
    import jax.numpy as jnp

    if precision == "f32":
        return jnp.dot(h, w, precision=jax.lax.Precision.HIGHEST) + b
    if precision == "int8":
        sw = jnp.maximum(jnp.abs(w).max(axis=0, keepdims=True), 1e-30) / 127.0
        sh = jnp.maximum(jnp.abs(h).max(axis=1, keepdims=True), 1e-30) / 127.0
        qw = jnp.round(w / sw).astype(jnp.int8)
        qh = jnp.round(h / sh).astype(jnp.int8)
        acc = jnp.dot(qh, qw, preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32) * sh * sw + b
    if precision == "fp8":
        f8 = jnp.float8_e4m3fn
        return jnp.dot(h.astype(f8).astype(jnp.float32),
                       w.astype(f8).astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST) + b
    raise ValueError(f"unknown precision {precision!r}")


def forward(params: Dict, x, n_quantiles: int, precision: str = "f32"):
    """(rows, 12) f32 features → (rows, Q) f32 ETA minutes."""
    import jax.numpy as jnp

    weekday = x[:, 8].astype(jnp.int32)
    hour = x[:, 9].astype(jnp.int32)
    dist = jnp.maximum(x[:, 10], 0.0)
    age = x[:, 11]
    feats = jnp.concatenate([
        x[:, 0:8],
        (weekday[:, None] == jnp.arange(7)[None, :]).astype(jnp.float32),
        (hour[:, None] == jnp.arange(24)[None, :]).astype(jnp.float32),
        ((dist - params["mean"][10]) / params["std"][10])[:, None],
        jnp.log1p(dist)[:, None],
        ((age - params["mean"][11]) / params["std"][11])[:, None]], axis=1)
    h = feats
    for layer in params["layers"][:-1]:
        h = gelu(_dense(h, layer["w"], layer["b"], precision))
    last = params["layers"][-1]
    sp = softplus(_dense(h, last["w"], last["b"], precision))
    q = n_quantiles
    pace = jnp.cumsum(sp[:, :q], axis=1)
    overhead = jnp.cumsum(sp[:, q:2 * q], axis=1)
    return pace * dist[:, None] + overhead


def gap(got, want):
    """Per-answer gap in minutes against the reference's minutes + 1:
    relative for long trips, absolute for the shortest."""
    import jax.numpy as jnp

    return jnp.abs(got - want) / (jnp.abs(want) + 1.0)


def table_gaps(params: Dict, feats, answers, n_quantiles: int,
               block_rows: int, answers_precision: str = "") -> Dict[str, float]:
    """Widest and mean gap of every answer in ``answers`` (rows, Q)
    against the reference over ``feats`` (rows, 12), block by block on
    the device. With ``answers_precision`` set, the answers compared
    are the reference's own at that lower precision (the control) and
    ``answers`` is not read."""
    import jax
    import jax.numpy as jnp

    rows = feats.shape[0]
    block_rows = min(block_rows, rows)
    dev = jax.tree_util.tree_map(jnp.asarray, params)

    @jax.jit
    def block(feats, answers, start):
        x = jax.lax.dynamic_slice_in_dim(feats, start, block_rows, 0)
        want = forward(dev, x, n_quantiles, "f32")
        if answers_precision:
            got = forward(dev, x, n_quantiles, answers_precision)
        else:
            got = jax.lax.dynamic_slice_in_dim(answers, start, block_rows, 0)
        g = gap(got, want)
        g = jnp.where(jnp.isfinite(g), g, jnp.inf)
        return g.max(), g.sum(dtype=jnp.float32)

    widest, total, counted = 0.0, 0.0, 0
    starts = list(range(0, rows - block_rows + 1, block_rows))
    if starts[-1] + block_rows < rows:       # a last, overlapping block
        starts.append(rows - block_rows)
    results = [block(feats, answers, jnp.int32(s)) for s in starts]
    for mx, sm in results:
        widest = max(widest, float(mx))
        total += float(sm)
        counted += block_rows * n_quantiles
    return {"max_gap": widest, "mean_gap": total / counted}
