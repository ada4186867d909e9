"""The one general traffic generator: it reads a mix's parameters from
``benchmark/traffic/<mix>.json`` and makes the inputs from ``--seed``.

Two kinds of input exist so far, each taken by one driver:

- an all-pairs origin–destination feature table built ON the device
  (``od_table``): stops drawn on the host, haversine distances and the
  per-row context drawn with ``jax.random`` in one jitted call, encoded
  in the program's 12-feature ABI (``FEATURE_NAMES`` order, one-hot
  layout; a test holds it against ``data/features.encode_features``);
- windows of probe traversals over a road graph (``probe_windows``):
  edges Zipf-skewed towards arterials, hours peaked at the two rushes,
  observed seconds from the congestion curve the repo's generators use
  (copied from ``data/road_graph.true_edge_time_s``) with lognormal
  noise. Every seed draws the same sizes, in another order.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from benchmark import seeds

N_WEATHER, N_TRAFFIC = 4, 4
N_FEATURES = 12      # weather(4) traffic(4) weekday hour distance_km age


# ── origin–destination table, on the device ──────────────────────────


def draw_stops(seed: int, n_stops: int, bbox) -> np.ndarray:
    """(n_stops, 2) f32 lat/lon, uniform in the bounding box."""
    rng = seeds.rng(seed, "stops")
    lat0, lat1, lon0, lon1 = bbox
    return np.stack([rng.uniform(lat0, lat1, n_stops),
                     rng.uniform(lon0, lon1, n_stops)],
                    axis=1).astype(np.float32)


def haversine_km(lat1, lon1, lat2, lon2):
    import jax.numpy as jnp

    r = 6371.0088
    lat1, lon1, lat2, lon2 = (jnp.radians(v) for v in
                              (lat1, lon1, lat2, lon2))
    a = (jnp.sin((lat2 - lat1) / 2) ** 2
         + jnp.cos(lat1) * jnp.cos(lat2) * jnp.sin((lon2 - lon1) / 2) ** 2)
    return 2 * r * jnp.arcsin(jnp.sqrt(jnp.clip(a, 0.0, 1.0)))


def od_raw_block(key, stops, first_origin, n_origins: int, ctx: Dict):
    """Raw columns of the rows (o, d) for ``n_origins`` origins from
    ``first_origin`` on and every destination, row-major in (o, d):
    (weather_idx, traffic_idx, weekday, hour, distance_km, driver_age).
    """
    import jax
    import jax.numpy as jnp

    n = stops.shape[0]
    rows = n_origins * n
    origin = jax.lax.dynamic_slice_in_dim(stops, first_origin, n_origins, 0)
    dist = haversine_km(origin[:, None, 0], origin[:, None, 1],
                        stops[None, :, 0], stops[None, :, 1]).reshape(rows)
    kw, kt, kd, kh, ka = jax.random.split(key, 5)

    def draw(k, p):
        """Index drawn with probabilities ``p``: one uniform a row
        against the cumulative shares (a categorical draw would make
        len(p) random numbers a row)."""
        cdf = np.cumsum(np.asarray(p, np.float64) / np.sum(p))[:-1]
        u = jax.random.uniform(k, (rows,), jnp.float32)
        return jnp.sum(u[:, None] >= jnp.asarray(cdf, jnp.float32)[None, :],
                       axis=1).astype(jnp.int32)

    weather = draw(kw, ctx["weather_p"])
    traffic = draw(kt, ctx["traffic_p"])
    weekday = jax.random.randint(kd, (rows,), 0, 7)
    hour = draw(kh, ctx["hour_p"])
    lo, hi = ctx["driver_age"]
    age = jnp.floor(jax.random.uniform(ka, (rows,), jnp.float32, lo, hi + 1))
    return weather, traffic, weekday, hour, dist, age


def od_encode(weather, traffic, weekday, hour, dist, age):
    """Raw columns → (rows, 12) f32 in the program's feature ABI."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    return jnp.concatenate([
        jax.nn.one_hot(weather, N_WEATHER, dtype=f32),
        jax.nn.one_hot(traffic, N_TRAFFIC, dtype=f32),
        weekday.astype(f32)[:, None], hour.astype(f32)[:, None],
        dist.astype(f32)[:, None], age.astype(f32)[:, None]], axis=-1)


def od_table(seed: int, cfg: Dict):
    """The resident (n_stops², 12) f32 feature table, built on the
    device in one jitted call: a loop over blocks of origins, each
    written in place. No host array of table size exists."""
    import jax
    import jax.numpy as jnp

    n, block = cfg["n_stops"], cfg["origins_per_block"]
    if n % block:
        raise ValueError("origins_per_block has to divide n_stops")
    stops = jnp.asarray(draw_stops(seed, n, cfg["bbox"]))
    key = jax.random.PRNGKey(seeds.sub_seed(seed, "od-context"))
    ctx = cfg["context"]

    @jax.jit
    def build(stops, key):
        def body(i, table):
            raw = od_raw_block(jax.random.fold_in(key, i), stops,
                               i * block, block, ctx)
            return jax.lax.dynamic_update_slice_in_dim(
                table, od_encode(*raw), i * block * n, 0)

        return jax.lax.fori_loop(
            0, n // block, body, jnp.zeros((n * n, N_FEATURES), jnp.float32))

    return build(stops, key)


# ── probe traversals over a road graph ───────────────────────────────

_CLASS_SPEED_MPS = np.asarray([11.1, 8.3, 5.6])
_CLASS_RUSH_SENSITIVITY = np.asarray([0.8, 0.5, 0.25])


def true_edge_time_s(length_m, road_class, hour):
    """Travel seconds of an edge at an hour, without noise (copy of
    ``data/road_graph.true_edge_time_s``)."""
    base = length_m / _CLASS_SPEED_MPS[road_class]
    h = hour.astype(np.float64)
    rush = (np.exp(-0.5 * ((h - 8.0) / 1.6) ** 2)
            + np.exp(-0.5 * ((h - 18.0) / 1.8) ** 2))
    congestion = 1.0 + _CLASS_RUSH_SENSITIVITY[road_class] * rush
    night = np.where((h >= 22) | (h <= 5), 0.85, 1.0)
    return base * congestion * night + 4.0


class ProbeSource:
    """Windows of probe traversals for one graph and one seed."""

    def __init__(self, seed: int, graph: Dict, mix: Dict) -> None:
        self._rng = seeds.rng(seed, "probes")
        self._graph = graph
        self._n = int(mix["probes_per_window"])
        self._sigma = float(mix["noise_sigma"])
        self._per_batch = int(mix["probes_per_batch"])
        n_arcs = len(graph["senders"])
        # Zipf over a ranking that puts arterials first, then
        # collectors, then locals, shuffled within a class by the seed.
        order = np.lexsort((self._rng.random(n_arcs), graph["road_class"]))
        p = 1.0 / np.arange(1, n_arcs + 1) ** float(mix["zipf_exponent"])
        self._cdf = np.cumsum(p / p.sum())
        self._order = order
        hour_p = np.asarray(mix["hour_p"], np.float64)
        self._hour_p = hour_p / hour_p.sum()

    def window(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(edge, hour, seconds) of one window's traversals, in arrival
        order. Probes arrive in batches of ``probes_per_batch``, each
        batch stamped with one hour, as a publisher sends them."""
        g = self._graph
        rank = np.searchsorted(self._cdf, self._rng.random(self._n))
        edge = self._order[np.minimum(rank, len(self._order) - 1)]
        n_batches = -(-self._n // self._per_batch)
        hour = np.repeat(self._rng.choice(24, size=n_batches, p=self._hour_p),
                         self._per_batch)[:self._n]
        t = true_edge_time_s(g["length_m"][edge].astype(np.float64),
                             g["road_class"][edge], hour)
        t = t * self._rng.lognormal(0.0, self._sigma, self._n)
        return (edge.astype(np.int64), hour.astype(np.int32),
                t.astype(np.float32))
