"""A planar road graph of an exact node and arc count, from a seed.

Stands in for a road graph that cannot be fetched (no network): the
9th DIMACS Challenge graphs are symmetric (every street is two arcs) and
mostly degree-2 chain vertices between intersections. So: intersections
on a jittered W×H grid, a random subset of the grid's streets kept (the
same subset for every seed: ``STREETS_SEED``), bend vertices strewn over
the streets, every segment in both directions.
With I intersections, S streets and B bends the graph has I + B nodes
and 2·(S + B) arcs, so S and B follow from the two counts asked for.

Road class per street and speed limit per class are drawn as the
repo's ``data/road_graph.generate_road_graph`` draws them (copied:
classes arterial / collector / local with p = 0.2 / 0.35 / 0.45 and
40 / 30 / 20 km/h). Array-level numpy throughout: about a second per
million nodes.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

CLASS_P = (0.2, 0.35, 0.45)
CLASS_SPEED_MPS = np.asarray([11.1, 8.3, 5.6], np.float32)
KEEP_FRACTION = 0.85         # of the grid's streets, before rounding
# WHICH streets are kept is the same for every seed: it fixes how many
# intersections have 0-4 streets, and the trainer's step program holds
# those counts in its shapes. Drawn from the seed they moved the step by
# 3% from seed to seed (the compiler's choices for a class of 150,416
# rows against one of 150,397: PERF.md section 6, PR 34), which is not
# the program's speed. The seed still draws where everything lies, the
# bends, the road classes and the order of the arcs. 7 gives 150,014
# intersections of three streets at the cell's size: the kind of graph
# the ledger's level was measured on.
STREETS_SEED = 7


def _haversine_m(lat1, lon1, lat2, lon2):
    r = 6_371_008.8
    lat1, lon1, lat2, lon2 = map(np.radians, (lat1, lon1, lat2, lon2))
    a = (np.sin((lat2 - lat1) / 2) ** 2
         + np.cos(lat1) * np.cos(lat2) * np.sin((lon2 - lon1) / 2) ** 2)
    return 2 * r * np.arcsin(np.sqrt(np.clip(a, 0, 1)))


def plan(n_nodes: int, n_arcs: int):
    """(W, H, streets, bends) for the two counts, or ValueError."""
    if n_arcs % 2:
        raise ValueError("a symmetric graph has an even arc count")
    segments = n_arcs // 2
    surplus = segments - n_nodes            # = streets - intersections
    if surplus <= 0:
        raise ValueError("needs more segments than nodes (mean degree > 2)")
    target = surplus / (2 * KEEP_FRACTION - 1)
    w = max(2, int(np.sqrt(target)))
    h = max(2, int(round(target / w)))
    inter = w * h
    streets, bends = inter + surplus, n_nodes - inter
    grid_streets = w * (h - 1) + h * (w - 1)
    if bends < 0 or streets > grid_streets:
        raise ValueError(f"no {w}x{h} grid gives {n_nodes} nodes and "
                         f"{n_arcs} arcs")
    return w, h, streets, bends


def road_graph(n_nodes: int, n_arcs: int, seed: int, bbox) -> Dict:
    """Graph dict in the program's schema: node_coords (N, 2) f32
    lat/lon, senders / receivers (A,) int32, length_m, road_class,
    speed_limit (A,)."""
    w, h, n_streets, n_bends = plan(n_nodes, n_arcs)
    rng = np.random.default_rng(seed)
    lat0, lat1, lon0, lon1 = bbox
    gy, gx = np.meshgrid(np.arange(h), np.arange(w), indexing="ij")
    jitter = rng.uniform(-0.3, 0.3, size=(h, w, 2))
    lat = lat0 + (gy + 0.5 + jitter[..., 0]) * (lat1 - lat0) / h
    lon = lon0 + (gx + 0.5 + jitter[..., 1]) * (lon1 - lon0) / w
    inter = np.stack([lat.ravel(), lon.ravel()], axis=1)
    n_inter = w * h
    ids = np.arange(n_inter).reshape(h, w)
    a = np.concatenate([ids[:, :-1].ravel(), ids[:-1, :].ravel()])
    b = np.concatenate([ids[:, 1:].ravel(), ids[1:, :].ravel()])
    keep = np.sort(np.random.default_rng(STREETS_SEED).permutation(
        len(a))[:n_streets])
    a, b = a[keep], b[keep]
    street_class = rng.choice(len(CLASS_P), size=n_streets,
                              p=CLASS_P).astype(np.int32)
    # bends: B balls into S streets, then laid out evenly along each
    street_of_bend = np.sort(rng.integers(0, n_streets, n_bends))
    per_street = np.bincount(street_of_bend, minlength=n_streets)
    first = np.cumsum(per_street) - per_street
    k = np.arange(n_bends) - first[street_of_bend]       # 0-based rank
    t = (k + 1) / (per_street[street_of_bend] + 1)
    pa, pb = inter[a[street_of_bend]], inter[b[street_of_bend]]
    d = pb - pa
    norm = np.sqrt((d ** 2).sum(axis=1, keepdims=True)) + 1e-12
    perp = np.stack([-d[:, 1], d[:, 0]], axis=1) / norm
    bend_xy = (pa + d * t[:, None]
               + perp * norm * 0.08 * rng.standard_normal((n_bends, 1)))
    coords = np.concatenate([inter, bend_xy]).astype(np.float32)
    bend_id = n_inter + np.arange(n_bends)
    # chain a → bend_0 → … → b: segment j of a street starts at the
    # street's (j-1)th bend (or a) and ends at its jth bend (or b)
    n_seg = n_streets + n_bends
    seg_street = np.repeat(np.arange(n_streets), per_street + 1)
    seg_first = np.cumsum(per_street + 1) - (per_street + 1)
    j = np.arange(n_seg) - seg_first[seg_street]
    bend_base = first[seg_street]
    src = np.where(j == 0, a[seg_street],
                   n_inter + bend_base + j - 1)
    dst = np.where(j == per_street[seg_street], b[seg_street],
                   n_inter + bend_base + j)
    assert bend_id.size == 0 or dst.max() < n_nodes
    senders = np.concatenate([src, dst]).astype(np.int32)
    receivers = np.concatenate([dst, src]).astype(np.int32)
    length = _haversine_m(coords[senders, 0], coords[senders, 1],
                          coords[receivers, 0], coords[receivers, 1])
    road_class = np.tile(street_class[seg_street], 2)
    return {
        "node_coords": coords,
        "senders": senders,
        "receivers": receivers,
        "length_m": np.maximum(length, 1.0).astype(np.float32),
        "road_class": road_class,
        "speed_limit": CLASS_SPEED_MPS[road_class],
    }
