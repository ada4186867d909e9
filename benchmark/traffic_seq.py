"""Route histories for the sequence scorer: a resident table of arc-id
sequences, made from ``--seed``.

The lengths are the mid-quantiles of a clipped log-normal (the mix's
``length_median``, ``length_sigma``, ``length_min``, ``length_max``), so
every seed draws the same multiset of lengths and a pass is the same
work on every seed; the seed draws the arcs, the order of the routes in
the table and the positions whose whole logit row is compared.

The arcs are random walks on a synthetic road graph: a square grid of
intersections whose directed street segments are the tokens (the
largest grid whose arcs fit the held slice of the vocabulary), a walk
taking at each intersection one of the segments that leave it, the
U-turn only at a dead end. Neighbouring tokens are neighbouring arcs,
as in a map-matched trajectory.
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Tuple

import numpy as np

from benchmark import seeds


def route_lengths(mix: Dict) -> List[int]:
    """``n_routes`` mid-quantiles of the clipped log-normal, ascending."""
    n = int(mix["n_routes"])
    normal = statistics.NormalDist()
    out = []
    for i in range(n):
        z = normal.inv_cdf((i + 0.5) / n)
        length = mix["length_median"] * np.exp(mix["length_sigma"] * z)
        out.append(int(np.clip(round(float(length)), mix["length_min"],
                               mix["length_max"])))
    return out


def grid_arcs(vocab: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(tail, head, successors) of the directed segments of the largest
    square grid with at most ``vocab`` of them; ``successors`` (A, 4)
    lists the arcs a walk may take after each arc, padded with -1, the
    U-turn listed only where nothing else leaves."""
    side = 2
    while 4 * (side + 1) * side <= vocab:
        side += 1
    node = np.arange(side * side).reshape(side, side)
    pairs = np.concatenate([
        np.stack([node[:, :-1].ravel(), node[:, 1:].ravel()], 1),
        np.stack([node[:-1, :].ravel(), node[1:, :].ravel()], 1)])
    tail = np.concatenate([pairs[:, 0], pairs[:, 1]])
    head = np.concatenate([pairs[:, 1], pairs[:, 0]])
    n_arcs = len(tail)
    leaving: Dict[int, List[int]] = {}
    for a in range(n_arcs):
        leaving.setdefault(int(tail[a]), []).append(a)
    succ = np.full((n_arcs, 4), -1, np.int64)
    for a in range(n_arcs):
        onward = [b for b in leaving[int(head[a])] if head[b] != tail[a]]
        onward = onward or leaving[int(head[a])]
        succ[a, :len(onward)] = onward
    return tail, head, succ


def route_table(seed: int, cfg: Dict, mix: Dict) -> Dict[str, np.ndarray]:
    """``ids`` (R, L_max) int32 (zero past a route's end), ``lengths``
    (R,) int32 and ``rows_at`` (R, P) int32: ``named_rows`` positions of
    each route, ascending, drawn below its last."""
    lengths = np.asarray(route_lengths(mix), np.int32)
    order = seeds.rng(seed, "route-order").permutation(len(lengths))
    lengths = lengths[order]
    _, _, succ = grid_arcs(int(cfg["vocab_size"]))
    n_succ = (succ >= 0).sum(1)
    rng = seeds.rng(seed, "route-arcs")
    width = int(lengths.max())
    draws = rng.random((width, len(lengths)))
    ids = np.zeros((len(lengths), width), np.int64)
    cur = rng.integers(0, len(succ), len(lengths))
    for t in range(width):
        ids[:, t] = cur
        cur = succ[cur, (draws[t] * n_succ[cur]).astype(np.int64)]
    ids = np.where(np.arange(width)[None, :] < lengths[:, None], ids, 0)
    rng = seeds.rng(seed, "named-rows")
    rows_at = np.stack([np.sort(rng.choice(int(n) - 1, int(mix["named_rows"]),
                                           replace=False))
                        for n in lengths])
    return {"ids": ids.astype(np.int32), "lengths": lengths,
            "rows_at": rows_at.astype(np.int32)}
