"""Operations and bytes the algorithms need, from shapes alone.

Matrix-multiply FLOPs only (2 per multiply-add), as an MFU counts
them; elementwise work is left out, so a share of the peak computed
from these is a lower reading of the useful work, never over 100%.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple


def dense_flops(rows: int, d_in: int, d_out: int) -> int:
    return 2 * rows * d_in * d_out


# ── the ETA scorer ───────────────────────────────────────────────────


def eta_layer_dims(cfg: Dict) -> Sequence[Tuple[int, int]]:
    """(d_in, d_out) of every dense layer of the scorer: the trunk over
    the internal feature width, then 2 heads (pace, overhead) per
    quantile."""
    dims = ([cfg["internal_features"]] + list(cfg["hidden"])
            + [2 * max(1, len(cfg["quantiles"]))])
    return list(zip(dims[:-1], dims[1:]))


def eta_flops_per_row(cfg: Dict) -> int:
    """Forward FLOPs of one scored row (219,648 for the shipped widths
    with three quantiles: 42·256 + 256·256 + 256·128 + 128·6, doubled)."""
    return sum(dense_flops(1, a, b) for a, b in eta_layer_dims(cfg))


def eta_weight_bytes(cfg: Dict) -> int:
    """One stream of the f32 parameters (weights and biases)."""
    return sum(4 * (a * b + b) for a, b in eta_layer_dims(cfg))


def eta_bytes_per_row(cfg: Dict) -> int:
    """Table in + table out, f32: what any implementation has to move
    for one row (60 B at 12 features and 3 quantiles)."""
    return 4 * (cfg["n_features"] + max(1, len(cfg["quantiles"])))


# ── the road GNN's train step ────────────────────────────────────────


def gnn_dense_layers(cfg: Dict):
    """Every dense layer of one forward pass as
    (rows_of, d_in, d_out, d_in_needing_grad, times): rows_of is "node"
    or "arc"; d_in_needing_grad counts the input columns a backward pass
    has to produce a gradient for (the coordinates and the edge features
    are data, so theirs is not needed)."""
    h, f, r = cfg["hidden"], cfg["n_edge_features"], cfg["n_rounds"]
    return [
        ("node", 2, h, 0, 1),                    # embed
        ("arc", 2 * h + f, h, 2 * h, r),         # message MLP, layer 1
        ("arc", h, h, h, r),                     # message MLP, layer 2
        ("node", 2 * h, h, 2 * h, r),            # node update
        ("arc", 2 * h + f, h, 2 * h, 1),         # readout, layer 1
        ("arc", h, 2, h, 1),                     # readout, layer 2
    ]


def gnn_forward_flops(cfg: Dict) -> int:
    rows = {"node": cfg["n_nodes"], "arc": cfg["n_arcs"]}
    return sum(t * dense_flops(rows[of], a, b)
               for of, a, b, _, t in gnn_dense_layers(cfg))


def gnn_train_step_flops(cfg: Dict) -> int:
    """Forward + backward of one train step over every arc: per layer
    the forward product, the weight gradient (same size) and the input
    gradient over the columns that need one. Recomputation and the
    optimizer's elementwise update are not counted."""
    rows = {"node": cfg["n_nodes"], "arc": cfg["n_arcs"]}
    total = 0
    for of, a, b, a_grad, t in gnn_dense_layers(cfg):
        total += t * (2 * dense_flops(rows[of], a, b)
                      + dense_flops(rows[of], a_grad, b))
    return total
