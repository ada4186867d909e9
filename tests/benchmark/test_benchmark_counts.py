"""FLOP and byte functions against hand counts for the shipped shapes;
the table of peaks."""

import pytest

from _toy import R

from benchmark import counts, peaks

ETA = R.load_json(R.HERE, "configs", "eta-od-table-10k.json")
GNN = R.load_json(R.HERE, "configs", "gnn-metro-fla.json")


def test_scorer_flops_per_row_is_bench_pys_219648():
    assert counts.eta_flops_per_row(ETA) == 219_648
    assert counts.eta_flops_per_row(ETA) == 2 * (
        42 * 256 + 256 * 256 + 256 * 128 + 128 * 6)


def test_scorer_bytes():
    assert counts.eta_bytes_per_row(ETA) == 60
    assert counts.eta_weight_bytes(ETA) == 4 * (
        42 * 256 + 256 + 256 * 256 + 256 + 256 * 128 + 128 + 128 * 6 + 6)


def test_gnn_forward_flops_by_hand():
    n, a = GNN["n_nodes"], GNN["n_arcs"]
    per_arc = 2 * (2 * (141 * 64 + 64 * 64)) + 2 * (141 * 64 + 64 * 2)
    per_node = 2 * 2 * 64 + 2 * (2 * 128 * 64)
    assert per_arc == 70_784 and per_node == 33_024
    assert counts.gnn_forward_flops(GNN) == a * per_arc + n * per_node


def test_gnn_train_step_flops_by_hand():
    n, a = GNN["n_nodes"], GNN["n_arcs"]
    # forward + weight gradient: twice the forward; input gradient:
    # every layer but the embed, over the 128 hidden columns of the
    # two first layers that read [h_s, h_r, features]
    dx_arc = 2 * (2 * 128 * 64 + 2 * 64 * 64) + 2 * 128 * 64 + 2 * 64 * 2
    dx_node = 2 * (2 * 128 * 64)
    want = (2 * counts.gnn_forward_flops(GNN) + a * dx_arc + n * dx_node)
    assert counts.gnn_train_step_flops(GNN) == want
    assert 0.55e12 < want < 0.70e12


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e"])
def test_v5e_peaks(kind):
    p = peaks.chip_peaks(kind)
    assert p.bf16_flops_per_s == 197e12 and p.hbm_bytes_per_s == 819e9
    assert p.source


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError):
        peaks.chip_peaks("cpu")
