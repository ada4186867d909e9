"""The reader of ``seq_topk_fused_pct`` on hand-made registries and in
the manifest (the counter a toy pass leaves is read in
``tests/test_seq_score_protocol.py``)."""

import pytest

from _toy import R, both_manifests, entry_of, reported

from routest_tpu.obs import MetricsRegistry
from routest_tpu.obs import registry as reg_mod

NAME = "seq_topk_fused_pct"
FAMILY = "rtpu_seq_topk_blocks_total"


@pytest.fixture
def registry():
    """An empty default registry for the length of a test."""
    old = reg_mod._default_registry
    reg_mod._default_registry = MetricsRegistry()
    yield reg_mod._default_registry
    reg_mod._default_registry = old


def _read():
    return R.load_module("metrics", NAME).read({"counts": {"passes": 1}})


def test_without_the_counter_there_is_no_number(registry):
    assert _read() is None           # the parent's program: no family
    registry.counter(FAMILY, "", ("path",))
    assert _read() is None           # a family that counted nothing


@pytest.mark.parametrize("fused,xla,want", [
    (1224.0, 0.0, 100.0), (0.0, 1224.0, 0.0), (306.0, 918.0, 25.0)])
def test_the_share_is_the_fused_blocks_of_all(registry, fused, xla, want):
    family = registry.counter(FAMILY, "", ("path",))
    if fused:
        family.labels(path="fused").inc(fused)
    if xla:
        family.labels(path="xla").inc(xla)
    assert _read() == want


@both_manifests
def test_the_manifest_lists_it_for_route_lm_score_and_no_older_cell(m):
    """Its fields and its own cell; nothing about its place in the list
    nor about which later cells join it."""
    fields, cells = entry_of(m, NAME)
    assert fields == {"name": NAME, "unit": "%", "better": "higher",
                      "source": "program_counter", "layer": "attention",
                      "moves": "od_rows_per_s"}
    assert "route-lm-score" in cells
    for cell in ("od-score", "gnn-refit"):
        assert NAME not in reported(m, cell)
