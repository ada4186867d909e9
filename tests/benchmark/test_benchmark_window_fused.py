"""The reader of ``seq_window_fused_pct`` on hand-made registries, on
the registry a toy ``route-lm-score`` pass leaves, and in the manifest."""

import pytest

from _toy import R, both_manifests, entry_of, reported

from routest_tpu.obs import MetricsRegistry
from routest_tpu.obs import registry as reg_mod

NAME = "seq_window_fused_pct"
FAMILY = "rtpu_seq_window_blocks_total"


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
    # the full layers' counter is another metric's
    registry.counter("rtpu_seq_attention_chunks_total", "",
                     ("path",)).labels(path="fused").inc(1456.0)
    assert _read() is None


@pytest.mark.parametrize("fused,xla,want", [
    (654.0, 0.0, 100.0), (0.0, 654.0, 0.0), (218.0, 654.0, 25.0)])
def test_the_share_is_the_fused_blocks_of_all(registry, fused, xla, want):
    family = registry.counter(FAMILY, "", ("path",))
    if fused:
        family.labels(path="fused").inc(fused)
    if xla:
        family.labels(path="xla").inc(xla)
    assert _read() == want


def test_a_toy_pass_on_the_cpu_reads_zero(registry):
    """The toy widths do not tile and the backend is no TPU: every
    block of the pass takes the XLA body, and the reader says so."""
    import tempfile

    from _toy_seq import cell_files

    from routest_tpu.serve import seq_score

    seq_score._metrics = None        # the scorer's families, made anew
    try:
        _, config, mix = cell_files()
        mod = R.load_module("drivers", mix["driver"])
        mod.Driver(R.Run(5, config, mix, R.REPO,
                         tempfile.mkdtemp(prefix="routest-benchmark-test-")))
        assert _read() == 0.0
        family = registry.get(FAMILY)
        assert {k[0] for k, _ in family.items()} == {"xla"}
    finally:
        seq_score._metrics = None


@both_manifests
def test_the_manifest_lists_it_for_route_lm_score_and_no_older_cell(m):
    """Its fields and its own cell; nothing about its place in the list
    nor about which later cells join it."""
    fields, cells = entry_of(m, NAME)
    assert fields == {"name": NAME, "unit": "%", "better": "higher",
                      "source": "program_counter", "layer": "attention",
                      "moves": "od_rows_per_s"}
    assert "route-lm-score" in cells
    for cell in ("od-score", "gnn-refit", "route-lm-sala-long"):
        assert NAME not in reported(m, cell)
