"""``setup_compile_s`` and ``setup_trace_lower_s``: the readers of the
program's own ``rtpu_compile_seconds_total{stage}`` on hand-made
registries (the family present, absent, partial), after a toy run, and
in the manifest."""

import time

import jax
import pytest

from _toy import R, both_manifests, cell_files, entry_of, manifest, reported

from routest_tpu.core import cache
from routest_tpu.obs import MetricsRegistry
from routest_tpu.obs import registry as reg_mod

NAMES = ("setup_compile_s", "setup_trace_lower_s")
FAMILY = "rtpu_compile_seconds_total"
ALL_CELLS = ("od-score", "gnn-refit", "route-lm-score",
             "route-lm-sala-long", "route-lm-kexaone-mixed")


@pytest.fixture
def registry():
    """An empty default registry for the length of a test; the compile
    listener's binding is put back after."""
    old, children = reg_mod._default_registry, cache._children
    reg_mod._default_registry, cache._children = MetricsRegistry(), None
    yield reg_mod._default_registry
    reg_mod._default_registry, cache._children = old, children


def _read(name):
    return R.load_module("metrics", name).read({"counts": {}})


def _count(registry, **stages):
    family = registry.counter(FAMILY, "", ("stage",))
    for stage, seconds in stages.items():
        family.labels(stage=stage).inc(seconds)


@pytest.mark.parametrize("name", NAMES)
def test_without_the_counter_there_is_no_number(registry, name):
    assert _read(name) is None           # the parent's program: no family
    registry.counter(FAMILY, "", ("stage",))
    assert _read(name) is None           # a family that counted nothing
    cache.count_compiles()               # bound, every stage at zero
    assert _read(name) is None


def test_each_reads_its_own_stages(registry):
    _count(registry, trace=6.5, lower=3.25, backend=170.0, cache_load=0.0)
    assert _read("setup_compile_s") == 170.0
    assert _read("setup_trace_lower_s") == 9.75


def test_a_stage_that_counted_nothing_leaves_its_reader_silent(registry):
    _count(registry, trace=6.5, cache_load=2.0)
    assert _read("setup_trace_lower_s") is None     # no lowering counted
    assert _read("setup_compile_s") is None         # the fetch is not it
    _count(registry, backend=2.5)
    assert _read("setup_compile_s") == 2.5
    assert _read("setup_trace_lower_s") is None


def test_a_toy_run_counts_its_set_up_and_nothing_in_the_window(registry):
    cell, config, mix = cell_files("od-score")
    result = R.execute(manifest(), cell, config, mix, 2 ** 31 + 5, 0.2,
                       False, jax.devices()[:1], time.perf_counter())
    assert result["compiles"]["setup"] >= 1
    assert result["compiles"]["window"] == 0
    compile_s, trace_lower_s = (_read(n) for n in NAMES)
    assert compile_s > 0.0 and trace_lower_s > 0.0
    # the comparison's own programs were counted after the readers would
    # have run, so this is an upper bound of what a reader sees; set-up
    # still holds both, each second once
    stages = {k[0]: c.value for k, c in registry.get(FAMILY).items()}
    assert stages["cache_load"] <= stages["backend"]
    counts = {k[0]: c.value
              for k, c in registry.get("rtpu_compiles_total").items()}
    assert counts["backend"] >= result["compiles"]["setup"]
    assert counts["trace"] >= counts["lower"] >= 1


@both_manifests
@pytest.mark.parametrize("name", NAMES)
def test_the_manifest_lists_it_for_every_accepted_cell(m, name):
    fields, cells = entry_of(m, name)
    assert fields == {"name": name, "unit": "s", "better": "lower",
                      "source": "program_counter", "layer": "set-up",
                      "moves": "setup_s"}
    for cell in ALL_CELLS:
        assert cell in cells and name in reported(m, cell)
