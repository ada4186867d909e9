"""``host_cpu_stall_ms.seq`` and ``.gnn``: the readers of the host's
account on the program's root spans, on hand-made span buffers (the
attributes present, absent, partial), after a toy refit run, and in the
manifest."""

import time

import jax
import pytest

from _toy import R, both_manifests, cell_files, entry_of, manifest, reported

from routest_tpu.obs import Tracer, configure_tracer

SEQ, GNN = "host_cpu_stall_ms.seq", "host_cpu_stall_ms.gnn"
SEQ_CELLS = ("route-lm-score", "route-lm-sala-long",
             "route-lm-kexaone-mixed")


def _root(tracer, name, n, **attrs):
    tracer.buffer.add({
        "name": name, "trace_id": f"t{n}", "span_id": f"root{n}",
        "parent_id": None, "start_unix": 0.0, "duration_ms": 1000.0,
        "status": "ok", "thread": 1, "attrs": attrs})


def _read(name, **counts):
    return R.load_module("metrics", name).read({"counts": counts})


def test_the_sum_is_over_the_windows_passes_alone(tracer):
    _root(tracer, "seq.score_pass", 0, psi_cpu_ms=9000.0, steal_ms=50.0)
    _root(tracer, "seq.score_pass", 1, psi_cpu_ms=12.5, steal_ms=0.0)
    _root(tracer, "live.retrain", 2, psi_cpu_ms=77.0, result="saved")
    _root(tracer, "seq.score_pass", 3, psi_cpu_ms=1800.0, steal_ms=40.0)
    assert _read(SEQ, passes=2) == 1852.5
    assert _read(SEQ, passes=3) == 10902.5
    assert _read(GNN, cycles=1) == 77.0


def test_the_cycles_are_the_saved_ones(tracer):
    _root(tracer, "live.retrain", 0, psi_cpu_ms=500.0, result="saved")
    _root(tracer, "live.retrain", 1, psi_cpu_ms=3.0, result="saved")
    _root(tracer, "live.retrain", 2, psi_cpu_ms=99.0, result="skipped")
    _root(tracer, "live.retrain", 3, steal_ms=4.0, result="saved")
    assert _read(GNN, cycles=2) == 7.0


@pytest.mark.parametrize("case", ["one-source", "the-other"])
def test_a_machine_with_one_of_the_two_sources_still_gives_a_number(
        case, tracer):
    attrs = {"psi_cpu_ms": 30.0} if case == "one-source" else {
        "steal_ms": 30.0}
    _root(tracer, "seq.score_pass", 1, cpu_ms=5.0, **attrs)
    _root(tracer, "seq.score_pass", 2, cpu_ms=5.0, **attrs)
    assert _read(SEQ, passes=2) == 60.0


@pytest.mark.parametrize("name,root,count", [
    (SEQ, "seq.score_pass", "passes"), (GNN, "live.retrain", "cycles")])
@pytest.mark.parametrize("case", ["tracer-off", "no-spans", "parent-commit",
                                  "neither-source", "missing-root",
                                  "none-asked"])
def test_with_nothing_sound_to_read_it_gives_none(name, root, count, case,
                                                  tracer):
    n = 2
    if case == "tracer-off":
        configure_tracer(Tracer(enabled=False))     # the fixture restores
    elif case == "parent-commit":                   # roots without the pair
        _root(tracer, root, 1, result="saved")
        _root(tracer, root, 2, result="saved")
    elif case == "neither-source":                  # no /proc to read
        _root(tracer, root, 1, result="saved", psi_cpu_ms=1.0)
        _root(tracer, root, 2, result="saved", cpu_ms=4000.0, gc_ms=0.0)
    elif case == "missing-root":
        _root(tracer, root, 1, result="saved", psi_cpu_ms=1.0)
    elif case == "none-asked":
        _root(tracer, root, 1, result="saved", psi_cpu_ms=1.0)
        n = 0
    assert _read(name, **{count: n}) is None


def test_a_toy_refit_runs_roots_carry_what_this_machine_can_read(tracer):
    import os

    cell, config, mix = cell_files("gnn-refit")
    result = R.execute(manifest(), cell, config, mix, 2 ** 31 + 13, 0.3,
                       False, jax.devices()[:1], time.perf_counter())
    assert result["correct"] is True
    cycles = result["operation_s"]["n"]
    roots = [s["attrs"] for s in tracer.buffer.snapshot()
             if s["name"] == "live.retrain"]
    assert len(roots) == cycles + 1
    assert all(r["cpu_ms"] > 0.0 for r in roots)
    assert "compile_ms" in roots[0]                 # the set-up cycle
    assert not any("compile_ms" in r for r in roots[1:])
    got = _read(GNN, cycles=cycles)
    if os.path.exists("/proc/pressure/cpu") or os.path.exists("/proc/stat"):
        assert got == pytest.approx(sum(
            r.get("psi_cpu_ms", 0.0) + r.get("steal_ms", 0.0)
            for r in roots[1:]))
    else:
        assert got is None


@both_manifests
def test_the_manifest_lists_each_for_its_own_cells(m):
    fields, cells = entry_of(m, SEQ)
    assert fields == {"name": SEQ, "unit": "ms", "better": "lower",
                      "source": "program_span", "layer": "host",
                      "moves": "od_rows_per_s"}
    for cell in SEQ_CELLS:
        assert cell in cells and SEQ in reported(m, cell)
    fields, cells = entry_of(m, GNN)
    assert fields == {"name": GNN, "unit": "ms", "better": "lower",
                      "source": "program_span", "layer": "host",
                      "moves": "gnn_edges_per_s"}
    assert "gnn-refit" in cells and GNN in reported(m, "gnn-refit")
    for cell in ("od-score",) + SEQ_CELLS:
        assert GNN not in reported(m, cell)
    for cell in ("od-score", "gnn-refit"):
        assert SEQ not in reported(m, cell)
