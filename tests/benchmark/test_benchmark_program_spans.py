"""The five per-layer metrics that read the program's own spans: each
reader on a hand-made span buffer, and on the buffer a toy ``gnn-refit``
run leaves."""

import time

import jax
import pytest

from _toy import R, both_manifests, cell_files, manifest

from benchmark import program_spans
from routest_tpu.obs import Tracer, configure_tracer

READERS = ["refit_host_pct", "refit_aggregate_ms", "refit_upload_ms",
           "refit_apply_ms", "refit_save_ms"]
#            aggregate upload steps apply save  root
SETUP = (500.0, 900.0, 9000.0, 80.0, 20.0, 10600.0)     # compiles
CYCLE_1 = (100.0, 30.0, 800.0, 40.0, 10.0, 1000.0)
CYCLE_2 = (140.0, 50.0, 1200.0, 80.0, 30.0, 1520.0)
WANT = {"refit_host_pct": 100.0 * (1.0 - 2000.0 / 2520.0),
        "refit_aggregate_ms": 120.0, "refit_upload_ms": 40.0,
        "refit_apply_ms": 60.0, "refit_save_ms": 20.0}


def _cycle(tracer, n, durations, result="saved", leave_out=()):
    """The records one ``run_once`` leaves: children first, as they
    finish, then the root."""
    root_id = f"root{n}"
    for phase, ms in zip(program_spans.PHASES, durations):
        if phase not in leave_out:
            tracer.buffer.add({
                "name": f"{program_spans.ROOT}.{phase}", "trace_id": f"t{n}",
                "span_id": f"{phase}{n}", "parent_id": root_id,
                "start_unix": 0.0, "duration_ms": ms, "status": "ok",
                "thread": 1, "attrs": {}})
    tracer.buffer.add({
        "name": program_spans.ROOT, "trace_id": f"t{n}", "span_id": root_id,
        "parent_id": None, "start_unix": 0.0, "duration_ms": durations[-1],
        "status": "ok", "thread": 1, "attrs": {"result": result}})


def _read(name, cycles):
    return R.load_module("metrics", name).read({"counts": {"cycles": cycles}})


@pytest.mark.parametrize("name", READERS)
def test_two_window_cycles_after_a_set_up_cycle_give_the_windows_means(
        name, tracer):
    _cycle(tracer, 0, SETUP)
    _cycle(tracer, 1, CYCLE_1)
    _cycle(tracer, 2, (1.0,) * 6, result="skipped")     # not a window cycle
    _cycle(tracer, 3, CYCLE_2)
    assert _read(name, 2) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("case", ["tracer-off", "no-spans", "missing-child",
                                  "missing-root", "no-cycles"])
def test_a_reader_with_nothing_sound_to_read_gives_none(name, case, tracer):
    cycles = 2
    if case == "tracer-off":
        configure_tracer(Tracer(enabled=False))     # the fixture restores
    elif case == "missing-child":
        _cycle(tracer, 1, CYCLE_1)
        _cycle(tracer, 2, CYCLE_2, leave_out=("apply",))
    elif case == "missing-root":
        _cycle(tracer, 1, CYCLE_1)
    elif case == "no-cycles":
        _cycle(tracer, 1, CYCLE_1)
        cycles = 0
    assert _read(name, cycles) is None


@both_manifests
def test_the_manifest_lists_the_five_for_gnn_refit_and_none_for_od_score(m):
    """The five, in this order, among ``gnn-refit``'s ``program_span``
    metrics; how many more there are is not this file's to say."""
    got = [x["name"] for x in R.metrics_of(m, "per_layer", "gnn-refit")
           if x["source"] == "program_span"]
    assert [name for name in got if name in READERS] == READERS
    assert not [x for x in R.metrics_of(m, "per_layer", "od-score")
                if x["source"] == "program_span"]


def test_a_toy_refit_run_leaves_spans_the_readers_return_numbers_from(
        tracer):
    cell, config, mix = cell_files("gnn-refit")
    result = R.execute(manifest(), cell, config, mix, 2 ** 31 + 7, 0.3,
                       False, jax.devices()[:1], time.perf_counter())
    assert result["correct"] is True and result["failed"] == 0
    cycles = result["operation_s"]["n"]
    ctx = {"counts": {"cycles": cycles}}
    got = {name: R.load_module("metrics", name).read(ctx)
           for name in READERS}
    assert all(v is not None and v > 0.0 for v in got.values()), got
    assert 0.0 < got["refit_host_pct"] < 100.0
    # the set-up cycle lies before the window's and is left out
    roots = [s for s in tracer.buffer.snapshot()
             if s["name"] == program_spans.ROOT]
    assert len(roots) == cycles + 1
    window = program_spans.window_cycles(ctx)
    assert [c["cycle"] for c in window] == [
        r["duration_ms"] for r in roots[1:]]
    # the four phases and the root's self time are the host's share
    whole = sum(c["cycle"] for c in window)
    phases = sum(got[n] for n in READERS[1:]) * cycles
    assert phases <= got["refit_host_pct"] / 100.0 * whole


def test_the_xplane_tool_reads_idle_by_span_and_scope_by_operation():
    """On the v5e trace the tests keep (recorded before the program had
    scopes: ``tf_op`` ends in the primitive). In a process of its own:
    the tool reads the protobuf through TensorFlow's ``xplane_pb2``."""
    import json
    import os
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(__file__))
    proc = subprocess.run(
        [sys.executable, os.path.join(R.HERE, "tools", "xplane_spans.py"),
         os.path.join(here, "data", "trace_od_v5e.xplane.pb"),
         "--module", "jit_score", "--spans", "window,pass,absent",
         "--top", "3"],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout[proc.stdout.index("{"):])
    spans = out["idle_in_spans"]
    assert set(spans) == {"window", "pass"} and spans["pass"]["n"] == 10
    for s in spans.values():
        assert 0.0 < s["idle_s"] < s["seconds"]
    assert spans["pass"]["idle_s"] <= spans["window"]["idle_s"]
    top = out["top_ops"]
    assert [o["op"] for o in top][:2] == ["fusion.24 bf16[65536,256]",
                                          "fusion.19 bf16[65536,128]"]
    assert all(o["tf_op"].startswith("jit(score_slice)/") for o in top)
    assert all("models/eta_mlp.py:" in o["source"] for o in top)
