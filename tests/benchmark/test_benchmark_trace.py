"""The reduction from a profiler trace to numbers, on synthetic spans and
on three small traces recorded on a v5e (PR 24, toy sizes: the scorer over
a 1,024-stop table in 65,536-row slices, once dispatched slice by slice
and once as one program a pass; one 40-step refit cycle over a
20,000-node graph)."""

import gzip
import json
import os

import pytest

from _toy import R

from benchmark import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _recorded(name):
    with gzip.open(os.path.join(DATA, f"trace_{name}_v5e.json.gz"), "rt") as f:
        return trace.Trace.from_json(json.load(f))


def _synthetic():
    ops = [("a", 0.0, 10.0, "matmul"), ("b", 5.0, 10.0, "other"),
           ("g", 30.0, 10.0, "gather_scatter"), ("a", 60.0, 5.0, "matmul")]
    modules = [("jit_step(1)", 0.0, 40.0), ("jit_other(2)", 55.0, 15.0)]
    host = [("window", 0.0, 100.0), ("cycle", 0.0, 50.0),
            ("refill", 50.0, 8.0)]
    return trace.Trace([trace.DevicePlane("/device:TPU:0", ops, modules)],
                       host)


def test_union_counts_overlap_once():
    assert trace.union_ns([(0, 10), (5, 15), (30, 40)]) == 25
    assert trace.union_ns([]) == 0
    assert trace.union_ns([(0, 10), (2, 3)]) == 10


def test_busy_ops_modules_and_kinds_on_synthetic_spans():
    tr = _synthetic()
    lo, hi = trace.window_of(tr, "window")
    assert (lo, hi) == (0.0, 100.0)
    assert trace.busy_seconds(tr, lo, hi) == pytest.approx(30e-9)
    assert trace.op_seconds(tr, lo, hi) == pytest.approx(
        {"a": 15e-9, "b": 10e-9, "g": 10e-9})
    assert trace.op_seconds(tr, lo, hi, "jit_step") == pytest.approx(
        {"a": 10e-9, "b": 10e-9, "g": 10e-9})
    assert trace.kind_seconds(tr, lo, hi, "jit_step")[
        "gather_scatter"] == pytest.approx(10e-9)
    assert trace.module_runs(tr, lo, hi, "jit_step") == [pytest.approx(40e-9)]
    # clipped to a narrower window
    assert trace.busy_seconds(tr, 0.0, 8.0) == pytest.approx(8e-9)


def test_a_loop_is_not_counted_beside_its_body():
    """A ``while`` lies on the operations' line and spans its body's
    operations: it keeps only its own time."""
    ops = [("while.4", 0.0, 100.0, "other"), ("a", 5.0, 30.0, "matmul"),
           ("b", 40.0, 50.0, "other"), ("c", 110.0, 10.0, "other")]
    tr = trace.Trace([trace.DevicePlane(
        "/device:TPU:0", ops, [("jit_pass(1)", 0.0, 120.0)])], [])
    assert trace.op_seconds(tr, 0.0, 200.0, "jit_pass") == pytest.approx(
        {"while.4": 20e-9, "a": 30e-9, "b": 50e-9, "c": 10e-9})
    assert trace.busy_seconds(tr, 0.0, 200.0) == pytest.approx(110e-9)
    assert trace.kind_seconds(tr, 0.0, 200.0)["matmul"] == pytest.approx(
        30e-9)


def test_gaps_are_named_by_the_innermost_annotation():
    tr = _synthetic()
    gaps = trace.idle_gaps(tr, 0.0, 100.0, ("refill", "cycle", "window"))
    # idle: 15-30 and 40-50 under cycle, 50-58 refill, 58-60 and 65-100
    assert gaps == pytest.approx(
        {"cycle": 25e-9, "refill": 8e-9, "window": 37e-9})
    assert sum(gaps.values()) == pytest.approx(
        100e-9 - trace.busy_seconds(tr, 0.0, 100.0))


def test_window_falls_back_to_the_device_events():
    tr = _synthetic()
    bare = trace.Trace(tr.devices, [])
    assert trace.window_of(bare, "window") == (0.0, 65.0)
    with pytest.raises(ValueError):
        trace.window_of(trace.Trace([], []), "window")


def test_instruction_text_to_name_and_kind():
    scatter = ("%fusion.13 = f32[1070376,64]{1,0:T(8,128)} fusion(f32[1070376,"
               "64]{1,0:T(8,128)} %copy.38, s32[2712798]{0:T(1024)} %gte.4, "
               "f32[2712798,64]{1,0:T(8,128)} %copy.39), kind=kCustom, "
               "calls=%fused_computation.40")
    matmul = ("%convolution_add_fusion.1 = bf16[131072,256]{1,0:T(8,128)(2,1)}"
              " fusion(bf16[131072,256]{1,0} %fusion.59), kind=kOutput, "
              "calls=%fused_computation.24")
    loop = ("%slice_add_fusion = f32[131072,3]{0,1:T(4,128)S(1)} fusion(f32["
            "131072,6]{0,1} %fusion.58), kind=kLoop, calls=%fc.60")
    tup = "%fusion.167 = (f32[141,64]{0,1}, f32[141,64]{0,1}) fusion(f32[141,64] %x), kind=kLoop"
    assert trace.short_name(scatter) == "fusion.13 f32[1070376,64]"
    assert trace.short_name(tup) == "fusion.167 f32[141,64]"
    assert trace.op_kind(scatter) == "gather_scatter"
    assert trace.op_kind(matmul) == "matmul"
    assert trace.op_kind(loop) == "other"
    assert trace.op_kind("%dynamic_update_slice.1 = f32[8,3] "
                         "dynamic-update-slice(f32[8,3] %a)") == "other"


def test_recorded_scorer_trace():
    tr = _recorded("od")
    lo, hi = trace.window_of(tr, "window")
    window_s, busy_s = (hi - lo) / 1e9, trace.busy_seconds(tr, lo, hi)
    assert window_s == pytest.approx(0.051763197)
    assert busy_s == pytest.approx(0.045683183)
    kinds = trace.kind_seconds(tr, lo, hi, "jit_score_slice")
    assert kinds["matmul"] / sum(kinds.values()) > 0.9
    assert "gather_scatter" not in kinds
    gaps = trace.idle_gaps(tr, lo, hi, ("pass", "window"))
    assert sum(gaps.values()) == pytest.approx(window_s - busy_s)
    assert gaps["pass"] > 100 * gaps["window"]
    assert len(trace.module_runs(tr, lo, hi, "jit_score_slice")) == 159


def test_recorded_one_program_pass_trace():
    """A pass as one program: each run's ``while`` spans the 16 slices'
    operations on the same line, and is not counted beside them."""
    tr = _recorded("od_pass")
    lo, hi = trace.window_of(tr, "window")
    busy_s = trace.busy_seconds(tr, lo, hi)
    assert busy_s == pytest.approx(0.044832845)
    ops = trace.op_seconds(tr, lo, hi, "jit_score_pass")
    assert sum(ops.values()) == pytest.approx(busy_s, rel=1e-6)
    loops = [d for n, _, d, _ in tr.devices[0].ops if n.startswith("while")]
    assert len(loops) == 10 and sum(loops) / 1e9 > 0.9 * busy_s
    assert ops["while.4 s32[]"] < 0.01 * busy_s
    assert len(trace.module_runs(tr, lo, hi, "jit_score_pass")) == 9


def test_recorded_refit_trace():
    tr = _recorded("gnn")
    lo, hi = trace.window_of(tr, "window")
    runs = trace.module_runs(tr, lo, hi, "jit_step")
    assert len(runs) == 40
    kinds = trace.kind_seconds(tr, lo, hi, "jit_step")
    share = kinds["gather_scatter"] / sum(kinds.values())
    assert share == pytest.approx(0.828, abs=0.005)
    # a step's operations fill its program's run
    assert sum(kinds.values()) == pytest.approx(sum(runs), rel=0.01)
    gaps = trace.idle_gaps(tr, lo, hi, ("refill-window", "cycle", "window"))
    assert gaps["cycle"] > gaps["refill-window"] > gaps["window"]


def test_readers_on_the_recorded_traces():
    """Each per-layer reader, fed the recorded traces and made-up
    counts; a reader that finds nothing returns nothing."""
    tr = _recorded("gnn")
    lo, hi = trace.window_of(tr, "window")
    ctx = {"trace": tr, "lo": lo, "hi": hi, "window_s": (hi - lo) / 1e9,
           "busy_s": trace.busy_seconds(tr, lo, hi), "chips": 1,
           "device_kind": "TPU v5 lite",
           "counts": {"module": "jit_step", "flops": 1e10,
                      "window_s": (hi - lo) / 1e9}}
    read = lambda name: R.load_module("metrics", name).read(ctx)
    assert read("gnn_step_device_ms") == pytest.approx(4.596, abs=0.01)
    assert read("gnn_gather_scatter_pct") == pytest.approx(82.8, abs=0.5)
    assert read("device_idle_pct.gnn") == pytest.approx(31.7, abs=0.5)
    assert 0 < read("gnn_mfu_pct") < 100
    ctx["counts"]["module"] = "jit_absent"
    assert read("gnn_step_device_ms") is None
    assert read("gnn_gather_scatter_pct") is None

    tr = _recorded("od")
    lo, hi = trace.window_of(tr, "window")
    rows = 10 * 1024 * 1024
    ctx = {"trace": tr, "lo": lo, "hi": hi, "window_s": (hi - lo) / 1e9,
           "busy_s": trace.busy_seconds(tr, lo, hi), "chips": 1,
           "device_kind": "TPU v5 lite",
           "counts": {"module": "jit_score_slice", "flops": rows * 219648,
                      "bytes": rows * 60, "window_s": (hi - lo) / 1e9}}
    roofline = read("score_roofline")
    mfu = read("score_mfu_pct")
    assert 20 < mfu < roofline < 35
    assert read("device_idle_pct.od") == pytest.approx(11.7, abs=0.5)
    ctx["counts"]["module"] = "jit_absent"
    assert read("score_roofline") is None


def test_load_reads_the_recorded_xplane_as_the_reduced_copy():
    tr = trace.load(os.path.join(DATA, "trace_od_v5e.xplane.pb"),
                    ("pass", "window"))
    kept = _recorded("od")
    assert [d.name for d in tr.devices] == ["/device:TPU:0"]
    assert len(tr.devices[0].ops) == len(kept.devices[0].ops) == 5760
    assert tr.devices[0].ops[100] == kept.devices[0].ops[100]
    assert tr.devices[0].modules[3] == kept.devices[0].modules[3]
    assert [s[0] for s in tr.host].count("pass") == 10
