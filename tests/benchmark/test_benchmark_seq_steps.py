"""The four per-layer metrics that read the scorer's own account of its
steps' device time (``benchmark/seq_steps.py``): each reader on a
hand-made span buffer (the spans present, absent, partial), on the
buffer a toy ``route-lm-score`` window leaves, and in the manifest."""

import os
import tempfile

import pytest

from _toy import R, both_manifests, entry_of, reported

from benchmark import seq_steps, trace
from routest_tpu.obs import Tracer, configure_tracer

READERS = ["seq_step_device_gap_pct", "seq_pass_unaccounted_pct",
           "seq_longest_class_us_per_token",
           "seq_shortest_class_us_per_token"]
SEQ_CELLS = ("route-lm-score", "route-lm-sala-long",
             "route-lm-kexaone-mixed")
#          class, real, padded, device_ms
STEPS = [(96, 96, 0, 48.0), (72, 70, 2, 28.0), (40, 20, 20, 5.0),
         (40, 13, 27, 5.0)]
WARM = [(c, r, p, 10.0 * ms) for c, r, p, ms in STEPS]      # compiles


def _pass(tracer, n, steps, pass_ms, root_device_ms="sum", wait=True,
          device_ms=True):
    """The records one recorded ``RouteScorer.score`` leaves, children
    first, as they finish."""
    def add(name, span_id, parent_id, ms, attrs):
        tracer.buffer.add({
            "name": name, "trace_id": f"t{n}", "span_id": span_id,
            "parent_id": parent_id, "start_unix": 0.0, "duration_ms": ms,
            "status": "ok", "thread": 1, "attrs": attrs})

    for i, (c, real, padded, _) in enumerate(steps):
        add("seq.step", f"s{n}.{i}", f"root{n}", 1.0, {
            "length_class": c, "real_tokens": real, "padded_tokens": padded})
    if wait:
        for i, (c, real, padded, ms) in enumerate(steps):
            attrs = {"length_class": c, "routes": 1, "real_tokens": real,
                     "padded_tokens": padded}
            if device_ms:
                attrs["device_ms"] = ms
            add(seq_steps.STEP, f"w{n}.{i}", f"wait{n}", ms, attrs)
        add(seq_steps.WAIT, f"wait{n}", f"root{n}", pass_ms - 2.0, {})
    attrs = {"routes": 4, "steps": len(steps),
             "real_tokens": sum(s[1] for s in steps),
             "padded_tokens": sum(s[2] for s in steps)}
    if root_device_ms == "sum":
        attrs["device_ms"] = sum(s[3] for s in steps)
    add(seq_steps.ROOT, f"root{n}", None, pass_ms, attrs)


def _traced(runs):
    """A device trace whose step programs ran for ``runs`` seconds,
    back to back from t = 1 s, inside a window of 0-100 s."""
    modules, t = [("jit_other(1)", 0.0, 5e8)], 1e9
    for i, s in enumerate(runs):
        modules.append((f"{seq_steps.STEP_PROGRAM}({i})", t, s * 1e9))
        t += s * 1e9
    return {"trace": trace.Trace(
        [trace.DevicePlane("/device:TPU:0", [], modules)], []),
        "lo": 0.0, "hi": 1e11}


def _read(name, passes, **ctx):
    return R.load_module("metrics", name).read(
        {"counts": {"passes": passes}, **ctx})


def test_two_window_passes_after_a_warm_up_give_the_windows_numbers(tracer):
    _pass(tracer, 0, WARM, 900.0)
    _pass(tracer, 1, STEPS, 88.0)
    _pass(tracer, 2, STEPS, 90.0)
    got = seq_steps.by_class({"counts": {"passes": 2}})
    assert {c: (v["device_ms"], v["real_tokens"], v["padded_tokens"],
                v["steps"]) for c, v in got.items()} == {
        96: (96.0, 192, 0, 2), 72: (56.0, 140, 4, 2), 40: (20.0, 66, 94, 4)}
    assert _read(READERS[2], 2) == pytest.approx(1e3 * 96.0 / 192)
    assert _read(READERS[3], 2) == pytest.approx(1e3 * 20.0 / 66)
    assert _read(READERS[1], 2) == pytest.approx(
        100.0 * (1.0 - 172.0 / 178.0))
    # the device trace saw 0.1715 s of step programs for the spans' 0.172
    traced = _traced([0.048, 0.028, 0.0049, 0.0049] * 2)
    assert _read(READERS[0], 2, **traced) == pytest.approx(
        100.0 * abs(0.172 - 0.1716) / 0.1716)
    # one pass asked for: the last one alone
    assert _read(READERS[1], 1) == pytest.approx(100.0 * (1 - 86.0 / 90.0))


def test_the_gap_is_a_distance_whichever_side_is_larger(tracer):
    _pass(tracer, 1, STEPS, 88.0)
    over = _read(READERS[0], 1, **_traced([0.080]))
    under = _read(READERS[0], 1, **_traced([0.100]))
    assert over == pytest.approx(7.5) and under == pytest.approx(14.0)
    # runs of other programs, and runs outside the window, are not its
    ctx = _traced([0.080])
    ctx["hi"] = 1.05e9
    assert _read(READERS[0], 1, **ctx) is None
    assert _read(READERS[0], 1, **_traced([])) is None
    assert _read(READERS[0], 1) is None             # an untraced context


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("case", [
    "tracer-off", "no-spans", "parent-commit", "no-device-ms",
    "no-root-sum", "missing-pass", "steps-dropped", "no-passes"])
def test_a_reader_with_nothing_sound_to_read_gives_none(name, case, tracer):
    passes = 2
    if case == "tracer-off":
        configure_tracer(Tracer(enabled=False))     # the fixture restores
    elif case == "parent-commit":                   # root, steps, one wait
        _pass(tracer, 1, STEPS, 88.0, root_device_ms=None, wait=False)
        _pass(tracer, 2, STEPS, 90.0, root_device_ms=None, wait=False)
    elif case == "no-device-ms":
        _pass(tracer, 1, STEPS, 88.0)
        _pass(tracer, 2, STEPS, 90.0, device_ms=False)
    elif case == "no-root-sum":
        _pass(tracer, 1, STEPS, 88.0)
        _pass(tracer, 2, STEPS, 90.0, root_device_ms=None)
    elif case == "missing-pass":
        _pass(tracer, 1, STEPS, 88.0)
    elif case == "steps-dropped":                   # the buffer overflowed
        _pass(tracer, 1, STEPS, 88.0)
        _pass(tracer, 2, STEPS, 90.0)
        kept = [s for s in tracer.buffer.snapshot()
                if s["span_id"] != "w2.1"]
        configure_tracer(Tracer(enabled=True))
        from routest_tpu.obs import get_tracer
        for s in kept:
            get_tracer().buffer.add(s)
    elif case == "no-passes":
        _pass(tracer, 1, STEPS, 88.0)
        passes = 0
    assert _read(name, passes, **_traced([0.086] * 2)) is None


def test_a_toy_window_leaves_spans_the_readers_return_numbers_from(tracer):
    from _toy_seq import cell_files

    _, config, mix = cell_files()
    mod = R.load_module("drivers", mix["driver"])
    driver = mod.Driver(R.Run(7, config, mix, R.REPO, tempfile.mkdtemp(
        prefix="routest-benchmark-test-")))
    driver.window(0.05)
    ctx = {"counts": driver.counts()}
    passes = seq_steps.window_passes(ctx)
    assert len(passes) == ctx["counts"]["passes"] >= 1
    plan = driver.plan
    for p in passes:
        assert [(a["length_class"], a["real_tokens"], a["padded_tokens"])
                for a in p["steps"]] == [
            (s.length, s.real_tokens, s.padded_tokens) for s in plan]
        assert 0.0 < p["device_ms"] <= p["pass_ms"]
    classes = seq_steps.by_class(ctx)
    assert sorted(classes) == sorted({s.length for s in plan})
    longest, shortest = _read(READERS[2], len(passes)), _read(READERS[3],
                                                              len(passes))
    assert longest == classes[max(classes)]["us_per_token"] > 0.0
    assert shortest == classes[min(classes)]["us_per_token"] > 0.0
    assert 0.0 <= _read(READERS[1], len(passes)) < 100.0
    # the warm-up pass, which compiled, is not among the window's
    roots = [s for s in tracer.buffer.snapshot()
             if s["name"] == seq_steps.ROOT]
    assert len(roots) == len(passes) + 1
    # what the builder's tool keeps of such a run
    spec = R.importlib.util.spec_from_file_location(
        "run_kept", os.path.join(R.HERE, "tools", "run_kept.py"))
    tool = R.importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    kept = tool.kept({"operation_s": {"n": len(passes)}}, len(passes))
    assert [r["name"] for r in kept["roots"]] == [seq_steps.ROOT] * len(roots)
    assert all("device_ms" in r and "cpu_ms" in r for r in kept["roots"])
    assert set(kept["by_class"]) == {str(c) for c in classes}
    both = kept["us_per_token"]
    assert 0.0 < both["weighted_over_classes"] <= both["pass_over_its_tokens"]
    assert kept["costs"]["host_pair_us"] > 0.0
    assert kept["costs"]["recorded_span_us"] > 0.0


@both_manifests
@pytest.mark.parametrize("name", READERS)
def test_the_manifest_lists_it_for_the_sequence_cells_alone(m, name):
    """Its fields and its own cells; nothing about its place in the list
    nor about which later cells join it."""
    fields, cells = entry_of(m, name)
    assert fields == {"name": name,
                      "unit": "us" if name.endswith("token") else "%",
                      "better": "lower", "source": "program_span",
                      "layer": "sequence scorer", "moves": "od_rows_per_s"}
    for cell in SEQ_CELLS:
        assert cell in cells and name in reported(m, cell)
    for cell in ("od-score", "gnn-refit"):
        assert name not in reported(m, cell)
