"""The refit cycle's own spans: one ``live.retrain`` root a cycle, five
sequential children, the same five durations in
``rtpu_live_retrain_phase_seconds{phase}``; and the two things the
benchmark's driver leans on (a tracer that is off, a pinned clock)."""

import time
import types

import numpy as np
import pytest

from routest_tpu.data.road_graph import generate_road_graph
from routest_tpu.live import trainer as trainer_mod
from routest_tpu.live.state import CongestionState
from routest_tpu.obs import Tracer, configure_tracer, get_registry

PHASES = ("aggregate", "upload", "steps", "apply", "save")


def _trainer(tmp_path, n_probes=600, **kw):
    """A trainer over a toy graph behind the three fields it reads of a
    router, its window filled with ``n_probes`` traversals."""
    g = generate_road_graph(n_nodes=160, seed=4)
    router = types.SimpleNamespace(
        graph_dict=lambda: g, _fingerprint=None,
        _gnn_path=str(tmp_path / "gnn.msgpack"))
    freeflow = g["length_m"] / np.maximum(g["speed_limit"], 0.1)
    state = CongestionState(freeflow)
    rng = np.random.default_rng(0)
    edges = rng.integers(0, len(freeflow), n_probes)
    state.fold(edges, freeflow[edges] * rng.uniform(1.0, 2.0, n_probes),
               t=1.0, hour=8)
    kw.setdefault("steps", 3)
    kw.setdefault("min_obs", 100)
    return trainer_mod.ContinuousTrainer(router, state, **kw)


def _phase_counts():
    family = get_registry().get("rtpu_live_retrain_phase_seconds")
    if family is None:
        return {}
    return {labels[0]: child.count for labels, child in family.items()}


def test_a_saved_cycle_is_one_root_with_the_five_children_in_order(
        tmp_path, tracer):
    tr = _trainer(tmp_path)
    before = _phase_counts()
    result = tr.run_once()
    assert result["trained"] is True
    assert set(result) == {"trained", "observations", "edges_labeled",
                           "loss", "window_rmse_s", "train_s", "path"}
    spans = tracer.buffer.snapshot()
    roots = [s for s in spans if s["name"] == "live.retrain"]
    assert len(roots) == 1
    root = roots[0]
    assert root["attrs"]["result"] == "saved"
    assert root["attrs"]["observations"] == 600
    assert root["attrs"]["edges"] == len(tr._graph["senders"])
    assert root["attrs"]["steps"] == 3
    children = sorted((s for s in spans
                       if s["parent_id"] == root["span_id"]),
                      key=lambda s: s["start_unix"])
    assert [s["name"] for s in children] == [
        "live.retrain." + p for p in PHASES]
    assert len(spans) == 6
    by_phase = {s["name"].rsplit(".", 1)[1]: s for s in children}
    assert by_phase["upload"]["attrs"]["bytes"] > 0
    assert by_phase["steps"]["attrs"]["steps"] == 3
    assert by_phase["apply"]["attrs"]["bytes"] == 4 * root["attrs"]["edges"]
    assert by_phase["save"]["attrs"]["bytes"] > 0
    # sequential and gapless: what the children leave of the root is
    # the logging and the window RMSE
    covered = sum(s["duration_ms"] for s in children)
    assert 0.95 * root["duration_ms"] <= covered <= root["duration_ms"]
    after = _phase_counts()
    assert {p: after[p] - before.get(p, 0) for p in PHASES} == dict.fromkeys(
        PHASES, 1)
    assert set(after) == set(PHASES)


def test_the_histogram_takes_the_spans_own_duration(tmp_path, tracer):
    def sums():
        family = get_registry().get("rtpu_live_retrain_phase_seconds")
        return {labels[0]: child.sum for labels, child in family.items()}

    tr = _trainer(tmp_path)
    tr.run_once()                       # creates the family
    tracer.buffer.clear()
    before = sums()
    tr.run_once()
    gained = {p: sums()[p] - before[p] for p in PHASES}
    for s in tracer.buffer.snapshot():
        if s["parent_id"] is not None:
            phase = s["name"].rsplit(".", 1)[1]
            assert gained[phase] == pytest.approx(s["duration_ms"] / 1e3,
                                                  rel=1e-6, abs=1e-6)


def test_a_skipped_cycle_has_a_root_and_no_steps_child(tmp_path, tracer):
    tr = _trainer(tmp_path, min_obs=10_000)
    result = tr.run_once()
    assert result["trained"] is False and "min_obs" in result["reason"]
    spans = tracer.buffer.snapshot()
    root = [s for s in spans if s["name"] == "live.retrain"][0]
    assert root["attrs"]["result"] == "skipped"
    assert [s["name"] for s in spans if s["parent_id"] == root["span_id"]] \
        == ["live.retrain.aggregate"]


def test_a_failing_cycle_does_not_raise_and_is_marked_failed(
        tmp_path, tracer):
    tr = _trainer(tmp_path)

    def boom(*_):
        raise RuntimeError("no device")

    tr._ensure_model()
    tr._ensure_step()
    tr._step_fn = boom
    result = tr.run_once()
    assert result == {"trained": False, "reason": "RuntimeError: no device"}
    spans = {s["name"]: s for s in tracer.buffer.snapshot()}
    assert spans["live.retrain"]["attrs"]["result"] == "failed"
    assert spans["live.retrain"]["status"] == "ok"      # it never raised
    assert spans["live.retrain.steps"]["status"] == "error"
    assert "live.retrain.apply" not in spans


def test_with_the_tracer_off_a_cycle_still_trains_and_observes(
        tmp_path, tracer):
    off = configure_tracer(Tracer(enabled=False))   # the fixture restores
    tr = _trainer(tmp_path)
    before = _phase_counts()
    assert tr.run_once()["trained"] is True
    assert len(off.buffer) == 0 and len(tracer.buffer) == 0
    after = _phase_counts()
    assert all(after[p] - before.get(p, 0) == 1 for p in PHASES)


def test_a_module_clock_with_only_perf_counter_and_localtime_still_trains(
        tmp_path, tracer, monkeypatch):
    """The benchmark's driver replaces the module's ``time`` with such
    an object (``benchmark/drivers/refit_cycles.py``): any other clock
    function reached for in ``live/trainer.py`` turns every cycle of
    its cell into ``failed``."""
    pinned = types.SimpleNamespace(
        perf_counter=time.perf_counter,
        localtime=lambda *_: types.SimpleNamespace(tm_hour=8))
    monkeypatch.setattr(trainer_mod, "time", pinned)
    tr = _trainer(tmp_path)
    result = tr.run_once()
    assert result["trained"] is True, result
    roots = [s for s in tracer.buffer.snapshot()
             if s["name"] == "live.retrain"]
    assert roots[0]["attrs"]["result"] == "saved"


def _roots(tracer):
    return [s for s in tracer.buffer.snapshot() if s["name"] == "live.retrain"]


def test_a_cycle_under_the_layout_reads_what_the_indexed_cycle_reads(
        tmp_path, tracer, monkeypatch):
    """The trainer lays the graph out once (``models/gnn.graph_layout``)
    and trains in that order; the numbers a cycle reports, and the
    artifact's graph fingerprint, are those of the graph as given."""
    from routest_tpu.models import gnn
    from routest_tpu.train.checkpoint import graph_fingerprint, load_gnn

    (tmp_path / "dense").mkdir()
    (tmp_path / "indexed").mkdir()
    dense = _trainer(tmp_path / "dense")
    first = dense.run_once()
    second = dense.run_once()
    monkeypatch.setattr(gnn, "graph_layout", lambda *_: None)
    indexed = _trainer(tmp_path / "indexed")
    want_first = indexed.run_once()
    want_second = indexed.run_once()
    for got, want in ((first, want_first), (second, want_second)):
        assert got["trained"] is True and want["trained"] is True
        assert got["edges_labeled"] == want["edges_labeled"]
        assert got["loss"] == pytest.approx(want["loss"], rel=1e-5)
        assert got["window_rmse_s"] == pytest.approx(want["window_rmse_s"],
                                                     rel=1e-5)
    attrs = [r["attrs"] for r in _roots(tracer)]
    assert [a["layout"] for a in attrs] == ["dense", "dense",
                                            "segment_sum", "segment_sum"]
    assert attrs[0]["layout_build_ms"] > 0      # the first cycle only
    assert all("layout_build_ms" not in a for a in attrs[1:])
    g = dense._graph
    want_fp = graph_fingerprint(g["node_coords"], g["senders"],
                                g["receivers"], g["length_m"])
    for tr in (dense, indexed):
        assert load_gnn(tr._path)[2] == want_fp


def test_the_step_the_trainer_jits_holds_no_scatter_under_the_layout(
        tmp_path, tracer):
    """What the cell is judged by: the program named ``jit_step``."""
    tr = _trainer(tmp_path)
    seen = []
    tr._ensure_model()
    tr._ensure_step()
    step = tr._step_fn

    def recording(*args):
        seen.append(args)
        return step(*args)

    tr._step_fn = recording
    assert tr.run_once()["trained"] is True
    text = step.lower(*seen[0]).as_text()
    assert "module @jit_step" in text
    assert "stablehlo.scatter" not in text
