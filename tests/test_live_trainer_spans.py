"""The refit cycle's own spans: one ``live.retrain`` root a cycle, five
sequential children, the same five durations in
``rtpu_live_retrain_phase_seconds{phase}``; and the two things the
benchmark's driver leans on (a tracer that is off, a pinned clock)."""

import os
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


def _pin_hour(monkeypatch, hour):
    """The module's ``time`` as the benchmark's driver replaces it
    (``benchmark/drivers/refit_cycles.py``): ``perf_counter`` and a
    ``localtime`` with the hour pinned, nothing else."""
    monkeypatch.setattr(trainer_mod, "time", types.SimpleNamespace(
        perf_counter=time.perf_counter,
        localtime=lambda *_: types.SimpleNamespace(tm_hour=hour)))


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
    _pin_hour(monkeypatch, 8)
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


# ── what a cycle hands the device ─────────────────────────────────────


def _record_steps(tr):
    """Wraps the trainer's step; returns the list that gets each call's
    ``(coords, batch, loss_w)`` as numpy (the next cycle donates the
    feature table, so a device array kept here would be deleted)."""
    import jax

    tr._ensure_model()
    tr._ensure_step()
    step, seen = tr._step_fn, []

    def recording(params, opt_state, *rest):
        seen.append(jax.tree_util.tree_map(np.asarray, rest))
        return step(params, opt_state, *rest)

    tr._step_fn = recording
    return seen


def _as_the_parent_built_it(tr, hour_now):
    """``(coords, batch, loss_w)`` of the trainer's current window the
    way every cycle built them before the static arrays stayed on the
    device: everything from the host's arrays, the whole feature table
    by ``edge_feature_array`` at the window's hours."""
    from routest_tpu.models.gnn import GraphBatch, edge_feature_array

    win, g, lay = tr._state.window(), tr._static, tr._layout
    edge = win["edge"] if lay is None else lay.arc_rank[win["edge"]]
    E = len(g["senders"])
    sums, counts = np.zeros(E, np.float64), np.zeros(E, np.float64)
    np.add.at(sums, edge, win["time_s"])
    np.add.at(counts, edge, 1.0)
    observed = counts > 0
    targets = np.zeros(E, np.float32)
    targets[observed] = (sums[observed] / counts[observed]).astype(
        np.float32)
    hours = np.full(E, hour_now, np.int32)
    hours[edge] = win["hour"]
    batch = GraphBatch(
        senders=np.asarray(g["senders"], np.int32),
        receivers=np.asarray(g["receivers"], np.int32),
        edge_feats=edge_feature_array(g["length_m"], g["speed_limit"],
                                      g["road_class"], hours),
        length_m=np.asarray(g["length_m"], np.float32),
        speed_limit=np.asarray(g["speed_limit"], np.float32),
        targets=targets, weights=np.ones((E,), np.float32),
        layout=lay and lay.slabs)
    return (np.asarray(g["node_coords"], np.float32), batch,
            observed.astype(np.float32))


def _assert_same_bits(got, want):
    import jax

    got_leaves, got_tree = jax.tree_util.tree_flatten(got)
    want_leaves, want_tree = jax.tree_util.tree_flatten(want)
    assert got_tree == want_tree
    for a, b in zip(got_leaves, want_leaves):
        b = np.asarray(b)
        assert (a.dtype, a.shape) == (b.dtype, b.shape)
        assert a.tobytes() == b.tobytes()


def _upload_attrs(tracer):
    return [s["attrs"] for s in tracer.buffer.snapshot()
            if s["name"] == "live.retrain.upload"]


@pytest.mark.parametrize("layout", ["dense", "segment_sum"])
def test_three_cycles_hand_the_step_the_parents_batch_bit_for_bit(
        layout, tmp_path, tracer, monkeypatch):
    """With the static arrays resident and the hour columns written on
    the device, the step still gets, leaf by leaf, what a cycle that
    rebuilt and re-sent everything gave it; and only the first cycle
    sends the static arrays."""
    if layout == "segment_sum":
        from routest_tpu.models import gnn

        monkeypatch.setattr(gnn, "graph_layout", lambda *_: None)
    tr = _trainer(tmp_path)
    seen = _record_steps(tr)
    freeflow = tr._state._val.copy()
    rng = np.random.default_rng(11)
    for cycle, (hour_now, probe_hour) in enumerate(((3, 8), (15, 17),
                                                    (22, 0))):
        if cycle:
            edges = rng.integers(0, len(freeflow), 400)
            tr._state.fold(edges, freeflow[edges] * rng.uniform(1.0, 3.0, 400),
                           t=1.0 + cycle, hour=probe_hour)
        _pin_hour(monkeypatch, hour_now)
        del seen[:]
        assert tr.run_once()["trained"] is True
        assert len(seen) == 3                   # steps=3: one call a step
        want = _as_the_parent_built_it(tr, hour_now)
        for got in seen:
            _assert_same_bits(got, want)
    assert [r["attrs"]["layout"] for r in _roots(tracer)] == [layout] * 3
    sent = _upload_attrs(tracer)
    assert [a["static_resident"] for a in sent] == [False, True, True]
    E = len(tr._static["senders"])
    assert sent[1]["bytes"] == sent[2]["bytes"] == 3 * 4 * E
    assert sent[1]["bytes"] < sent[0]["bytes"] / 5


@pytest.mark.parametrize("dies_in", ["step", "hour-program"])
def test_a_cycle_that_dies_after_the_table_was_donated_does_not_poison_the_next(
        dies_in, tmp_path, tracer, monkeypatch):
    _pin_hour(monkeypatch, 9)       # two trainers, one hour
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    tr = _trainer(tmp_path / "a")
    seen = _record_steps(tr)
    assert tr.run_once()["trained"] is True     # the table is resident
    step, hours_fn = tr._step_fn, tr._hours_fn

    def dying(*args):
        # the hour program consumes the table it is donated
        (step if dies_in == "step" else hours_fn)(*args)
        raise RuntimeError("lost the device")

    if dies_in == "step":
        tr._step_fn = dying
    else:
        tr._hours_fn = dying
    assert tr.run_once() == {"trained": False,
                             "reason": "RuntimeError: lost the device"}
    assert _roots(tracer)[-1]["attrs"]["result"] == "failed"
    tr._step_fn, tr._hours_fn = step, hours_fn
    del seen[:]
    again = tr.run_once()
    assert again["trained"] is True, again
    assert _roots(tracer)[-1]["attrs"]["result"] == "saved"
    # rebuilt from the host's arrays: the static state went up again
    assert [a["static_resident"] for a in _upload_attrs(tracer)] == [
        False, True, False]
    fresh = _trainer(tmp_path / "b")
    fresh_seen = _record_steps(fresh)
    assert fresh.run_once()["trained"] is True
    _assert_same_bits(seen[0], fresh_seen[0])


def test_apply_is_one_program_that_reads_what_the_eager_forward_reads(
        tmp_path, tracer):
    import jax

    tr = _trainer(tmp_path)
    seen = _record_steps(tr)
    assert tr.run_once()["trained"] is True
    coords, batch, _ = jax.tree_util.tree_map(jax.numpy.asarray, seen[0])
    got = np.asarray(tr._apply_fn(tr._params, coords, batch))
    want = np.asarray(tr._model.apply(tr._params, coords, batch))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # a module of its own, so that a trace tells it from the step
    text = tr._apply_fn.lower(tr._params, coords, batch).as_text()
    assert "module @jit_apply" in text and "module @jit_step" not in text


def test_non_finite_predictions_are_still_rejected_and_nothing_is_saved(
        tmp_path, tracer):
    tr = _trainer(tmp_path)
    assert tr.run_once()["trained"] is True
    saved_at = os.path.getmtime(tr._path)
    params, apply_fn = tr._params, tr._apply_fn
    E = len(tr._static["senders"])

    def poisoned(*args):
        pred = np.array(apply_fn(*args))
        pred[E // 2] = np.nan
        return pred

    tr._apply_fn = poisoned
    assert tr.run_once() == {"trained": False,
                             "reason": "non-finite predictions after fit"}
    assert _roots(tracer)[-1]["attrs"]["result"] == "rejected"
    assert tr._params is params and os.path.getmtime(tr._path) == saved_at
    tr._apply_fn = apply_fn
    assert tr.run_once()["trained"] is True
    # a rejected cycle is no failure: the static arrays stayed
    assert [a["static_resident"] for a in _upload_attrs(tracer)] == [
        False, True, True]
