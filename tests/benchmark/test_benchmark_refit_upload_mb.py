"""``refit_upload_mb``: the reader of ``live.retrain.upload``'s
``bytes`` attribute, on a hand-made span buffer and on the buffer a toy
``gnn-refit`` run leaves."""

import time

import jax
import pytest

from _toy import (ACCEPTED_CELLS, R, both_manifests, cell_files, entry_of,
                  manifest, reported)

from benchmark import program_spans
from routest_tpu.obs import Tracer, configure_tracer

NAME = "refit_upload_mb"


def _cycle(tracer, n, sent, result="saved", upload=True):
    """The records of one ``run_once`` that the reader looks at: the
    upload child (``sent`` bytes; ``None``: no such attribute), then
    the root."""
    if upload:
        tracer.buffer.add({
            "name": f"{program_spans.ROOT}.upload", "trace_id": f"t{n}",
            "span_id": f"upload{n}", "parent_id": f"root{n}",
            "start_unix": 0.0, "duration_ms": 5.0, "status": "ok",
            "thread": 1, "attrs": {} if sent is None else {"bytes": sent}})
    tracer.buffer.add({
        "name": program_spans.ROOT, "trace_id": f"t{n}",
        "span_id": f"root{n}", "parent_id": None, "start_unix": 0.0,
        "duration_ms": 1000.0, "status": "ok", "thread": 1,
        "attrs": {"result": result}})


def _read(cycles):
    return R.load_module("metrics", NAME).read({"counts": {"cycles": cycles}})


def test_the_mean_is_over_the_windows_saved_cycles_alone(tracer):
    _cycle(tracer, 0, 236_000_000)              # set-up: the static arrays
    _cycle(tracer, 1, 32_000_000)
    _cycle(tracer, 2, 7, result="skipped")      # not a window cycle
    _cycle(tracer, 3, 34_000_000)
    assert _read(2) == pytest.approx(33.0)
    assert _read(3) == pytest.approx((236.0 + 32.0 + 34.0) / 3)


@pytest.mark.parametrize("case", ["tracer-off", "no-spans", "no-attribute",
                                  "missing-child", "missing-root",
                                  "no-cycles"])
def test_with_nothing_sound_to_read_it_gives_none(case, tracer):
    cycles = 2
    if case == "tracer-off":
        configure_tracer(Tracer(enabled=False))     # the fixture restores
    elif case == "no-attribute":
        _cycle(tracer, 1, 32_000_000)
        _cycle(tracer, 2, None)
    elif case == "missing-child":
        _cycle(tracer, 1, 32_000_000)
        _cycle(tracer, 2, 0, upload=False)
    elif case == "missing-root":
        _cycle(tracer, 1, 32_000_000)
    elif case == "no-cycles":
        _cycle(tracer, 1, 32_000_000)
        cycles = 0
    assert _read(cycles) is None


def test_a_toy_refit_runs_window_cycles_send_the_windows_three_vectors(
        tracer):
    cell, config, mix = cell_files("gnn-refit")
    result = R.execute(manifest(), cell, config, mix, 2 ** 31 + 11, 0.3,
                       False, jax.devices()[:1], time.perf_counter())
    assert result["correct"] is True and result["failed"] == 0
    cycles = result["operation_s"]["n"]
    got = _read(cycles)
    # targets, loss weights and hours: 4 bytes an arc each
    assert got == pytest.approx(3 * 4 * config["n_arcs"] / 1e6)
    uploads = [s["attrs"] for s in tracer.buffer.snapshot()
               if s["name"] == program_spans.ROOT + ".upload"]
    assert len(uploads) == cycles + 1
    assert [a["static_resident"] for a in uploads] == [False] + [True] * cycles
    # the set-up cycle, which sends the static arrays, is left out
    assert got < uploads[0]["bytes"] / 1e6 / 5


@both_manifests
def test_the_manifest_lists_it_for_gnn_refit_alone_of_the_accepted(m):
    assert entry_of(m, NAME)[0] == {
        "name": NAME, "unit": "MB", "better": "lower",
        "source": "program_span", "layer": "refit cycle (host)",
        "moves": "gnn_edges_per_s"}
    assert [c for c in ACCEPTED_CELLS if NAME in reported(m, c)] == [
        "gnn-refit"]
