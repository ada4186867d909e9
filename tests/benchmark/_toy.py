"""Toy sizes of the two cells for the CPU tests: the real files, with
only the sizes shrunk. And the two manifests that every test of the
manifest's shape runs on: the accepted one and a copy with a made-up
cell and a made-up last ``per_layer`` entry appended."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import run as R  # noqa: E402

TOY = {
    "od-score": ({"n_stops": 96, "origins_per_block": 32},
                 {"rows_per_slice": 1024, "reference_block_rows": 4096}),
    "gnn-refit": ({"n_nodes": 2000, "n_arcs": 5068,
                   "observation_window": 2048},
                  {"probes_per_window": 2048, "probes_per_batch": 64}),
}


def manifest():
    return R.load_json(R.REPO, "BENCHMARK.json")


# What a later PR may do to the manifest without editing a file that is
# there: append a cell (here an accepted sequence configuration under the
# OTHER sequence cell's traffic, so the pair is new; it is never run),
# append its name to the lists of the metrics it reports, and append a
# ``per_layer`` entry. Every test of the manifest's shape runs on the
# accepted manifest and on this copy: one that pins a place in a list,
# a list's other members or a count fails on the copy.
MADE_UP_CELL = {
    "name": "route-lm-made-up", "config": "minicpm-sala-l9-16",
    "traffic": "route-histories-1k-26k", "chips": 1,
    "why": "made up by the tests: a third sequence cell, never run"}
JOINED = ["seq_mfu_pct", "seq_step_host_pct", "seq_padded_token_pct",
          "device_idle_pct.seq"]        # the model-blind sequence metrics
MADE_UP_ENTRY = {
    "name": "made_up_last_ms", "unit": "ms", "better": "lower",
    "source": "program_span", "layer": "refit cycle (host)",
    "moves": "gnn_edges_per_s", "workloads": ["gnn-refit"]}
BORROWED_READER = {"made_up_last_ms": "refit_save_ms"}


def extended():
    """The manifest, read anew, with the made-up cell and entry appended."""
    m = manifest()
    m["workloads"].append(dict(MADE_UP_CELL))
    for metric in m["end_to_end"] + m["per_layer"]:
        if metric["name"] in ["od_rows_per_s"] + JOINED:
            metric["workloads"].append(MADE_UP_CELL["name"])
    m["per_layer"].append(dict(MADE_UP_ENTRY))
    return m


MANIFESTS = {"accepted": manifest, "extended": extended}
ACCEPTED_CELLS = ("od-score", "gnn-refit", "route-lm-score",
                  "route-lm-sala-long")


def both_manifests(test):
    """Run a test of the manifest's shape on the accepted manifest and
    on the extended copy, as two cases (argument ``m``)."""
    return pytest.mark.parametrize(
        "m", [pytest.param(make(), id=name)
              for name, make in MANIFESTS.items()])(test)


def reader_file(name: str) -> str:
    """``benchmark/metrics/<name>.py``; the made-up entry points at an
    accepted reader."""
    return os.path.join(R.HERE, "metrics",
                        BORROWED_READER.get(name, name) + ".py")


def entry_of(m, name: str):
    """(fields, workloads) of the one ``per_layer`` entry of that name:
    its fields without the list of cells, which later cells may join."""
    (entry,) = [dict(e) for e in m["per_layer"] if e["name"] == name]
    return entry, entry.pop("workloads")


def reported(m, cell: str):
    """Names of the per-layer metrics the cell reports in a traced run."""
    e2e = [x["name"] for x in R.metrics_of(m, "end_to_end", cell)]
    return [x["name"] for x in R.metrics_of(m, "per_layer", cell, e2e)]


def cell_files(name: str):
    """(cell, config, mix) of a cell at its toy size."""
    cell, config, mix = R.load_cell(manifest(), name)
    config.update(TOY[name][0])
    mix.update(TOY[name][1])
    return cell, config, mix
