"""Toy sizes of the two cells for the CPU tests: the real files, with
only the sizes shrunk."""

import os
import sys

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


def cell_files(name: str):
    """(cell, config, mix) of a cell at its toy size."""
    cell, config, mix = R.load_cell(manifest(), name)
    config.update(TOY[name][0])
    mix.update(TOY[name][1])
    return cell, config, mix
