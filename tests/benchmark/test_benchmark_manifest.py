"""BENCHMARK.json names only what exists, in the allowed characters.
Every test of the manifest's shape runs twice: on the accepted manifest
and on a copy to which a made-up cell and a made-up last ``per_layer``
entry are appended (``_toy.extended``), as a later PR appends them."""

import os
import re

import pytest

from _toy import (JOINED, MANIFESTS, R, both_manifests, manifest,
                  reader_file)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
M = manifest()


def _names(m):
    out = [("config", c["name"]) for c in m["configs"]]
    out += [("workload", w["name"]) for w in m["workloads"]]
    out += [("traffic", w["traffic"]) for w in m["workloads"]]
    out += [("metric", x["name"]) for x in m["end_to_end"] + m["per_layer"]]
    return out


def _metrics(m):
    return m["end_to_end"] + m["per_layer"]


def _each(items, ident=lambda x: x["name"]):
    """One case for each item of each manifest: (manifest, item)."""
    return [pytest.param(m, x, id=f"{which}-{ident(x)}")
            for which, make in MANIFESTS.items() for m in [make()]
            for x in items(m)]


def test_the_extended_copy_only_appends():
    """What stands in the accepted manifest stands in the copy, at its
    place; the made-up cell is a new pair of accepted files and joins
    lists at their ends; the made-up entry is the last one."""
    m, x = manifest(), MANIFESTS["extended"]()
    grown = ("workloads", "end_to_end", "per_layer")
    assert all(x[k] == m[k] for k in m if k not in grown) and set(x) == set(m)
    assert x["workloads"][:-1] == m["workloads"]
    made_up = x["workloads"][-1]
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert (made_up["config"], made_up["traffic"]) not in pairs
    assert made_up["config"] in [c for c, _ in pairs]
    assert made_up["traffic"] in [t for _, t in pairs]
    assert x["per_layer"][-1]["name"] not in [e["name"]
                                              for e in m["per_layer"]]
    joined = 0
    for was, now in zip(_metrics(m), _metrics(x)):
        lists = was.pop("workloads", None), now.pop("workloads", None)
        assert was == now
        if lists[0] != lists[1]:
            assert lists[1] == lists[0] + [made_up["name"]]
            joined += 1
    assert joined == 1 + len(JOINED)


@pytest.mark.parametrize("m,name", _each(_names, "-".join))
def test_name_uses_allowed_characters(m, name):
    assert NAME.match(name[1]), name


@pytest.mark.parametrize("m,metric", _each(_metrics))
def test_metric_fields(m, metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")


@both_manifests
def test_names_are_unique(m):
    for group in ("configs", "workloads"):
        names = [x["name"] for x in m[group]]
        assert len(names) == len(set(names))
    metrics = [x["name"] for x in _metrics(m)]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("m,config", _each(lambda m: m["configs"]))
def test_config_file_exists_and_is_its_own(m, config):
    path = os.path.join(R.REPO, config["file"])
    assert os.path.isfile(path)
    assert any(config["file"].startswith(p + "/") for p in m["paths"])
    held = R.load_json(path)
    assert held["name"] == config["name"]
    assert held["reduced"] == config["reduced"]


@pytest.mark.parametrize("m,cell", _each(lambda m: m["workloads"]))
def test_cell_finds_its_files(m, cell):
    assert cell["config"] in [c["name"] for c in m["configs"]]
    assert cell["chips"] in (1, 4)
    assert len(cell["why"]) <= 200 and "\n" not in cell["why"]
    mix = R.load_json(R.HERE, "traffic", cell["traffic"] + ".json")
    assert os.path.isfile(os.path.join(R.HERE, "drivers",
                                       mix["driver"] + ".py"))
    assert mix["limits"], "a cell compares at least one number"


@pytest.mark.parametrize("m,metric", _each(lambda m: m["per_layer"]))
def test_per_layer_metric_has_a_reader_and_moves_what_its_cells_report(
        m, metric):
    assert os.path.isfile(reader_file(metric["name"]))
    moved = [x for x in m["end_to_end"] if x["name"] == metric["moves"]]
    assert len(moved) == 1
    cells = [w["name"] for w in m["workloads"]]
    for cell in metric.get("workloads", cells):
        assert cell in cells
        assert cell in moved[0].get("workloads", cells)


@pytest.mark.parametrize("m,cell", _each(lambda m: m["workloads"]))
def test_every_cell_reports_setup_one_more_and_a_layer(m, cell):
    e2e = [x["name"] for x in R.metrics_of(m, "end_to_end", cell["name"])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert R.metrics_of(m, "per_layer", cell["name"], e2e)


@both_manifests
def test_command_and_paths(m):
    assert m["command"][:2] == ["python3", "benchmark/run.py"]
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    setup = [x for x in m["end_to_end"] if x["name"] == "setup_s"]
    assert setup and setup[0]["bound"] <= 0.1
    assert all(0.01 <= x["bound"] <= 0.1 for x in m["end_to_end"])
    assert 1 <= m["run_seconds"] <= 51


def test_without_a_tpu_a_run_exits_non_zero_and_prints_no_result(capsys):
    rc = R.main(["--workload", M["workloads"][0]["name"], "--seed", "1",
                 "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_without_the_program_a_run_exits_non_zero_and_prints_no_result(
        tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    ``paths`` has nothing to measure."""
    import shutil
    import subprocess
    import sys

    shutil.copy(os.path.join(R.REPO, "BENCHMARK.json"), tmp_path)
    for p in M["paths"]:
        shutil.copytree(os.path.join(R.REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    done = subprocess.run(
        [sys.executable, *M["command"][1:], "--workload",
         M["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=120)
    assert done.returncode != 0
    assert done.stdout == ""
