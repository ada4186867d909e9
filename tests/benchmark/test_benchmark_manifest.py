"""BENCHMARK.json names only what exists, in the allowed characters."""

import os
import re

import pytest

from _toy import R, manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
M = manifest()


def _names():
    out = [("config", c["name"]) for c in M["configs"]]
    out += [("workload", w["name"]) for w in M["workloads"]]
    out += [("traffic", w["traffic"]) for w in M["workloads"]]
    out += [("metric", m["name"]) for m in M["end_to_end"] + M["per_layer"]]
    return out


@pytest.mark.parametrize("kind,name", _names())
def test_name_uses_allowed_characters(kind, name):
    assert NAME.match(name), (kind, name)


@pytest.mark.parametrize("metric", M["end_to_end"] + M["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_fields(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")


def test_names_are_unique():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in M[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in M["end_to_end"] + M["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("config", M["configs"], ids=lambda c: c["name"])
def test_config_file_exists_and_is_its_own(config):
    path = os.path.join(R.REPO, config["file"])
    assert os.path.isfile(path)
    assert any(config["file"].startswith(p + "/") for p in M["paths"])
    held = R.load_json(path)
    assert held["name"] == config["name"]
    assert held["reduced"] == config["reduced"]


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda w: w["name"])
def test_cell_finds_its_files(cell):
    assert cell["config"] in [c["name"] for c in M["configs"]]
    assert cell["chips"] in (1, 4)
    assert len(cell["why"]) <= 200 and "\n" not in cell["why"]
    mix = R.load_json(R.HERE, "traffic", cell["traffic"] + ".json")
    assert os.path.isfile(os.path.join(R.HERE, "drivers",
                                       mix["driver"] + ".py"))
    assert mix["limits"], "a cell compares at least one number"


@pytest.mark.parametrize("metric", M["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader_and_moves_what_its_cells_report(
        metric):
    assert os.path.isfile(os.path.join(R.HERE, "metrics",
                                       metric["name"] + ".py"))
    moved = [m for m in M["end_to_end"] if m["name"] == metric["moves"]]
    assert len(moved) == 1
    cells = [w["name"] for w in M["workloads"]]
    for cell in metric.get("workloads", cells):
        assert cell in cells
        assert cell in moved[0].get("workloads", cells)


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda w: w["name"])
def test_every_cell_reports_setup_one_more_and_a_layer(cell):
    e2e = [m["name"] for m in R.metrics_of(M, "end_to_end", cell["name"])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert R.metrics_of(M, "per_layer", cell["name"], e2e)


def test_command_and_paths():
    assert M["command"][:2] == ["python3", "benchmark/run.py"]
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    setup = [m for m in M["end_to_end"] if m["name"] == "setup_s"]
    assert setup and setup[0]["bound"] <= 0.1
    assert all(0.01 <= m["bound"] <= 0.1 for m in M["end_to_end"])
    assert 1 <= M["run_seconds"] <= 51


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
