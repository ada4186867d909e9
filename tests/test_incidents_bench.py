"""Incident correlation end to end (slow): re-runs
``scripts/bench_incidents.py --quick`` and asserts the ISSUE-20
direction invariants: a bad deploy rolled out through the canary state
machine, a chaos-jammed customize cycle, and a geo-front region kill
each page with the injected cause ranked suspect #1 in the bundle's
``suspects.json`` (matched on the paging scope's blast-radius labels),
while a clean window of ≥20 legitimate metric flips and ≥2 verified
model swaps produces zero pages and zero false attributions. Tier-1
covers the ledger/ranker core hermetically (tests/test_ledger.py);
this exercises the composed pipeline."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_INJECTED = ("bad_deploy", "jammed_customize", "region_kill")


def _assert_record_shape(record):
    assert record["all_pass"], record["scenarios"]
    assert set(record["scenarios"]) == set(_INJECTED) | {"clean_window"}
    for name in _INJECTED:
        s = record["scenarios"][name]
        assert s["checks"]["paged_with_suspects"], s
        assert s["checks"]["true_cause_ranked_first"], s
        assert s["suspects"], s
    top = record["scenarios"]["bad_deploy"]["suspects"][0]
    assert top["kind"] == "rollout.phase"
    assert top["labels"].get("version") == "v2-err"
    assert "version" in top["matched"]
    jam = record["scenarios"]["jammed_customize"]["suspects"][0]
    assert jam["kind"] in ("live.customize_failed", "chaos.fire",
                           "chaos.arm")
    kill = record["scenarios"]["region_kill"]["suspects"][0]
    assert kill["kind"] == "region.kill"
    assert kill["labels"].get("region") == "east"
    clean = record["scenarios"]["clean_window"]
    assert clean["flips"] >= 20 and clean["verified_swaps"] >= 2
    assert clean["incidents"] == 0
    assert clean["checks"]["zero_false_attributions"], clean


@pytest.mark.slow
def test_incidents_quick(tmp_path):
    out = tmp_path / "incidents.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "bench_incidents.py"),
         "--quick", "--out", str(out)],
        cwd=REPO, timeout=900, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    _assert_record_shape(json.loads(out.read_text()))


@pytest.mark.slow
def test_committed_incidents_artifact_passes():
    """The committed measurement of record must itself satisfy the
    acceptance bar."""
    record = json.load(open(os.path.join(REPO, "artifacts",
                                         "incidents.json")))
    _assert_record_shape(record)
