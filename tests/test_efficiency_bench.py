"""Device efficiency end to end (slow): re-runs
``scripts/bench_efficiency.py --quick`` — real 2-replica fleets under
open-loop batch load with the watchdog pinned to the committed battery
curves — and asserts the ISSUE-17 direction invariants: an injected
``device.compute`` slowdown and a forced pathological bucket config
are each detected and paged by the dedicated efficiency SLO within the
bounded window with a flight-recorder bundle naming the program,
replica, and bucket and embedding the expected-vs-measured curve; the
clean fleet raises zero efficiency pages across ≥1 metric flip and ≥1
verified model swap with every watchdog armed on its pin; and the
always-on ledger stays inside the existing ≤5% p95 observability
budget. Tier-1 covers the ledger/watchdog core hermetically
(tests/test_efficiency.py); this exercises the composed loop."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_efficiency_quick(tmp_path):
    out = tmp_path / "efficiency.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "bench_efficiency.py"),
         "--quick", "--out", str(out),
         "--cache-dir", str(tmp_path / "cache")],
        cwd=REPO, timeout=2400, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    record = json.loads(out.read_text())
    assert record["all_pass"], record["checks"]
    scen = record["scenarios"]
    for name in ("device_slowdown", "padding_blowup"):
        s = scen[name]
        assert s["checks"]["detected_and_paged"], s
        assert s["page"]["detect_s"] <= s["detect_bound_s"], s
        assert s["checks"]["bundle_names_program_replica_bucket"], s
        assert s["checks"]["healthy_replica_zero_pages"], s
        assert s["bundle"]["curve_points"] > 0, s["bundle"]
    clean = scen["clean"]
    assert clean["checks"]["zero_efficiency_pages"], clean
    assert clean["metric_flips"] >= 1 and clean["swaps_accepted"] >= 1
    assert clean["checks"]["watchdogs_armed_and_pinned"], clean
    assert clean["checks"]["fleet_rollup_counts_goodput"], clean
    assert clean["checks"]["timeline_family_visible_both_tiers"], clean
    assert scen["overhead"]["checks"]["ledger_within_p95_budget"], \
        scen["overhead"]
