"""Blackbox probing end to end (slow): re-runs
``scripts/bench_probing.py --quick`` — real fleets, open-loop load,
three injected correctness faults — and asserts the ISSUE-15 direction
invariants: every injected fault (compute divergence, stale metric
epoch, divergent model past the swap gate) is detected and paged by
the prober's correctness SLO within the bounded window with a bundle
naming the faulty replica, the clean run raises zero correctness pages
across ≥1 legitimate metric flip and ≥1 verified model swap, probe
traffic appears in no user-facing SLO family, and probe overhead stays
within the budget. Tier-1 covers the prober core hermetically
(tests/test_prober.py); this exercises the composed loop."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_probing_quick(tmp_path):
    out = tmp_path / "probing.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "bench_probing.py"),
         "--quick", "--out", str(out),
         "--cache-dir", str(tmp_path / "cache")],
        cwd=REPO, timeout=2400, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    record = json.loads(out.read_text())
    assert record["all_pass"], record["checks"]
    scen = record["scenarios"]
    # Each injected fault: detected, paged within bound, bundle names
    # the faulty replica with the probe/oracle pair embedded.
    for name in ("compute_divergence", "stale_epoch",
                 "divergent_model"):
        s = scen[name]
        assert s["checks"]["detected_and_paged"], s
        assert s["page"]["detect_s"] <= s["detect_bound_s"], s
        assert s["checks"]["bundle_names_faulty_replica"], s
        assert s["checks"]["user_slo_ok"], s
    assert scen["stale_epoch"]["checks"]["skew_dimension_identified"], \
        scen["stale_epoch"]
    # Clean run: green across a flip and a verified swap; exclusion
    # exact; overhead bounded.
    clean = scen["clean"]
    assert clean["checks"]["zero_correctness_pages"], clean
    assert clean["metric_flips"] >= 1 and clean["swaps_accepted"] >= 1
    assert clean["checks"]["probe_traffic_excluded"], clean["exclusion"]
    assert clean["checks"]["strict_oracle_parity"], clean["strict_oracle"]
    assert clean["checks"]["overhead_within_budget"], clean["overhead"]
