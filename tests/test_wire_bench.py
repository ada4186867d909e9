"""Binary wire serving end to end (slow): re-runs
``scripts/bench_wire.py --quick`` — a real supervised worker behind
the in-process gateway, plus the bench_probing live fleet with the
wire format armed — and asserts the ISSUE-19 direction invariants:
bitwise wire↔JSON parity through the gateway, ≥2× small-batch rows/s
over the JSON path, <1 ms gateway-added p95 over a direct channel
hop, sustained ≥100k rows/s through one gateway, connection reuse
(not per-request HTTP), and the prober's ``wire`` parity kind green
across a metric flip and a verified model swap under open-loop binary
load. Tier-1 covers the codec and serving paths hermetically
(tests/test_wirecodec.py, tests/test_wire_serving.py); this exercises
the measured loop."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_wire_quick(tmp_path):
    out = tmp_path / "wire.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "bench_wire.py"),
         "--quick", "--out", str(out),
         "--cache-dir", str(tmp_path / "cache")],
        cwd=REPO, timeout=2400, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    record = json.loads(out.read_text())
    assert record["all_pass"], record["checks"]
    micro = record["scenarios"]["micro"]
    assert micro["parity"]["ok"], micro["parity"]
    assert micro["speedup_small_batches"] >= 2.0, micro["throughput"]
    assert micro["gateway_overhead"]["added_p95_ms"] < 1.0, \
        micro["gateway_overhead"]
    assert micro["sustained"]["rows_per_s"] >= 100_000, micro["sustained"]
    assert micro["channel"]["reuse_ratio"] > 0.9, micro["channel"]
    probe = record["scenarios"]["probe_parity"]
    assert probe["checks"]["wire_probe_green"], probe
    assert probe["swaps_accepted"] >= 1 and probe["metric_flips"] >= 1
    assert probe["correctness_wire_state"] == "ok", probe
