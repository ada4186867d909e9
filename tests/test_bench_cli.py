"""Contract of bench.py, the one-chip measurement: it needs a TPU and
never measures another backend under a device metric's name, and its
peak-rate table refuses a chip it does not know."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402


def test_bench_without_a_chip_exits_nonzero_and_prints_no_metric():
    proc = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                          capture_output=True, text=True, cwd=REPO,
                          timeout=120)
    assert proc.returncode != 0
    assert '"metric"' not in proc.stdout, proc.stdout
    # one line, naming what it found instead of a TPU
    reason = proc.stderr.strip().splitlines()[-1]
    assert reason.startswith("bench: ") and "cpu" in reason


def test_chip_peaks_knows_the_v5e_as_jax_names_it():
    assert bench.chip_peaks("TPU v5 lite") == (197.0, 819.0)
    assert bench.chip_peaks("TPU v5e") == bench.chip_peaks("TPU v5 lite")


@pytest.mark.parametrize("kind", ["cpu", "", None, "TPU v9000", "NVIDIA H100"])
def test_chip_peaks_raises_on_an_unknown_kind(kind):
    with pytest.raises(ValueError, match="no peak-rate table row"):
        bench.chip_peaks(kind)
