"""Published peak rates by ``device_kind``; an unknown kind is an error.

Copied from ``bench._CHIP_PEAKS`` (PR 21 checked that JAX names the v5e
"TPU v5 lite"). Source of the v5e row: Google Cloud documentation,
"TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s.
"""

from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    bf16_flops_per_s: float
    hbm_bytes_per_s: float
    source: str


_V5E = Peaks(197e12, 819e9, 'Google Cloud documentation, "TPU v5e"')

PEAKS = {"v5 lite": _V5E, "v5e": _V5E}


def mfu_pct(ctx):
    """The window's counted matmul FLOPs over its time (host clock) as
    a share of the chips' bf16 peak (what every ``*_mfu_pct`` reader
    returns)."""
    c = ctx["counts"]
    if not c.get("flops") or not c.get("window_s"):
        return None
    peak = chip_peaks(ctx["device_kind"]).bf16_flops_per_s
    return 100.0 * c["flops"] / c["window_s"] / (peak * ctx["chips"])


def chip_peaks(device_kind: str) -> Peaks:
    kind = (device_kind or "").lower()
    for key, peaks in PEAKS.items():
        if key in kind:
            return peaks
    raise ValueError(
        f"no peak-rate row for device kind {device_kind!r}: add one to "
        f"benchmark/peaks.py with its source")
