"""Whole train step's share of the chip's bf16 peak: forward + backward
matmul FLOPs of every step of the window over the window's time, host
work of the cycles included. The step computes in float32, so the bf16
peak is a ceiling it cannot reach; the share is comparable from PR to
PR all the same."""

from benchmark.peaks import mfu_pct as read  # noqa: F401
