"""Whole scoring step's share of the chip's bf16 peak: matmul FLOPs of
every row scored in the window over the window's time (host clock)."""

from benchmark.peaks import mfu_pct as read  # noqa: F401
