"""Whole scoring pass's share of the chip's bf16 peak: the matmul FLOPs
of the NECESSARY work of every pass of the window (``benchmark/
counts_seq.py``: real tokens, the keys a layer lets a query see, the
assignments on held experts) over the window's time (host clock)."""

from benchmark.peaks import mfu_pct as read  # noqa: F401
