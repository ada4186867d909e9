"""Milliseconds in which the machine kept a runnable task waiting for a
CPU or the hypervisor ran someone else, over the window's refit cycles:
Σ (``psi_cpu_ms`` + ``steal_ms``) of the saved ``live.retrain`` roots."""

from benchmark.host_stall import stall_ms
from benchmark.program_spans import ROOT


def read(ctx):
    return stall_ms(ctx, ROOT, "cycles", result="saved")
