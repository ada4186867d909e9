"""Milliseconds in which the machine kept a runnable task waiting for a
CPU or the hypervisor ran someone else, over the window's passes:
Σ (``psi_cpu_ms`` + ``steal_ms``) of the ``seq.score_pass`` roots."""

from benchmark.host_stall import stall_ms
from benchmark.seq_steps import ROOT


def read(ctx):
    return stall_ms(ctx, ROOT, "passes")
