"""Seconds the process spent in XLA's backend, compiling its programs
or fetching them from the persistent cache, by the program's own
counter ``rtpu_compile_seconds_total{stage="backend"}`` when the reader
runs (all of it set-up's where ``compiles.window`` is 0)."""

from benchmark.setup_parts import stage_seconds


def read(ctx):
    return stage_seconds("backend")
