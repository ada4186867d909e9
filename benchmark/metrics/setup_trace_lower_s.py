"""Seconds the process spent tracing its programs in Python and
lowering them to StableHLO (``rtpu_compile_seconds_total``, stages
``trace`` + ``lower``): what a persistent cache does not save and
lowering ahead of time would."""

from benchmark.setup_parts import stage_seconds


def read(ctx):
    return stage_seconds("trace", "lower")
