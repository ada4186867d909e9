"""Mean ``live.retrain.aggregate`` per cycle of the window, from the
program's own spans: the window's aggregation and every host array the
cycle prepares."""

from benchmark import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx, "aggregate")
