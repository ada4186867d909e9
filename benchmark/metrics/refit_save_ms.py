"""Mean ``live.retrain.save`` per cycle of the window, from the
program's own spans: writing the artifact."""

from benchmark import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx, "save")
