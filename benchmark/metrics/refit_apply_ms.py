"""Mean ``live.retrain.apply`` per cycle of the window, from the
program's own spans: the forward pass over all arcs after the steps, its
fetch and the finiteness check."""

from benchmark import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx, "apply")
