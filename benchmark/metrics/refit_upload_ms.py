"""Mean ``live.retrain.upload`` per cycle of the window, from the
program's own spans: model and step bring-up and the hand-over of the
cycle's arrays to the device (an upload still in flight at its end is
absorbed by the steps)."""

from benchmark import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx, "upload")
