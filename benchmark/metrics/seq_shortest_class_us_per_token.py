"""Device microseconds a REAL token in the steps of the shortest length
class, over the window's passes, from ``seq.wait.step``."""

from benchmark.seq_steps import class_us_per_token


def read(ctx):
    return class_us_per_token(ctx, longest=False)
