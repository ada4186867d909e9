"""Device time of one train step: mean duration of the runs of the
step's program inside the traced window."""

from benchmark import trace


def read(ctx):
    runs = trace.module_runs(ctx["trace"], ctx["lo"], ctx["hi"],
                             ctx["counts"]["module"])
    if not runs:
        return None
    return 1e3 * sum(runs) / len(runs)
