"""Share of the train step's device time in gather and scatter
(segment-sum) operations. The trace names them only ``fusion.N``; what
marks them is in the instruction's text (``benchmark.trace.op_kind``)."""

from benchmark import trace


def read(ctx):
    kinds = trace.kind_seconds(ctx["trace"], ctx["lo"], ctx["hi"],
                               ctx["counts"]["module"])
    total = sum(kinds.values())
    if total <= 0.0:
        return None
    return 100.0 * kinds.get("gather_scatter", 0.0) / total
