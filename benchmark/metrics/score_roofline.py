"""Scoring kernels' share of their roofline: the least time the chip
could take for the passes' necessary work (matmul FLOPs over the bf16
peak against table in + table out + one weight stream a pass over the
HBM peak; for the shipped widths the compute bound governs, 0.9 G rows/s
against 13.6 G rows/s) over the summed device time of the operations of
the scoring program in the trace."""

from benchmark import peaks, trace


def read(ctx):
    c = ctx["counts"]
    ops = trace.op_seconds(ctx["trace"], ctx["lo"], ctx["hi"], c["module"])
    device_s = sum(ops.values())
    if device_s <= 0.0:
        return None
    peak = peaks.chip_peaks(ctx["device_kind"])
    least = max(c["flops"] / peak.bf16_flops_per_s,
                c["bytes"] / peak.hbm_bytes_per_s)
    return 100.0 * least / device_s
