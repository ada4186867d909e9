"""The state-space scan kernel's share of its roofline: the least time
the chip could take for the NECESSARY products of the scan in every
held block of the traced window's passes (``benchmark/counts_falcon.py``
``ssd_scan_products``: each real token's place in its chunk, never the
tiles visited; FLOPs over the bf16 peak against x, B, C, dt and y once
a token and each route's final state once over the HBM peak, the larger
of the two) over the summed device time, in the same window, of the
operations the trace names ``ssd_scan_step…`` (the kernel's ``name=``).
``None`` where no such operation ran: a commit, a backend or a model
without the kernel."""

from benchmark import counts_falcon, peaks, trace

KERNEL = "ssd_scan_step"


def read(ctx):
    ops = trace.op_seconds(ctx["trace"], ctx["lo"], ctx["hi"])
    device_s = sum(s for name, s in ops.items() if name.startswith(KERNEL))
    passes = ctx["counts"].get("passes", 0)
    if device_s <= 0.0 or passes <= 0:
        return None
    flops, nbytes = counts_falcon.ssd_scan_products(ctx["config"],
                                                    ctx["mix"]["lengths"])
    peak = peaks.chip_peaks(ctx["device_kind"])
    least = passes * max(flops / peak.bf16_flops_per_s,
                         nbytes / peak.hbm_bytes_per_s)
    return 100.0 * least / device_s
