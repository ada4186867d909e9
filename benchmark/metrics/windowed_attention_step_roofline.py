"""The sliding layers' window kernel's share of its roofline: the least
time the chip could take for the NECESSARY score and value products of
the traced window's passes (``benchmark/counts_window.py``
``window_attention_products``: a query charged the ``min(t + 1,
window)`` keys it sees, not the keys of the tiles the kernel visits;
FLOPs over the bf16 peak against the step's queries, keys, values and
outputs once over the HBM peak; for the published widths the compute
bound governs, narrowly) over the summed device time, in the same
window, of the operations the trace names ``windowed_attention_step…``
(the kernel's ``name=``). ``None`` where no such operation ran: a commit
or a model without the kernel."""

from benchmark import counts_window, peaks, trace

KERNEL = "windowed_attention_step"


def read(ctx):
    ops = trace.op_seconds(ctx["trace"], ctx["lo"], ctx["hi"])
    device_s = sum(s for name, s in ops.items() if name.startswith(KERNEL))
    passes = ctx["counts"].get("passes", 0)
    if device_s <= 0.0 or passes <= 0:
        return None
    flops, nbytes = counts_window.window_attention_products(
        ctx["config"], ctx["mix"]["lengths"])
    peak = peaks.chip_peaks(ctx["device_kind"])
    least = passes * max(flops / peak.bf16_flops_per_s,
                         nbytes / peak.hbm_bytes_per_s)
    return 100.0 * least / device_s
