"""The dense causal latent-attention kernel's share of its roofline: the
least time the chip could take for the NECESSARY score and value
products of the traced window's passes (``benchmark/counts_gigachat.py``
``latent_attention_products``: a query charged the ``t + 1`` keys it may
see, not the keys of the tiles a mask multiplies; FLOPs over the bf16
peak against each block's queries, keys, values and outputs once over
the HBM peak; at the published widths the compute bound governs) over
the summed device time, in the same window, of the operations the trace
names ``latent_attention_step…`` (the kernel's ``name=``). ``None``
where no such operation ran: a commit, a backend or a model without the
kernel."""

from benchmark import counts_gigachat, peaks, trace

KERNEL = "latent_attention_step"


def read(ctx):
    ops = trace.op_seconds(ctx["trace"], ctx["lo"], ctx["hi"])
    device_s = sum(s for name, s in ops.items() if name.startswith(KERNEL))
    passes = ctx["counts"].get("passes", 0)
    if device_s <= 0.0 or passes <= 0:
        return None
    flops, nbytes = counts_gigachat.latent_attention_products(
        ctx["config"], ctx["mix"]["lengths"])
    peak = peaks.chip_peaks(ctx["device_kind"])
    least = passes * max(flops / peak.bf16_flops_per_s,
                         nbytes / peak.hbm_bytes_per_s)
    return 100.0 * least / device_s
