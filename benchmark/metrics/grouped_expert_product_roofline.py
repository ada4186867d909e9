"""The held experts' grouped-product kernels' share of their roofline:
the least time the chip could take for the NECESSARY products of the
traced window's passes (``benchmark/counts_experts.py``
``grouped_expert_products``: the assignments that landed on held
experts, which the driver reports, times their expert's three matrices,
not the rows of the tiles the kernels visit; FLOPs over the bf16 peak
against every held expert's matrices once a step an expert block plus
each held row in and out once over the HBM peak, whichever is longer;
the expert blocks a pass ran from the scorer's counter
``rtpu_seq_expert_blocks_total``, not from a model's layer list)
over the summed device time, in the same window, of the operations the
trace names ``grouped_expert_product…`` (the kernels' ``name=``).
``None`` where no such operation ran, or nothing counted the blocks: a
commit or a model without the kernels."""

from benchmark import counts_experts, peaks, trace

KERNEL = "grouped_expert_product"


def block_steps(counts):
    """Expert blocks the steps of ONE pass ran (blocks a step x steps),
    from what the scorer has counted since the process began: all of
    ``rtpu_seq_expert_blocks_total`` over the passes, which are
    ``rtpu_seq_tokens_total{kind=real}`` over a pass's real tokens (the
    driver's). ``None`` without the counters."""
    try:
        from routest_tpu.obs import get_registry
    except ImportError:
        return None
    blocks, tokens = (get_registry().get("rtpu_seq_expert_blocks_total"),
                      get_registry().get("rtpu_seq_tokens_total"))
    if blocks is None or tokens is None or not counts.get("tokens_real"):
        return None
    real = sum(child.value for labels, child in tokens.items()
               if labels[0] == "real")
    if real <= 0.0:
        return None
    return (sum(child.value for _, child in blocks.items())
            * counts["tokens_real"] / real)


def read(ctx):
    ops = trace.op_seconds(ctx["trace"], ctx["lo"], ctx["hi"])
    device_s = sum(s for name, s in ops.items() if name.startswith(KERNEL))
    counts = ctx["counts"]
    passes = counts.get("passes", 0)
    blocks = block_steps(counts)
    if (device_s <= 0.0 or passes <= 0 or not blocks
            or "held_assignments" not in counts):
        return None
    flops, nbytes = counts_experts.grouped_expert_products(
        ctx["config"], counts["held_assignments"], blocks)
    peak = peaks.chip_peaks(ctx["device_kind"])
    least = passes * max(flops / peak.bf16_flops_per_s,
                         nbytes / peak.hbm_bytes_per_s)
    return 100.0 * least / device_s
