"""Keys the dense causal latent-attention blocks multiplied (whole
chunks up to a block of queries' last key, padded tokens included, the
prediction module's block among them) over the keys their real queries
saw (``t + 1``), from the scorer's counter
``rtpu_seq_latent_keys_total{kind=needed|visited}``: 1.0 is an attention
that multiplies only what a real token sees; padding and the masked
part of the chunks on the diagonal read above it. ``None`` where the
program has no such counter (an older commit, another model) or has
counted nothing."""

from typing import Dict, Optional


def read(ctx: Dict) -> Optional[float]:
    try:
        from routest_tpu.obs import get_registry
    except ImportError:
        return None
    family = get_registry().get("rtpu_seq_latent_keys_total")
    if family is None:
        return None
    by_kind = {labels[0]: child.value for labels, child in family.items()}
    needed = by_kind.get("needed", 0.0)
    if needed <= 0.0 or "visited" not in by_kind:
        return None
    return by_kind["visited"] / needed
