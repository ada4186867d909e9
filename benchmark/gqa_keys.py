"""Keys the plain grouped-query layers multiplied over the keys their
real queries saw, from the scorer's counter
``rtpu_seq_gqa_keys_total{layer=window|full, kind=needed|visited}``:
1.0 is an attention that multiplies only what a real token sees;
padding, the masked part of a block on the diagonal and the block
before a short window all read above it. ``None`` where the program has
no such counter (an older commit, another model) or has counted
nothing."""

from __future__ import annotations

from typing import Optional


def visited_over_needed(layer: str) -> Optional[float]:
    try:
        from routest_tpu.obs import get_registry
    except ImportError:
        return None
    family = get_registry().get("rtpu_seq_gqa_keys_total")
    if family is None:
        return None
    by = {labels: child.value for labels, child in family.items()}
    needed = by.get((layer, "needed"), 0.0)
    if needed <= 0.0 or (layer, "visited") not in by:
        return None
    return by[(layer, "visited")] / needed
