"""Keys the block-selecting layers' second stage multiplied over the
keys in the chosen blocks at or before the query, from the scorer's
counter ``rtpu_seq_sparse_keys_total{kind=visited|chosen}``: 1.0 is a
second stage that multiplies only what was chosen; a mask over every
causal chunk reads the route's length over 2 x 4,096. ``None`` where
the program has no such counter (an older commit) or has counted
nothing."""

from typing import Dict, Optional


def read(ctx: Dict) -> Optional[float]:
    try:
        from routest_tpu.obs import get_registry
    except ImportError:
        return None
    family = get_registry().get("rtpu_seq_sparse_keys_total")
    if family is None:
        return None
    by_kind = {labels[0]: child.value for labels, child in family.items()}
    if by_kind.get("chosen", 0.0) <= 0.0 or "visited" not in by_kind:
        return None
    return by_kind["visited"] / by_kind["chosen"]
