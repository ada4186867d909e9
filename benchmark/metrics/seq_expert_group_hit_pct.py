"""Share of the real tokens of the expert blocks one of whose chosen
experts lies in the held experts' routing group, from the scorer's
counter ``rtpu_seq_expert_group_tokens_total{kind=held_group|all}``:
under group-limited routing a token keeps 4 of 8 groups, so about half
the tokens bring this chip no row at all and the others about twice the
rows an unlimited router would give it: how lumpy the grouped product's
work is. ``None`` where the program has no such counter (an older
commit, a router with one group) or has counted nothing."""

from typing import Dict, Optional


def read(ctx: Dict) -> Optional[float]:
    try:
        from routest_tpu.obs import get_registry
    except ImportError:
        return None
    family = get_registry().get("rtpu_seq_expert_group_tokens_total")
    if family is None:
        return None
    by_kind = {labels[0]: child.value for labels, child in family.items()}
    total = by_kind.get("all", 0.0)
    if total <= 0.0 or "held_group" not in by_kind:
        return None
    return 100.0 * by_kind["held_group"] / total
