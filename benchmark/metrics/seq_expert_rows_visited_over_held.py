"""Rows the held experts' grouped product multiplied over the rows it
had to, from the scorer's counter ``rtpu_seq_expert_rows_total{kind=
visited|held}``: 1.0 is a product that multiplies the held assignments
alone; the padding of each expert's last tile of rows reads above it.
``visited`` is the layout's rule applied by the program to the device's
per-expert counts, not a count the kernels make: it shows the padding
the layout asks for, not a kernel that visits more than that.
``None`` where the program has no such counter (an older commit,
another model) or has counted nothing."""

from typing import Dict, Optional


def read(ctx: Dict) -> Optional[float]:
    try:
        from routest_tpu.obs import get_registry
    except ImportError:
        return None
    family = get_registry().get("rtpu_seq_expert_rows_total")
    if family is None:
        return None
    by_kind = {labels[0]: child.value for labels, child in family.items()}
    held = by_kind.get("held", 0.0)
    if held <= 0.0 or "visited" not in by_kind:
        return None
    return by_kind["visited"] / held
