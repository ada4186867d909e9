"""Share of the full layers' blocks of queries whose selection ran the
radix top-k as the fused kernel, from the scorer's counter
``rtpu_seq_topk_blocks_total{path}``: 100 where the kernel engaged for
every selecting block of every pass, 0 where the selection function
refused the cell's shapes. ``None`` where the program has no such
counter (an older commit) or has counted nothing."""

from typing import Dict, Optional


def read(ctx: Dict) -> Optional[float]:
    try:
        from routest_tpu.obs import get_registry
    except ImportError:
        return None
    family = get_registry().get("rtpu_seq_topk_blocks_total")
    if family is None:
        return None
    by_path = {labels[0]: child.value for labels, child in family.items()}
    total = sum(by_path.values())
    if total <= 0.0:
        return None
    return 100.0 * by_path.get("fused", 0.0) / total
