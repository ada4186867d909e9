"""Operations and bytes of the sliding layers' score and value products
of the first route-sequence model, from shapes alone: what a kernel that
does that step and nothing else has to do (as
``counts_seq.full_attention_products`` for the full layers; that file
stays as it is)."""

from __future__ import annotations

from typing import Dict, Sequence

from benchmark.counts_seq import keys_seen
from benchmark.reference.dots3_ref import attention_sizes, layer_kinds

KIND = "sliding_attention"


def window_attention_products(cfg: Dict, lengths: Sequence[int],
                              bytes_per: int = 2) -> tuple[int, int]:
    """(FLOPs, bytes) of the score and value products of the sliding
    layers in one pass over routes of these lengths. FLOPs as
    ``counts_seq.attention_flops`` counts them: a query is charged the
    keys its window holds, ``min(t + 1, window)``, not the keys of the
    tiles a kernel visits. Bytes: the least any tiling can move, a
    layer's queries, keys (the rotary part once a key, not once a head),
    values and outputs of every real token once."""
    a = attention_sizes(cfg, KIND)
    layers = sum(kind == KIND for kind, _ in layer_kinds(cfg))
    seen = sum(keys_seen(int(n), a["window"]) for n in lengths)
    flops = 2 * a["heads"] * (a["d_nope"] + a["d_rope"] + a["d_v"]) * seen
    per_token = (a["heads"] * (a["d_nope"] + a["d_rope"])      # queries
                 + a["heads"] * a["d_nope"] + a["d_rope"]       # keys
                 + 2 * a["heads"] * a["d_v"])                   # values, out
    nbytes = bytes_per * per_token * sum(int(n) for n in lengths)
    return layers * flops, layers * nbytes
