"""Operations and bytes of the route-sequence scorer, from shapes alone:
the NECESSARY work, whatever implements it. Matrix-multiply FLOPs only
(2 per multiply-add), of real tokens only; a query is charged the keys
its layer lets it see — ``min(t + 1, index_topk)`` in a full layer,
``min(t + 1, window)`` in a sliding one — the selector all ``t + 1``
keys of the queries that have more than ``index_topk`` to choose from,
the expert layer the assignments that land on held experts and nothing
for the others. Padding, keys computed under a mask and recomputation
are not counted, so a share of the peak computed from these cannot
pass 100%.
"""

from __future__ import annotations

from typing import Dict, Sequence

from benchmark.reference.dots3_ref import attention_sizes, layer_kinds


def attention_weight_count(cfg: Dict, kind: str) -> int:
    a, d = attention_sizes(cfg, kind), cfg["hidden_size"]
    n = (d * a["r_q"] + a["r_q"] * a["heads"] * (a["d_nope"] + a["d_rope"])
         + d * (a["r_kv"] + a["d_rope"])
         + a["r_kv"] * a["heads"] * (a["d_nope"] + a["d_v"])
         + d * a["heads"] + a["heads"] * a["d_v"] * d)
    if a["top_k"]:
        n += (a["r_q"] * a["index_heads"] * a["index_dim"]
              + d * a["index_dim"] + d * a["index_heads"])
    return n


def keys_seen(length: int, cap: int) -> int:
    """sum over t < length of min(t + 1, cap)."""
    full = min(length, cap)
    return full * (full + 1) // 2 + (length - full) * cap


def attention_flops(cfg: Dict, kind: str, length: int) -> int:
    """Projections, score and value products and, in a full layer, the
    selector, for one route of ``length`` tokens."""
    a = attention_sizes(cfg, kind)
    flops = 2 * length * attention_weight_count(cfg, kind)
    cap = a["top_k"] or a["window"]
    flops += (2 * a["heads"] * (a["d_nope"] + a["d_rope"] + a["d_v"])
              * keys_seen(length, cap))
    if a["top_k"] and length > a["top_k"]:
        choosing = (length * (length + 1) // 2
                    - a["top_k"] * (a["top_k"] + 1) // 2)
        flops += 2 * a["index_heads"] * a["index_dim"] * choosing
    return flops


def full_attention_products(cfg: Dict, lengths: Sequence[int],
                            bytes_per: int = 2) -> tuple[int, int]:
    """(FLOPs, bytes) of the score and value products of the full
    layers in one pass over routes of these lengths: what a kernel that
    does that step and nothing else has to do. FLOPs as
    :func:`attention_flops` counts them: a query is charged the keys it
    may SEE, ``min(t + 1, index_topk)``, not the keys a mask
    multiplies. Bytes: the least any blocking of the queries can move,
    a layer's queries, keys (the rotary part once a key, not once a
    head), values and outputs of every real token once."""
    a = attention_sizes(cfg, "full_attention")
    layers = sum(kind == "full_attention" for kind, _ in layer_kinds(cfg))
    seen = sum(keys_seen(int(n), a["top_k"]) for n in lengths)
    flops = 2 * a["heads"] * (a["d_nope"] + a["d_rope"] + a["d_v"]) * seen
    per_token = (a["heads"] * (a["d_nope"] + a["d_rope"])      # queries
                 + a["heads"] * a["d_nope"] + a["d_rope"]       # keys
                 + 2 * a["heads"] * a["d_v"])                   # values, out
    nbytes = bytes_per * per_token * sum(int(n) for n in lengths)
    return layers * flops, layers * nbytes


def mlp_flops(d: int, width: int) -> int:
    return 2 * 3 * d * width


def pass_flops(cfg: Dict, lengths: Sequence[int],
               held_assignments: float) -> float:
    """One pass over routes of these lengths. ``held_assignments``: the
    (token, expert) assignments that landed on held experts, summed
    over the expert layers."""
    d, tokens = cfg["hidden_size"], sum(int(n) for n in lengths)
    total = 0.0
    for attn_kind, ffn_kind in layer_kinds(cfg):
        total += sum(attention_flops(cfg, attn_kind, int(n)) for n in lengths)
        if ffn_kind == "dense":
            total += tokens * mlp_flops(d, cfg["intermediate_size"])
        else:
            total += tokens * (2 * d * cfg["published"]["n_routed_experts"]
                               + mlp_flops(d, cfg["moe_intermediate_size"]
                                           * cfg["n_shared_experts"]))
    total += held_assignments * mlp_flops(d, cfg["moe_intermediate_size"])
    return total + tokens * 2 * d * cfg["vocab_size"]


def weight_bytes(cfg: Dict, bytes_per: int = 2) -> int:
    """One stream of every held parameter."""
    d, n = cfg["hidden_size"], 0
    for attn_kind, ffn_kind in layer_kinds(cfg):
        n += attention_weight_count(cfg, attn_kind)
        if ffn_kind == "dense":
            n += 3 * d * cfg["intermediate_size"]
        else:
            m = cfg["moe_intermediate_size"]
            n += (d * cfg["published"]["n_routed_experts"]
                  + 3 * d * m * (cfg["n_routed_experts"]
                                 + cfg["n_shared_experts"]))
    return bytes_per * (n + 2 * d * cfg["vocab_size"])
