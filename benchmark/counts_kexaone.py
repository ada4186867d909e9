"""Operations and bytes of the third route-sequence model's scoring
pass (configuration ``k-exaone-236b-ep8``), from shapes alone: the
NECESSARY work, whatever implements it. Matrix-multiply FLOPs only (2
per multiply-add), of real tokens only:

- every weight matrix of the held layers once a token — attention's
  four, the dense MLP's three or the router (over ALL published
  experts) and the shared expert — and the head (the embedding is a
  lookup);
- a query head's score and value products over the keys its layer lets
  it see: ``min(t + 1, sliding_window)`` in a sliding layer, ``t + 1``
  in a full one, and nothing for a key computed under a mask or in a
  block's padding;
- the held experts' three matrices once an assignment that landed on a
  held expert (``held_assignments``, which the program reports and the
  reference confirms) and nothing for the others;
- the prediction module over a route's ``n - 1`` positions: its
  projection, one full-attention expert block, the head again.

Padding and recomputation are not counted, so a share of the peak
computed from these cannot pass 100%.
"""

from __future__ import annotations

from typing import Dict, Sequence

from benchmark.counts_seq import keys_seen, mlp_flops
from benchmark.reference.kexaone_ref import FULL, SLIDING, layer_kinds


def attention_weight_count(cfg: Dict) -> int:
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    wide = cfg["num_attention_heads"] * dh
    narrow = cfg["num_key_value_heads"] * dh
    return 2 * d * wide + 2 * d * narrow


def ffn_weight_count(cfg: Dict, kind: str, experts: int) -> int:
    """The matrices of one feed-forward block with ``experts`` routed
    experts beside the router and the shared one."""
    d = cfg["hidden_size"]
    if kind == "dense":
        return 3 * d * cfg["intermediate_size"]
    m = cfg["moe_intermediate_size"]
    return (d * cfg["published"]["num_experts"]
            + 3 * d * m * (experts + cfg["num_shared_experts"]))


def has_module(cfg: Dict) -> bool:
    return (cfg["num_nextn_predict_layers"] > 0
            and cfg.get("share", {}).get("mtp_held", True))


def parameter_count(cfg: Dict) -> int:
    """Every parameter held: matrices, norm vectors, the router's bias,
    embedding, head, the module."""
    d = cfg["hidden_size"]
    block = attention_weight_count(cfg) + 2 * cfg["head_dim"] + 2 * d
    sparse = (block + ffn_weight_count(cfg, "sparse", cfg["num_experts"])
              + cfg["published"]["num_experts"])
    n = 2 * d * cfg["vocab_size"] + d
    for _, ffn in layer_kinds(cfg):
        n += sparse if ffn == "sparse" else block + ffn_weight_count(
            cfg, ffn, 0)
    if has_module(cfg):
        n += sparse + 2 * d * d + 3 * d
    return n


def attention_products(cfg: Dict, kind: str, length: int) -> int:
    """Score and value products of one route in one layer of ``kind``."""
    cap = cfg["sliding_window"] if kind == SLIDING else length
    return (2 * cfg["num_attention_heads"] * 2 * cfg["head_dim"]
            * keys_seen(length, cap))


def pass_flops(cfg: Dict, lengths: Sequence[int],
               held_assignments: float) -> float:
    """One pass over routes of these lengths. ``held_assignments``: the
    (token, expert) assignments that landed on held experts, summed
    over the expert layers, the module's among them."""
    d = cfg["hidden_size"]
    head = 2 * d * cfg["vocab_size"]
    tokens = sum(int(n) for n in lengths)
    total = float(tokens * head)
    for attn, ffn in layer_kinds(cfg):
        total += 2 * tokens * (attention_weight_count(cfg)
                               + ffn_weight_count(cfg, ffn, 0))
        total += sum(attention_products(cfg, attn, int(n)) for n in lengths)
    if has_module(cfg):
        positions = sum(max(int(n) - 1, 0) for n in lengths)
        total += positions * (2 * (2 * d * d + attention_weight_count(cfg)
                                   + ffn_weight_count(cfg, "sparse", 0))
                              + head)
        total += sum(attention_products(cfg, FULL, max(int(n) - 1, 0))
                     for n in lengths)
    return total + held_assignments * mlp_flops(
        d, cfg["moe_intermediate_size"])


def weight_bytes(cfg: Dict, bytes_per: int = 2) -> int:
    """One stream of every held parameter."""
    return bytes_per * parameter_count(cfg)
