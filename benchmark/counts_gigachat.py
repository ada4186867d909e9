"""Operations and bytes of the fourth route-sequence model's scoring
pass (configuration ``gigachat3.1-702b-ep16``), from shapes alone: the
NECESSARY work, whatever implements it. Matrix-multiply FLOPs only (2
per multiply-add), of real tokens only:

- every weight matrix of the held layers once a token — latent
  attention's five, the dense MLP's three or the router (over ALL
  published experts) and the shared expert — and the head (the
  embedding is a lookup);
- a query head's score and value products over the ``t + 1`` keys it
  sees, in the expanded form: ``2 * (qk_nope_head_dim + qk_rope_head_dim
  + v_head_dim)`` a head and (query, key) pair (768 at the published
  widths; the absorbed form's 2,176 is no necessary work where nothing
  is cached), and nothing for a key computed under the mask or in a
  block's padding;
- the held experts' three matrices once an assignment that landed on a
  held expert (``held_assignments``, which the program reports and the
  reference confirms) and nothing for the others;
- the prediction module over a route's ``n - 1`` positions: its
  projection, one block of the trunk's expert kind, the head again.

Padding and recomputation are not counted, so a share of the peak
computed from these cannot pass 100%.
"""

from __future__ import annotations

from typing import Dict, Sequence

from benchmark.counts_seq import keys_seen, mlp_flops
from benchmark.reference.gigachat_ref import layer_kinds


def attention_weight_count(cfg: Dict) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r_q, r_kv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (d * r_q + r_q * h * (dn + dr) + d * (r_kv + dr)
            + r_kv * h * (dn + dv) + h * dv * d)


def ffn_weight_count(cfg: Dict, kind: str, experts: int) -> int:
    """The matrices of one feed-forward block with ``experts`` routed
    experts beside the router and the shared one."""
    d = cfg["hidden_size"]
    if kind == "dense":
        return 3 * d * cfg["intermediate_size"]
    m = cfg["moe_intermediate_size"]
    return (d * cfg["published"]["n_routed_experts"]
            + 3 * d * m * (experts + cfg["n_shared_experts"]))


def has_module(cfg: Dict) -> bool:
    return (cfg["num_nextn_predict_layers"] > 0
            and cfg.get("share", {}).get("mtp_held", True))


def parameter_count(cfg: Dict) -> int:
    """Every parameter held: matrices, norm vectors, the router's bias,
    embedding, head, the module."""
    d = cfg["hidden_size"]
    block = (attention_weight_count(cfg) + cfg["q_lora_rank"]
             + cfg["kv_lora_rank"] + 2 * d)
    sparse = (block + ffn_weight_count(cfg, "sparse", cfg["n_routed_experts"])
              + cfg["published"]["n_routed_experts"])
    n = 2 * d * cfg["vocab_size"] + d
    for ffn in layer_kinds(cfg):
        n += sparse if ffn == "sparse" else block + ffn_weight_count(
            cfg, ffn, 0)
    if has_module(cfg):
        n += sparse + 2 * d * d + 3 * d
    return n


def attention_products(cfg: Dict, length: int) -> int:
    """Score and value products of one route in one block: every causal
    key, the expanded form."""
    per_pair = 2 * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                    + cfg["v_head_dim"])
    return cfg["num_attention_heads"] * per_pair * keys_seen(length, length)


def latent_attention_products(cfg: Dict, lengths: Sequence[int],
                              bytes_per: int = 2) -> tuple[int, int]:
    """(FLOPs, bytes) of the score and value products of every dense
    causal block of one pass over routes of these lengths, the module's
    block over a route's n - 1 positions: what a kernel that does that
    step and nothing else has to do. FLOPs as :func:`pass_flops` counts
    them: a query is charged the ``t + 1`` keys it may SEE, not the keys
    of the tiles a mask multiplies. Bytes: the least any blocking of the
    queries can move, a block's queries, keys (the rotary part once a
    key, not once a head), values and outputs of every real token
    once."""
    h, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    per_token = h * (dn + dr) + h * dn + dr + 2 * h * dv
    flops = tokens = 0
    # a trunk block sees a route's n tokens, the module's block n - 1
    for short in [0] * len(layer_kinds(cfg)) + [1] * has_module(cfg):
        seen = [max(int(n) - short, 0) for n in lengths]
        flops += sum(attention_products(cfg, n) for n in seen)
        tokens += sum(seen)
    return flops, bytes_per * per_token * tokens


def pass_flops(cfg: Dict, lengths: Sequence[int],
               held_assignments: float) -> float:
    """One pass over routes of these lengths. ``held_assignments``: the
    (token, expert) assignments that landed on held experts, summed
    over the expert blocks, the module's among them."""
    d = cfg["hidden_size"]
    head = 2 * d * cfg["vocab_size"]
    tokens = sum(int(n) for n in lengths)
    total = float(tokens * head)
    for ffn in layer_kinds(cfg):
        total += 2 * tokens * (attention_weight_count(cfg)
                               + ffn_weight_count(cfg, ffn, 0))
        total += sum(attention_products(cfg, int(n)) for n in lengths)
    if has_module(cfg):
        positions = sum(max(int(n) - 1, 0) for n in lengths)
        total += positions * (2 * (2 * d * d + attention_weight_count(cfg)
                                   + ffn_weight_count(cfg, "sparse", 0))
                              + head)
        total += sum(attention_products(cfg, max(int(n) - 1, 0))
                     for n in lengths)
    return total + held_assignments * mlp_flops(
        d, cfg["moe_intermediate_size"])


def weight_bytes(cfg: Dict, bytes_per: int = 2) -> int:
    """One stream of every held parameter."""
    return bytes_per * parameter_count(cfg)
