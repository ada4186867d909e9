"""Operations and bytes of the held experts' grouped product of a
sparse-expert route-sequence model, from the configuration's widths and
what the pass counted: what kernels
that do that product and nothing else have to do (as
``counts_window.window_attention_products`` for the sliding layers;
``counts_seq.py`` and ``counts_kexaone.py`` stay as they are)."""

from __future__ import annotations

from typing import Dict

from benchmark.counts_seq import mlp_flops


def experts_held(cfg: Dict) -> int:
    """Routed experts of a layer that this chip holds (the
    configuration's count: the published one is under ``published``)."""
    return int(cfg["n_routed_experts"] if "n_routed_experts" in cfg
               else cfg["num_experts"])


def grouped_expert_products(cfg: Dict, held_assignments: float,
                            block_steps: float,
                            bytes_per: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of the held experts' products in one pass whose
    steps ran ``block_steps`` expert blocks in all (blocks a step x
    steps: what the program counts, no model's layer list read here) and
    in which ``held_assignments`` (token, slot) choices landed on held
    experts. FLOPs as ``counts_seq.pass_flops`` and
    ``counts_kexaone.pass_flops`` count them: an assignment is charged
    its expert's three matrices once, a row of a tile's padding nothing.
    Bytes: the least any tiling can move, every held expert's three
    matrices once a step an expert block, and each held row in once (the
    compute dtype) and out once (float32)."""
    d, m = cfg["hidden_size"], cfg["moe_intermediate_size"]
    flops = held_assignments * mlp_flops(d, m)
    nbytes = (block_steps * experts_held(cfg) * 3 * d * m * bytes_per
              + held_assignments * d * (bytes_per + 4))
    return flops, nbytes
