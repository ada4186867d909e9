"""Operations and bytes of the fifth route-sequence model's scoring pass
(configuration ``falcon-h1-34b-l0-7``), from shapes alone: the NECESSARY
work, whatever implements it. Matrix-multiply FLOPs only (2 per
multiply-add), of real tokens only:

- every weight matrix of the held blocks once a token — the attention's
  four, the state-space mixer's in-projection (z, xBC, dt) and
  out-projection, the MLP's three — and the head over the held rows
  (the embedding is a lookup);
- a query head's score and value products over the ``t + 1`` keys it
  sees, and nothing for a key computed under the mask or in a block's
  padding;
- the scan's products (:func:`ssd_scan_products`): for a real token at
  place ``i`` of its chunk, ``G 2N (i + 1)`` for the scores ``C B^T``
  of its groups, ``H 2P (i + 1)`` for the decayed scores times x, and
  ``H 4NP`` for its row of ``C S^T`` and its part of the state's
  update. The convolution, the gates and the norms are no matrix
  products.

Padding and recomputation are not counted, so a share of the peak
computed from these cannot pass 100%.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

from benchmark.counts_seq import keys_seen, mlp_flops
from benchmark.reference.falcon_h1_ref import layer_indices


def ssm_shape(cfg: Dict) -> Tuple[int, int, int, int]:
    """(heads H, head width P, groups G, state N) of the scan."""
    return (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_n_groups"],
            cfg["mamba_d_state"])


def attention_weight_count(cfg: Dict) -> int:
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    wide = cfg["num_attention_heads"] * dh
    narrow = cfg["num_key_value_heads"] * dh
    return 2 * d * wide + 2 * d * narrow


def ssm_weight_count(cfg: Dict) -> int:
    """The mixer's matrices: in_proj (z, xBC, dt) and out_proj."""
    d, (heads, _, groups, n) = cfg["hidden_size"], ssm_shape(cfg)
    wide = cfg["mamba_d_ssm"]
    return d * (wide + wide + 2 * groups * n + heads) + wide * d


def block_parameter_count(cfg: Dict) -> int:
    """Every parameter of one hybrid block: matrices, the convolution's
    taps and bias, the gated norm, ``dt_bias``, ``A_log`` and ``D``, the
    two norms."""
    d, (heads, _, groups, n) = cfg["hidden_size"], ssm_shape(cfg)
    conv = cfg["mamba_d_ssm"] + 2 * groups * n
    return (attention_weight_count(cfg) + ssm_weight_count(cfg)
            + (cfg["mamba_d_conv"] + 1) * conv + cfg["mamba_d_ssm"]
            + 3 * heads + 3 * d * cfg["intermediate_size"] + 2 * d)


def parameter_count(cfg: Dict) -> int:
    """Every parameter held: the blocks, the embedding and the head over
    the held rows, the final norm."""
    d = cfg["hidden_size"]
    return (len(layer_indices(cfg)) * block_parameter_count(cfg)
            + 2 * d * cfg["vocab_size"] + d)


def attention_products(cfg: Dict, length: int) -> int:
    """Score and value products of one route in one block: every causal
    key."""
    return (2 * cfg["num_attention_heads"] * 2 * cfg["head_dim"]
            * keys_seen(length, length))


def ssd_scan_products(cfg: Dict, lengths: Sequence[int],
                      bytes_per: int = 2) -> Tuple[int, int]:
    """(FLOPs, bytes) of the scan in every held block of one pass over
    routes of these lengths: what a kernel that does the scan and
    nothing else has to do. FLOPs as the module's text counts them;
    bytes the least any chunking can move: x, B and C (``bytes_per``),
    ``dt`` (float32) in and y out once a real token, and each route's
    final state (float32) once."""
    heads, p, groups, n = ssm_shape(cfg)
    chunk = cfg["mamba_chunk_size"]
    flops = tokens = 0
    for length in (int(v) for v in lengths):
        whole, rest = divmod(length, chunk)
        # sum over real tokens of (i + 1), i the place in the chunk
        places = whole * chunk * (chunk + 1) // 2 + rest * (rest + 1) // 2
        flops += (groups * 2 * n + heads * 2 * p) * places \
            + heads * 4 * n * p * length
        tokens += length
    per_token = bytes_per * (2 * heads * p + 2 * groups * n) + 4 * heads
    layers = len(layer_indices(cfg))
    return (layers * flops,
            layers * (per_token * tokens + 4 * heads * p * n * len(lengths)))


def pass_flops(cfg: Dict, lengths: Sequence[int]) -> float:
    """One pass over routes of these lengths."""
    d = cfg["hidden_size"]
    tokens = sum(int(n) for n in lengths)
    per_token = 2 * (attention_weight_count(cfg) + ssm_weight_count(cfg)) \
        + mlp_flops(d, cfg["intermediate_size"])
    layers = len(layer_indices(cfg))
    total = float(tokens * (layers * per_token + 2 * d * cfg["vocab_size"]))
    total += layers * sum(attention_products(cfg, int(n)) for n in lengths)
    return total + ssd_scan_products(cfg, lengths)[0]


def weight_bytes(cfg: Dict, bytes_per: int = 2) -> int:
    """One stream of every held parameter."""
    return bytes_per * parameter_count(cfg)
