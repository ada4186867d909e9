"""Operations and bytes of the second route-sequence model's scoring
pass (configuration ``minicpm-sala-l9-16``), from shapes alone: the
NECESSARY work, whatever implements it. Matrix-multiply FLOPs only (2
per multiply-add), of real tokens only:

- every weight matrix of the held layers and the head once a token (the
  embedding is a lookup);
- the sparse mixer's first stage: a query head against the compressed
  keys visible to it (``visible_compressed``), nothing for the others;
- its second stage: a query head's score and value products over the
  keys of its chosen blocks at or before the query — ``chosen_keys``,
  the sum over (sparse layer, real token, key-value head) that the
  program reports and the reference confirms — and nothing for a key
  computed under a mask;
- the linear mixer as a chunked scan at ``LINEAR_CHUNK`` tokens a chunk
  (the chunk this count states; a scan at another chunk does other
  work): per chunk and head the score and value products of the
  C (C + 1) / 2 causal pairs inside the chunk (nothing for the pairs
  under the causal mask), ``q S`` (C x d x d) and ``k^T v`` (d x C x d),
  a last partial chunk at its real tokens.

Padding and recomputation are not counted, so a share of the peak
computed from these cannot pass 100%.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from benchmark.reference.sala_ref import LINEAR, SPARSE
from benchmark.reference.sala_ref import layer_kinds as _held_layers

LINEAR_CHUNK = 256


def layer_kinds(cfg: Dict) -> List[str]:
    """The mixer kind of each layer that is held."""
    return [kind for kind, _ in _held_layers(cfg)]


def mixer_weight_count(cfg: Dict, kind: str) -> int:
    d = cfg["hidden_size"]
    if kind == SPARSE:
        wide = cfg["num_attention_heads"] * cfg["head_dim"]
        narrow = cfg["num_key_value_heads"] * cfg["head_dim"]
        return d * wide + 2 * d * narrow + d * wide + wide * d
    wide = cfg["lightning_nh"] * cfg["lightning_head_dim"]
    return 3 * d * wide + d * wide + wide * d


def layer_weight_count(cfg: Dict, kind: str) -> int:
    """The matrices of one layer (the norms' vectors are not products)."""
    return (mixer_weight_count(cfg, kind)
            + 3 * cfg["hidden_size"] * cfg["intermediate_size"])


def parameter_count(cfg: Dict) -> int:
    """Every parameter held: matrices, norm vectors, embedding, head."""
    d, n = cfg["hidden_size"], 0
    for kind in layer_kinds(cfg):
        n += layer_weight_count(cfg, kind) + 2 * d
        if kind == SPARSE:
            n += 2 * cfg["head_dim"]
        else:
            n += (2 * cfg["lightning_head_dim"]
                  + cfg["lightning_nh"] * cfg["lightning_head_dim"])
    return n + 2 * d * cfg["vocab_size"] + d


def visible_compressed(cfg: Dict, length: int) -> int:
    """sum over t < length of the compressed keys visible to query t."""
    sp = cfg["sparse"]
    size, stride = sp["kernel_size"], sp["kernel_stride"]
    t = np.arange(length, dtype=np.int64)
    return int(np.maximum(0, (t - (size - 1)) // stride + 1).sum())


def linear_flops(cfg: Dict, length: int, chunk: int = LINEAR_CHUNK) -> int:
    heads, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
    total = 0
    for c0 in range(0, length, chunk):
        e = min(chunk, length - c0)
        total += 2 * heads * (e * (e + 1) * d + 2 * e * d * d)
    return total


def pass_flops(cfg: Dict, lengths: Sequence[int], chosen_keys: float) -> float:
    """One pass over routes of these lengths. ``chosen_keys``: the keys
    in chosen blocks at or before the query, summed over the sparse
    layers, the real tokens and the key-value heads."""
    tokens = sum(int(n) for n in lengths)
    kinds = layer_kinds(cfg)
    dh = cfg["head_dim"]
    per_group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    total = 2.0 * tokens * (sum(layer_weight_count(cfg, k) for k in kinds)
                            + cfg["hidden_size"] * cfg["vocab_size"])
    total += 2.0 * per_group * 2 * dh * chosen_keys
    n_sparse = sum(1 for k in kinds if k == SPARSE)
    dense_len = cfg["sparse"]["dense_len"]
    total += n_sparse * sum(
        2.0 * cfg["num_attention_heads"] * dh
        * visible_compressed(cfg, int(n))
        for n in lengths if int(n) >= dense_len)
    total += sum(1 for k in kinds if k == LINEAR) * sum(
        linear_flops(cfg, int(n)) for n in lengths)
    return total


def weight_bytes(cfg: Dict, bytes_per: int = 2) -> int:
    """One stream of every held parameter."""
    return bytes_per * parameter_count(cfg)
