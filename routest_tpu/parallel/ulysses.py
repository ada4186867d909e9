"""Ulysses-style sequence parallelism: all-to-all seq↔head re-sharding.

The alternative long-context strategy to the ring (DeepSpeed-Ulysses
pattern): instead of rotating K/V blocks, one ``lax.all_to_all`` converts
the sequence sharding into a head sharding — every device then attends
over the whole sequence for its slice of heads (streamed blockwise, so
the per-device score residency is O(S·chunk) per resident head, not
O(S²)), and a second all-to-all restores the sequence sharding.
Collective count is constant in mesh size — four all_to_alls (q, k, v,
out) plus an all_gather of the key mask when one is supplied — vs the
ring's ``n-1`` hops of three ppermutes each; the trade is requiring
``n_heads % axis_size == 0`` and holding full-sequence K/V (not score)
activations per device.

Ring keeps even K/V residency at O(S/n) and overlaps its hops; Ulysses
wins at moderate S where collective count dominates. Both are exposed
so a sequence model can pick per workload (which wins where is not
measured on the chip).
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh

from routest_tpu.parallel.ring import (blockwise_attention, full_attention,
                                       sharded_attention)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      *, axis_name: str, axis_size: int,
                      key_mask: Optional[jax.Array] = None,
                      causal: bool = False) -> jax.Array:
    """Per-device program: (B, S_local, H, D) in, same shape out.

    Call inside shard_map with the sequence axis sharded over
    ``axis_name``. Requires H % axis_size == 0.
    """
    if axis_size == 1:
        return full_attention(q, k, v, key_mask, causal)
    if q.shape[2] % axis_size:
        raise ValueError(
            f"n_heads={q.shape[2]} not divisible by axis_size={axis_size}")

    def seq_to_heads(x):  # (B, S/n, H, D) → (B, S, H/n, D)
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def heads_to_seq(x):  # (B, S, H/n, D) → (B, S/n, H, D)
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    q_h, k_h, v_h = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    full_mask = None
    if key_mask is not None:
        full_mask = jax.lax.all_gather(key_mask, axis_name, axis=1, tiled=True)
    # Blockwise (flash-style) per head shard: long sequences would
    # otherwise materialize the whole (S, S) score matrix per device —
    # the ceiling the ring never had. Short sequences take the exact
    # full_attention early-out inside.
    out = blockwise_attention(q_h, k_h, v_h, full_mask, causal)
    return heads_to_seq(out)


def ulysses_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array,
                              mesh: Mesh, seq_axis: str = "seq",
                              data_axis: Optional[str] = None,
                              key_mask: Optional[jax.Array] = None,
                              causal: bool = False) -> jax.Array:
    """Convenience wrapper over full (B, S, H, D) arrays (cf. ring)."""
    return sharded_attention(ulysses_attention, q, k, v, mesh, seq_axis,
                             data_axis, key_mask, causal)
