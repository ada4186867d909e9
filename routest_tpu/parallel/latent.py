"""Dense causal attention over two-part keys: every head its own
``d_nope``-wide key part, one rotary part shared by the heads, a value
of a width of its own (latent attention in its expanded form, with no
selector, no window and no gate: query t sees every key ``s <= t``).

The score is ``(q . k + q_shared . k_shared) * scale``; products take
the arrays' own dtype and accumulate in float32, the softmax is float32.
A block of queries runs the two-part online softmax of the selecting
layers (``parallel/select.py``) under a mask that is causal and nothing
else, over the chunks of keys that hold a key ``s <= t`` of the block; a
chunk wholly in the block's future is not visited. Two forms of that
step, chosen by :func:`latent_path` from shapes, dtype and backend
alone: ``fused``, ``select._attend_fused``'s kernel (a device trace
calls it ``latent_attention_step`` here), whose float32 score tile
never leaves VMEM, and ``xla``, ``select._attend_xla`` (the CPU,
float32, toy widths; the kernel's oracle). The two key parts are never
joined into one array, and the queries are made a block at a time
(``q_fn``), so that a layer holds its keys and values once and no
(L, H, d_nope + d_rope) array of either side.

The keys and values come padded to whole chunks, :func:`padded_keys`
rows of them: the caller pads their narrow latents before it expands
them, which costs no copy of the expanded arrays; a padded key lies
after every query, so the causal mask is all that hides it.
``gqa.causal_visited`` says how many (query, key) pairs are really
multiplied at a padded length: the blocks and chunks are its.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from routest_tpu.parallel.gqa import causal_chunk, causal_keys
from routest_tpu.parallel.select import (HEAD_TILE, _attend_fused,
                                         _attend_xla, _key_taps,
                                         key_tile_for)

KERNEL = "latent_attention_step"


def padded_keys(length: int, block: int, chunk: int) -> int:
    """Rows of keys and values :func:`causal_attention` reads for routes
    padded to ``length``: whole chunks."""
    _, chunk = causal_chunk(length, block, chunk)
    return -(-length // chunk) * chunk


def latent_path(heads: int, length: int, block: int, chunk: int, d: int,
                d_shared: int, d_v: int, dtype, backend: str = "") -> str:
    """The step :func:`causal_attention` runs at these shapes:
    ``"fused"`` on a TPU where bfloat16 arrays tile for the kernel (as
    ``select.attention_path``, but a value need not be whole lanes wide:
    its block is the whole width; 128 and 192 are what has run),
    ``"xla"`` everywhere else. ``backend`` defaults to JAX's own."""
    block, chunk = causal_chunk(length, block, chunk)
    tiles = (heads % HEAD_TILE == 0 and block % 32 == 0
             and key_tile_for(chunk) > 0 and d % 128 == 0 and d_v % 64 == 0
             and d_shared % 64 == 0)
    on_tpu = (backend or jax.default_backend()) == "tpu"
    return ("fused" if on_tpu and tiles and jnp.dtype(dtype) == jnp.bfloat16
            else "xla")


def causal_attention(q_fn: Callable, k, k_shared, v, *, length: int,
                     scale: float, block: int = 256, chunk: int = 1024,
                     scope: str = ""):
    """``q_fn(b, t0)`` → (q (block, H, d), q_shared (block, H, dr)) of
    the queries ``t0 .. t0 + block - 1`` of route b; k (B, P, H, d),
    k_shared (B, P, dr), v (B, P, H, dv) with P = :func:`padded_keys`.
    → (out (B, L, H, dv) in ``v.dtype``, n_keys (B, L), first_key (B,
    L)). ``length`` must be a multiple of ``block`` (or smaller)."""
    b_sz, padded, heads = k.shape[:3]
    path = latent_path(heads, length, block, chunk, k.shape[-1],
                       k_shared.shape[-1], v.shape[-1], k.dtype)
    block, chunk = causal_chunk(length, block, chunk)
    if length % block or padded != padded_keys(length, block, chunk):
        raise ValueError(f"length {length} in blocks of {block} over "
                         f"{padded} keys in chunks of {chunk}")
    n_blk = length // block
    s_pos = jnp.arange(padded, dtype=jnp.int32)
    if path == "fused":     # by head, once a layer: what the kernel tiles
        k_heads, v_heads = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        tile = key_tile_for(chunk)

    def one(n):
        b, i = n // n_blk, n % n_blk
        t_pos = i * block + jnp.arange(block, dtype=jnp.int32)
        keys = causal_keys(t_pos, s_pos)
        q, q_shared = q_fn(b, i * block)
        with jax.named_scope(scope):
            if path == "fused":
                out = _attend_fused(
                    q, q_shared, k_heads, k_shared, v_heads, keys, b,
                    ((i + 1) * block + tile - 1) // tile, scale=scale,
                    key_tile=tile, name=KERNEL)
            else:
                out = _attend_xla(
                    q, q_shared, k, k_shared, v, keys, b,
                    ((i + 1) * block + chunk - 1) // chunk, chunk=chunk,
                    scale=scale)
        return (out.transpose(1, 0, 2).astype(v.dtype),) + _key_taps(keys)

    out, n_keys, first = jax.lax.map(one, jnp.arange(b_sz * n_blk))
    return (out.reshape((b_sz, length) + v.shape[2:]),
            n_keys.reshape(b_sz, length), first.reshape(b_sz, length))
