"""Causal attention over grouped-query heads with one-part keys: every
causal key, or a short window of them.

Keys and values have G heads, queries G * Hg: query head ``h`` reads
key-value head ``h // Hg``, so the arrays are laid out by group — q (B,
L, G, Hg, d), k (B, L, G, d), v (B, L, G, dv) — and one product serves
a group's Hg query heads. The score is ``q.k * scale``; products take
the arrays' own dtype and accumulate in float32, the softmax is float32.
No mask is an array of the model's: both are made from position iotas
(:func:`causal_keys`, :func:`window_keys`).

- :func:`causal_attention`: query t sees ``s <= t``. A block of queries
  runs the grouped online softmax that the block-selecting mixer runs
  (``select._attend_group_xla``) over the chunks of keys that hold a
  key ``s <= t`` of the block; a chunk wholly in the block's future is
  not visited. The keys are padded to whole chunks once a layer, so the
  chunk does not shrink with the length's divisors.
- :func:`window_attention`: query t sees ``t - window + 1 <= s <= t``,
  ``window`` no larger than ``block``. A block of queries is scored
  against its own block of keys and the one before it, 2 x ``block``
  keys a query, several blocks a step; the first block of a route has
  an empty one before it, so a window never reaches into another row of
  the batch.

Each returns the attention output and, per query, the number of keys it
saw and the first of them, read off the masks that were applied.
:func:`causal_visited` and :func:`window_visited` say how many (query,
key) pairs the two really multiply at a padded length: what the
scorer's ``rtpu_seq_gqa_keys_total{kind=visited}`` counts.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from routest_tpu.parallel.select import _NEG, _attend_group_xla, _key_taps


def causal_keys(t_pos, s_pos):
    """(Q, K) bool: key s is at or before query t."""
    return s_pos[None, :] <= t_pos[:, None]


def window_keys(t_pos, s_pos, window: int):
    """(..., Q, K) bool from t_pos (..., Q) and s_pos (..., K): the
    ``window`` keys that end at the query; a position before the route's
    first is no key."""
    t, s = t_pos[..., :, None], s_pos[..., None, :]
    return (s <= t) & (s > t - window) & (s >= 0)


def causal_chunk(length: int, block: int, chunk: int):
    """(block of queries, chunk of keys) that :func:`causal_attention`
    steps by at this padded length."""
    block = min(block, length)
    return block, min(max(chunk, block), -(-length // block) * block)


def causal_visited(length: int, block: int, chunk: int) -> int:
    """(query, key) pairs one route of this padded length multiplies in
    one causal layer: each block of queries times the whole chunks up
    to its last key."""
    block, chunk = causal_chunk(length, block, chunk)
    return sum(block * chunk * (((i + 1) * block + chunk - 1) // chunk)
               for i in range(length // block))


def window_visited(length: int, block: int) -> int:
    """The same for one window layer: two blocks of keys a query."""
    return length * 2 * min(block, length)


def causal_attention(q, k, v, *, scale: float, block: int = 256,
                     chunk: int = 1024, scope: str = ""):
    """→ (out (B, L, G, Hg, dv) in ``v.dtype``, n_keys (B, L), first_key
    (B, L)). ``L`` must be a multiple of ``block`` (or smaller)."""
    b_sz, length, groups, per, _ = q.shape
    block, chunk = causal_chunk(length, block, chunk)
    if length % block:
        raise ValueError(f"length {length} is not a multiple of {block}")
    n_blk = length // block
    padded = -(-length // chunk) * chunk
    widen = ((0, 0), (0, padded - length), (0, 0), (0, 0))
    kp, vp = jnp.pad(k, widen), jnp.pad(v, widen)
    s_pos = jnp.arange(padded, dtype=jnp.int32)

    def one(n):
        b, i = n // n_blk, n % n_blk
        t_pos = i * block + jnp.arange(block, dtype=jnp.int32)
        qb = jax.lax.dynamic_slice_in_dim(q[b], i * block, block, 0)
        keys = causal_keys(t_pos, s_pos)
        with jax.named_scope(scope):
            out = _attend_group_xla(
                qb, kp, vp, keys[None], b,
                ((i + 1) * block + chunk - 1) // chunk, chunk=chunk,
                scale=scale)
        return (out.transpose(2, 0, 1, 3).astype(v.dtype),) + _key_taps(keys)

    out, n_keys, first = jax.lax.map(one, jnp.arange(b_sz * n_blk))
    return (out.reshape(b_sz, length, groups, per, v.shape[-1]),
            n_keys.reshape(b_sz, length), first.reshape(b_sz, length))


def window_attention(q, k, v, *, window: int, scale: float,
                     block: int = 128, rows: int = 2048, scope: str = ""):
    """→ as :func:`causal_attention`. ``rows``: queries of one step (the
    most whole blocks of them that divide the length)."""
    b_sz, length, groups, per, d = q.shape
    block = min(block, length)
    if window > block:
        raise ValueError(f"a window of {window} keys needs blocks of at "
                         f"least as many queries, not {block}")
    if length % block:
        raise ValueError(f"length {length} is not a multiple of {block}")
    n_blk = length // block
    per_step = math.gcd(n_blk, max(1, rows // block))
    n_steps = n_blk // per_step
    before = ((0, 0), (block, 0), (0, 0), (0, 0))
    kp, vp = jnp.pad(k, before), jnp.pad(v, before)
    offs = jnp.arange(per_step, dtype=jnp.int32)[:, None] * block

    def spans(x, t0):
        """(blocks, 2 * block, G, d): each block's keys after those of
        the block before it."""
        x = jax.lax.dynamic_slice_in_dim(x, t0, (per_step + 1) * block, 0)
        x = x.reshape((per_step + 1, block) + x.shape[1:])
        return jnp.concatenate([x[:-1], x[1:]], 1)

    def one(n):
        b, t0 = n // n_steps, (n % n_steps) * per_step * block
        qb = jax.lax.dynamic_slice_in_dim(q[b], t0, per_step * block, 0)
        qb = qb.reshape(per_step, block, groups, per, d)
        t_pos = t0 + offs + jnp.arange(block, dtype=jnp.int32)[None, :]
        s_pos = (t0 - block) + offs + jnp.arange(2 * block,
                                                 dtype=jnp.int32)[None, :]
        keys = window_keys(t_pos, s_pos, window)       # (n, block, 2 block)
        with jax.named_scope(scope):
            s = jnp.einsum("nqghd,nkgd->nghqk", qb, spans(kp[b], t0),
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(keys[:, None, None], s, _NEG)
            p = jnp.exp(s - s.max(-1, keepdims=True))
            p = p / p.sum(-1, keepdims=True)
            out = jnp.einsum("nghqk,nkgd->nqghd", p.astype(v.dtype),
                             spans(vp[b], t0),
                             preferred_element_type=jnp.float32)
        n_keys, at = _key_taps(keys)
        first = jnp.take_along_axis(s_pos, at, axis=-1)
        return (out.astype(v.dtype).reshape(per_step * block, groups, per,
                                            -1),
                n_keys.reshape(-1), first.reshape(-1))

    out, n_keys, first = jax.lax.map(one, jnp.arange(b_sz * n_steps))
    return (out.reshape(b_sz, length, groups, per, v.shape[-1]),
            n_keys.reshape(b_sz, length), first.reshape(b_sz, length))
