"""Ring attention: exact attention over a mesh-sharded sequence axis.

Each device holds one block of the sequence. K/V blocks travel around the
ICI ring (``lax.ppermute``) while every device accumulates attention for
its resident queries with an online softmax — the running max/denominator
rescaling that makes blockwise attention exact, not approximate. After
``axis_size`` hops every query has seen every key, yet no device ever
materialized more than a (local_q × local_k) score tile: O(S²) compute,
O(S²/n²) memory per step, O(S/n) activation residency.

The reference has nothing like this (no attention, no collectives —
SURVEY.md §5.7/§5.8); this is the TPU-native scaling path for
long-route sequence models built on this package.

Layouts: q/k/v are (B, S, H, D); masks are (B, S) with 1.0 = real token.
``ring_attention`` is the per-device program (call it inside shard_map
with the sequence axis sharded); ``ring_attention_sharded`` wraps it for
callers holding unsharded arrays.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from routest_tpu.core.smap import shard_map

_NEG = -1e30  # finite "minus infinity": keeps exp() NaN-free on all-masked tiles
DEFAULT_CHUNK = 1024  # blockwise K/V streaming granularity (bench imports it)


def full_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   key_mask: Optional[jax.Array] = None,
                   causal: bool = False,
                   scale: Optional[float] = None) -> jax.Array:
    """Single-device reference: (B, S, H, D) → (B, S, H, D).

    The oracle ring/Ulysses must match bit-for-bit in f32 (up to summation
    order); also the fallback when the mesh has one device on the axis.
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    mask = jnp.ones(s.shape[-1], bool)[None, None, None, :]
    if key_mask is not None:
        mask = mask & (key_mask[:, None, None, :] > 0)
    if causal:
        q_pos = jnp.arange(q.shape[1])[:, None]
        k_pos = jnp.arange(k.shape[1])[None, :]
        mask = mask & (q_pos >= k_pos)[None, None]
    s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1) * mask
    denom = jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    p = p / denom * jnp.clip(mask.sum(-1, keepdims=True), 0, 1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        key_mask: Optional[jax.Array] = None,
                        causal: bool = False,
                        scale: Optional[float] = None,
                        chunk: int = DEFAULT_CHUNK) -> jax.Array:
    """Exact attention that never materializes the (S, S) score matrix:
    K/V stream through in ``chunk``-sized blocks under the same online
    softmax the ring uses — a single-device flash-style loop. Peak score
    memory is (B, H, S, chunk) instead of (B, H, S, S), so one device's
    sequence ceiling is set by bandwidth, not by the score tensor; the
    ring/Ulysses collectives then multiply ceiling AND compute across
    chips. (Blockwise composes with Ulysses: each head-shard can stream
    its full-row scores chunk-by-chunk.)

    The scan body is ``jax.checkpoint``-ed: without it, backprop would
    stash every chunk's (B, H, S, chunk) score/prob tensors as
    residuals — O(S²) total, the very tensor this function exists to
    avoid. Rematerialization recomputes each tile in the backward pass,
    keeping TRAINING memory at the same O(S·chunk) bound as inference
    (grad parity is tested against the full oracle).

    Under ``causal=True`` chunks wholly in a query's future still pay
    their QK einsum here before masking to zero (~2× FLOPs at large S):
    this function's consumers are non-causal route encoders. The causal
    consumer, the route-sequence language model, runs
    ``parallel/select.py``, whose blocks of queries visit only the
    chunks of keys at or before them (and, under a window, only the
    blocks the window touches).

    Same layouts and mask/causal semantics as :func:`full_attention`
    (the parity oracle)."""
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    if s_k <= chunk:
        return full_attention(q, k, v, key_mask, causal, scale)
    scale = scale if scale is not None else d ** -0.5
    n_chunks = (s_k + chunk - 1) // chunk
    pad = n_chunks * chunk - s_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # Padded keys are masked off; an absent mask gains one that covers
    # only the padding.
    km = (jnp.ones((b, s_k), q.dtype) if key_mask is None
          else key_mask.astype(q.dtype))
    if pad:
        km = jnp.pad(km, ((0, 0), (0, pad)))
    k_blocks = k.reshape(b, n_chunks, chunk, h, d).transpose(1, 0, 2, 3, 4)
    v_blocks = v.reshape(b, n_chunks, chunk, h, d).transpose(1, 0, 2, 3, 4)
    m_blocks = km.reshape(b, n_chunks, chunk).transpose(1, 0, 2)
    q_pos = jnp.arange(s_q)

    def body(carry, blk):
        acc, m, denom, start = carry
        k_blk, v_blk, km_blk = blk
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk,
                       preferred_element_type=jnp.float32) * scale
        tile_mask = km_blk[:, None, None, :] > 0
        if causal:
            k_pos = start + jnp.arange(chunk)
            tile_mask = tile_mask & (q_pos[:, None] >= k_pos[None, :])[None, None]
        s = jnp.where(tile_mask, s, _NEG)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None]) * tile_mask
        correction = jnp.exp(m - m_new)
        denom = denom * correction + p.sum(-1)
        acc = acc * correction[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32))
        return (acc, m_new, denom, start + chunk), None

    acc0 = jnp.zeros((b, h, s_q, d), jnp.float32)
    m0 = jnp.full((b, h, s_q), _NEG, jnp.float32)
    den0 = jnp.zeros((b, h, s_q), jnp.float32)
    (acc, _, denom, _), _ = jax.lax.scan(
        jax.checkpoint(body), (acc0, m0, den0, jnp.zeros((), jnp.int32)),
        (k_blocks, v_blocks, m_blocks))
    out = acc / jnp.maximum(denom, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   *, axis_name: str, axis_size: int,
                   key_mask: Optional[jax.Array] = None,
                   causal: bool = False,
                   scale: Optional[float] = None) -> jax.Array:
    """Per-device ring attention. Call inside shard_map.

    q/k/v: (B, S_local, H, D) — this device's sequence block.
    key_mask: (B, S_local) for the local key block (rotates with K/V).
    Returns (B, S_local, H, D) for the resident queries.
    """
    if axis_size == 1:
        return full_attention(q, k, v, key_mask, causal, scale)

    scale = scale if scale is not None else q.shape[-1] ** -0.5
    b, s_q, h, _ = q.shape
    s_k = k.shape[1]
    my = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    kmask = None if key_mask is None else key_mask.astype(q.dtype)
    q_pos = my * s_q + jnp.arange(s_q)

    acc = jnp.zeros((b, h, s_q, q.shape[-1]), jnp.float32)
    m = jnp.full((b, h, s_q), _NEG, jnp.float32)
    denom = jnp.zeros((b, h, s_q), jnp.float32)

    def tile_update(acc, m, denom, k_blk, v_blk, km, step):
        # after `step` clockwise hops we hold the block born on device my-step
        src = (my - step) % axis_size
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk,
                       preferred_element_type=jnp.float32) * scale
        tile_mask = None if km is None else km[:, None, None, :] > 0
        if causal:
            k_pos = src * s_k + jnp.arange(s_k)
            cmask = (q_pos[:, None] >= k_pos[None, :])[None, None]
            tile_mask = cmask if tile_mask is None else tile_mask & cmask
        if tile_mask is not None:
            s = jnp.where(tile_mask, s, _NEG)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        if tile_mask is not None:
            # explicit mask multiply: on an all-masked tile exp(NEG-NEG)=1
            # would otherwise inject phantom probability mass
            p = p * tile_mask
        correction = jnp.exp(m - m_new)
        denom = denom * correction + p.sum(-1)
        acc = acc * correction[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32))
        return acc, m_new, denom

    rotate = functools.partial(jax.lax.ppermute, axis_name=axis_name,
                               perm=perm)

    # resident block first, then axis_size-1 rotate+compute hops — no
    # final dead rotation riding the ICI; the mask block only travels
    # the ring when a mask exists at all
    if kmask is None:
        def hop(carry, step):
            k_blk, v_blk, acc, m, denom = carry
            k_blk, v_blk = rotate(k_blk), rotate(v_blk)
            acc, m, denom = tile_update(acc, m, denom, k_blk, v_blk, None, step)
            return (k_blk, v_blk, acc, m, denom), None

        acc, m, denom = tile_update(acc, m, denom, k, v, None, 0)
        (_, _, acc, _, denom), _ = jax.lax.scan(
            hop, (k, v, acc, m, denom), jnp.arange(1, axis_size))
    else:
        def hop(carry, step):
            k_blk, v_blk, km, acc, m, denom = carry
            k_blk, v_blk, km = rotate(k_blk), rotate(v_blk), rotate(km)
            acc, m, denom = tile_update(acc, m, denom, k_blk, v_blk, km, step)
            return (k_blk, v_blk, km, acc, m, denom), None

        acc, m, denom = tile_update(acc, m, denom, k, v, kmask, 0)
        (_, _, _, acc, _, denom), _ = jax.lax.scan(
            hop, (k, v, kmask, acc, m, denom), jnp.arange(1, axis_size))
    out = acc / jnp.maximum(denom, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def sharded_attention(attn_fn, q: jax.Array, k: jax.Array, v: jax.Array,
                      mesh: Mesh, seq_axis: str,
                      data_axis: Optional[str],
                      key_mask: Optional[jax.Array],
                      causal: bool) -> jax.Array:
    """Shared shard_map wrapper for the per-device attention programs.

    Builds the spec/arg tuples conditionally so a masked call adds the
    mask input while an unmasked one omits it entirely — letting the
    per-device program (which receives ``key_mask=None``) skip its mask
    collectives and per-tile compare/multiply.
    """
    axis_size = mesh.shape[seq_axis]
    qkv_spec = P(data_axis, seq_axis, None, None)
    specs = (qkv_spec, qkv_spec, qkv_spec)
    args = (q, k, v)
    if key_mask is not None:
        specs += (P(data_axis, seq_axis),)
        args += (key_mask,)

    @functools.partial(shard_map, mesh=mesh, in_specs=specs,
                       out_specs=qkv_spec)
    def run(q, k, v, *maybe_mask):
        return attn_fn(q, k, v, axis_name=seq_axis, axis_size=axis_size,
                       key_mask=maybe_mask[0] if maybe_mask else None,
                       causal=causal)

    return run(*args)


def ring_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array,
                           mesh: Mesh, seq_axis: str = "seq",
                           data_axis: Optional[str] = None,
                           key_mask: Optional[jax.Array] = None,
                           causal: bool = False) -> jax.Array:
    """Shard the sequence axis of full (B, S, H, D) arrays and run the ring.

    The mesh's ``seq_axis`` size must divide S; batch optionally shards
    over ``data_axis``. This is the convenience wrapper — models compose
    :func:`ring_attention` directly inside their own shard_map programs.
    """
    return sharded_attention(ring_attention, q, k, v, mesh, seq_axis,
                             data_axis, key_mask, causal)
