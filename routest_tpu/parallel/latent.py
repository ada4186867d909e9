"""Dense causal attention over two-part keys: every head its own
``d_nope``-wide key part, one rotary part shared by the heads, a value
of a width of its own (latent attention in its expanded form, with no
selector, no window and no gate: query t sees every key ``s <= t``).

The score is ``(q . k + q_shared . k_shared) * scale``; products take
the arrays' own dtype and accumulate in float32, the softmax is float32,
probabilities are cast to ``v.dtype`` for the value product, a masked
key adds exactly zero mass. The arrays come laid out by head, as the
layer's expansions write them: queries (B, H, L, d) and (B, H, L, dr),
keys (B, H, P, d) and (B, P, dr), values with the length LAST (B, H,
dv, P). The two key parts are never joined into one array.

**What is multiplied** (:func:`causal_grid`): a block of ``block``
queries against the tiles of ``tile`` keys that hold a key ``s <= t`` of
the block; the last of them, the block's DIAGONAL tile, only up to the
block's own last key, in whole blocks of keys, and only the last of
those, the one the diagonal crosses, is masked. Nothing past a block's
diagonal is visited. The scorer's counters read the same tables:
:func:`visited` pairs, :func:`grid_steps` by kind.

Two forms of the step, chosen by :func:`latent_path` from shapes, dtype
and backend alone:

- ``"fused"``: ONE Pallas kernel a layer and step (:func:`_causal_fused`,
  ``latent_attention_step`` in a device trace), a grid over groups of
  ``HEAD_TILE`` heads x the causal triangle's (route, block, tile)
  triples, flattened into prefetched tables. The score tile is KEY-MAJOR,
  (keys, queries): the running max and sum are rows across the
  queries' lanes, the 192-wide value streams through the MXU against
  the probabilities as ``v^T p^T``, and the mask is an iota triangle,
  applied to the diagonal block of keys alone: an interior tile does no
  mask work.
- ``"xla"``: ``select._attend_xla`` over the same keys, in chunks of
  ``block`` (the CPU, float32, toy widths; the kernel's oracle).

The keys and values come padded to whole tiles, :func:`padded_keys`
rows of them: the caller pads their narrow latents before it expands
them, which costs no copy of the expanded arrays; a padded key lies
after every query and is never visited.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from routest_tpu.parallel.gqa import causal_chunk, causal_keys
from routest_tpu.parallel.select import (_NEG, _VMEM_BYTES, HEAD_TILE,
                                         _attend_xla, _key_taps)

KERNEL = "latent_attention_step"


def padded_keys(length: int, block: int, chunk: int) -> int:
    """Rows of keys and values :func:`causal_attention` reads for routes
    padded to ``length``: whole tiles."""
    _, chunk = causal_chunk(length, block, chunk)
    return -(-length // chunk) * chunk


def causal_grid(routes: int, length: int, block: int, chunk: int):
    """The causal triangle as the kernel walks it: (route, block of
    queries, tile of keys) int32 columns, a row a grid step of one group
    of heads, the tiles of a block in order and its diagonal tile last.
    Block i multiplies the keys ``0 .. (i + 1) * block - 1``: whole
    tiles, then of its diagonal tile the blocks of keys up to its own."""
    block, tile = causal_chunk(length, block, chunk)
    rows = [(b, i, j) for b in range(routes) for i in range(length // block)
            for j in range(-(-(i + 1) * block // tile))]
    return np.asarray(rows, np.int32).reshape(-1, 3)


def visited(length: int, block: int, chunk: int) -> int:
    """(query, key) pairs one route of this padded length multiplies in
    one dense causal block, both forms: what :func:`causal_grid`'s steps
    multiply, each block of queries times the keys up to its own."""
    block, tile = causal_chunk(length, block, chunk)
    grid = causal_grid(1, length, block, chunk)
    return int(block * np.minimum(
        tile, (grid[:, 1] + 1) * block - grid[:, 2] * tile).sum())


def grid_steps(routes: int, length: int, block: int, chunk: int,
               heads: int) -> dict:
    """The kernel's grid steps in one dense causal block of a step:
    ``interior`` (whole tiles, no mask) and ``diagonal`` (one a block of
    queries and route), each times the groups of heads."""
    grid = causal_grid(routes, length, block, chunk)
    block, _ = causal_chunk(length, block, chunk)
    diagonal = routes * (length // block)
    groups = max(heads // HEAD_TILE, 1)
    return {"interior": groups * (len(grid) - diagonal),
            "diagonal": groups * diagonal}


def latent_path(heads: int, length: int, block: int, chunk: int, d: int,
                d_shared: int, d_v: int, dtype, backend: str = "") -> str:
    """The step :func:`causal_attention` runs at these shapes:
    ``"fused"`` on a TPU where bfloat16 arrays tile for the kernel
    (heads a multiple of ``HEAD_TILE``, the block whole lanes of
    queries, the tile whole blocks, the key widths whole lanes or the
    shared 64, the value whole sublane groups: 128 and 192 are what has
    run), ``"xla"`` everywhere else. ``backend`` defaults to JAX's
    own."""
    block, chunk = causal_chunk(length, block, chunk)
    tiles = (heads % HEAD_TILE == 0 and block % 128 == 0
             and chunk % block == 0 and d % 128 == 0 and d_v % 64 == 0
             and d_shared % 64 == 0)
    on_tpu = (backend or jax.default_backend()) == "tpu"
    return ("fused" if on_tpu and tiles and jnp.dtype(dtype) == jnp.bfloat16
            else "xla")


def causal_attention(q, q_shared, k, k_shared, v, *, length: int,
                     scale: float, block: int = 256, chunk: int = 1024,
                     scope: str = ""):
    """q (B, H, L, d), q_shared (B, H, L, dr); k (B, H, P, d), k_shared
    (B, P, dr), v (B, H, dv, P) with P = :func:`padded_keys` → (out (B,
    L, H, dv) in ``v.dtype``, n_keys (B, L), first_key (B, L)).
    ``length`` must be a multiple of ``block`` (or smaller)."""
    b_sz, heads, padded = k.shape[:3]
    path = latent_path(heads, length, block, chunk, k.shape[-1],
                       k_shared.shape[-1], v.shape[2], k.dtype)
    block, chunk = causal_chunk(length, block, chunk)
    if length % block or padded != padded_keys(length, block, chunk):
        raise ValueError(f"length {length} in blocks of {block} over "
                         f"{padded} keys in chunks of {chunk}")
    # what each query saw, through the one expression of the mask
    n_keys, first = _key_taps(causal_keys(
        jnp.arange(length, dtype=jnp.int32),
        jnp.arange(padded, dtype=jnp.int32)))
    with jax.named_scope(scope):
        if path == "fused":
            out = _causal_fused(q, q_shared, k, k_shared, v, block=block,
                                tile=chunk, scale=scale).transpose(0, 3, 1, 2)
        else:
            out = _causal_xla(q, q_shared, k, k_shared, v, block=block,
                              scale=scale)
    taps = (b_sz, length)
    return (out.astype(v.dtype), jnp.broadcast_to(n_keys, taps),
            jnp.broadcast_to(first, taps))


def _causal_xla(q, q_shared, k, k_shared, v, *, block: int, scale: float):
    """``select._attend_xla`` a block of queries over its keys in chunks
    of ``block``: the pairs the kernel multiplies → (B, L, H, dv)."""
    b_sz, _, length, _ = q.shape
    n_blk = length // block
    k_rows, v_rows = k.transpose(0, 2, 1, 3), v.transpose(0, 3, 1, 2)
    s_pos = jnp.arange(k.shape[2], dtype=jnp.int32)

    def one(n):
        b, i = n // n_blk, n % n_blk
        t_pos = i * block + jnp.arange(block, dtype=jnp.int32)
        q_b, qs_b = (jax.lax.dynamic_slice_in_dim(x[b], i * block, block, 1)
                     .transpose(1, 0, 2) for x in (q, q_shared))
        out = _attend_xla(q_b, qs_b, k_rows, k_shared, v_rows,
                          causal_keys(t_pos, s_pos), b, i + 1, chunk=block,
                          scale=scale)
        return out.transpose(1, 0, 2).astype(v.dtype)

    out = jax.lax.map(one, jnp.arange(b_sz * n_blk))
    return out.reshape((b_sz, length) + out.shape[2:])


# ── the kernel ───────────────────────────────────────────────────────


def _update(q_ref, qs_ref, k_ref, ks_ref, v_ref, acc_ref, m_ref, den_ref,
            *, n_k: int, masked: bool, scale: float):
    """The online softmax of every head of the group over the tile's
    first ``n_k`` keys, key-major: a score tile is (keys, queries) and
    the statistics (1, queries). Where ``masked`` the last ``n_q`` of
    them are the block's own keys and a query sees those at or before
    it: an iota triangle. A query sees at least its own key in every
    update, so the new max is a real score and a masked key's ``exp(NEG
    - max)`` is exactly 0."""
    n_q = q_ref.shape[1]
    split = n_k - n_q if masked else n_k
    nt = (((1,), (1,)), ((), ()))
    if masked:
        seen = (jax.lax.broadcasted_iota(jnp.int32, (n_q, n_q), 0)
                <= jax.lax.broadcasted_iota(jnp.int32, (n_q, n_q), 1))

    def head(h):
        s = jax.lax.dot_general(k_ref[h, :n_k, :], q_ref[h], nt,
                                preferred_element_type=jnp.float32)
        s = (s + jax.lax.dot_general(ks_ref[:n_k, :], qs_ref[h], nt,
                                     preferred_element_type=jnp.float32)
             ) * scale
        parts = [(0, s[:split])] if split else []
        if masked:
            parts.append((split, jnp.where(seen, s[split:], _NEG)))
        m = m_ref[h]
        m_new = m
        for _, part in parts:
            m_new = jnp.maximum(m_new, part.max(0, keepdims=True))
        fix = jnp.exp(m - m_new)
        acc, den = acc_ref[h] * fix, den_ref[h] * fix
        for start, part in parts:
            p = jnp.exp(part - m_new)
            acc = acc + jnp.dot(
                v_ref[h, :, start:start + part.shape[0]], p.astype(
                    v_ref.dtype), preferred_element_type=jnp.float32)
            den = den + p.sum(0, keepdims=True)
        acc_ref[h], den_ref[h], m_ref[h] = acc, den, m_new

    if masked:      # a diagonal tile, one a block: a loop keeps it short
        pl.loop(0, q_ref.shape[0])(head)
    else:           # unrolled: one head's softmax beside the next one's
        for h in range(q_ref.shape[0]):     # products
            head(h)


def _causal_kernel(route_ref, block_ref, tile_ref, q_ref, qs_ref, k_ref,
                   ks_ref, v_ref, o_ref, acc_ref, m_ref, den_ref, *,
                   scale: float):
    """One grid step: a group of heads of one block of queries against
    one tile of keys, the step's (route, block, tile) from the prefetched
    tables. An interior tile is multiplied whole and unmasked; the
    diagonal tile up to the block's own keys, the last block of them
    masked, and then the block's output is written."""
    w = pl.program_id(1)
    n_q, tile = q_ref.shape[1], k_ref.shape[1]
    per = tile // n_q
    # blocks of keys of this tile up to the block's own: more than a
    # tile holds on an interior tile
    n_sub = block_ref[w] + 1 - tile_ref[w] * per
    kw = dict(q_ref=q_ref, qs_ref=qs_ref, k_ref=k_ref, ks_ref=ks_ref,
              v_ref=v_ref, acc_ref=acc_ref, m_ref=m_ref, den_ref=den_ref,
              scale=scale)

    @pl.when(tile_ref[w] == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        den_ref[...] = jnp.zeros_like(den_ref)

    @pl.when(n_sub > per)
    def _():
        _update(n_k=tile, masked=False, **kw)

    for n in range(1, per + 1):
        pl.when(n_sub == n)(functools.partial(
            _update, n_k=n * n_q, masked=True, **kw))

    @pl.when(n_sub <= per)
    def _():
        o_ref[...] = (acc_ref[...] / den_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "tile", "scale",
                                             "head_tile", "interpret"))
def _causal_fused(q, q_shared, k, k_shared, v, *, block: int, tile: int,
                  scale: float, head_tile: int = HEAD_TILE,
                  interpret: bool = False):
    """Every block of ``block`` queries of every route against its causal
    keys in tiles of ``tile``, as one kernel: the arrays as
    :func:`causal_attention` takes them → (B, H, dv, L) in ``v.dtype``,
    the length last as the value's. The grid's (route, block, tile)
    columns are :func:`causal_grid`'s, prefetched scalars: no route is
    sliced out in HBM and no step lies past a diagonal. Jitted, so that
    the layers of a step program share one trace and lowering of it."""
    b_sz, heads, length, d = q.shape
    d_r, d_v = q_shared.shape[-1], v.shape[2]
    grid = causal_grid(b_sz, length, block, tile)
    route, blk, til = (jnp.asarray(grid[:, c]) for c in range(3))

    def by_query(g, w, r, i, j):
        return r[w], g, i[w], 0

    def by_key(g, w, r, i, j):
        return r[w], g, j[w], 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(heads // head_tile, len(grid)),
        in_specs=[pl.BlockSpec((None, head_tile, block, d), by_query),
                  pl.BlockSpec((None, head_tile, block, d_r), by_query),
                  pl.BlockSpec((None, head_tile, tile, d), by_key),
                  pl.BlockSpec((None, tile, d_r),
                               lambda g, w, r, i, j: (r[w], j[w], 0)),
                  pl.BlockSpec((None, head_tile, d_v, tile),
                               lambda g, w, r, i, j: (r[w], g, 0, j[w]))],
        out_specs=pl.BlockSpec((None, head_tile, d_v, block),
                               lambda g, w, r, i, j: (r[w], g, 0, i[w])),
        scratch_shapes=[pltpu.VMEM((head_tile, d_v, block), jnp.float32),
                        pltpu.VMEM((head_tile, 1, block), jnp.float32),
                        pltpu.VMEM((head_tile, 1, block), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_causal_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b_sz, heads, d_v, length), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        name=KERNEL,
        interpret=interpret,
    )(route, blk, til, q, q_shared, k, k_shared, v)
