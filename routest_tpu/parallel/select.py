"""Causal attention over a window of keys, and over a selected set of
keys, one block of queries at a time.

Both take the keys in two parts, as latent attention makes them: a
per-head part ``k`` (B, L, H, D) and a part ``k_shared`` (B, L, Dr)
that every head scores against (the rotary part of a latent key). The
queries are not taken as an array: ``q_fn(b, t0)`` makes the block of
queries of route ``b`` from position ``t0`` on, ``(q (block, H, D),
q_shared (block, H, Dr))``, inside the loop, so that the queries of all
heads never exist at once (at 128 heads of width 192 they are as large
as the keys). The score is ``(q.k + q_shared.k_shared) * scale``;
products take the arrays' own dtype and accumulate in float32, the
softmax is float32.

- :func:`windowed_attention`: query t sees ``t - window + 1 <= s <= t``
  (:func:`window_keys`). A block of queries is scored against the few
  blocks of keys that its window touches and nothing else: the work is
  O(L * window), not O(L^2).
- :func:`selected_attention`: query t sees the ``top_k`` keys ``s <= t``
  with the largest selector score ``I(t, s) = sum_j w_j(t) relu(qI_j(t)
  . kI(s))`` (every ``s <= t`` while there are no more than ``top_k``;
  equal scores go to the lower s). The selection is applied as a mask
  over the causal blocks of keys (an online softmax over chunks of
  keys, as ``ring.blockwise_attention``), not as a gather: a chunk
  wholly in a block's future is not visited, by the selector or by the
  attention. The threshold comes from a radix search over the scores'
  bit patterns (:func:`top_k_mask`), not from a sort.

Each returns the attention output and, per query, the number of keys it
saw and the first of them: what the sequence scorer's counters and the
benchmark's comparison of key sets read.

**Which online-softmax step runs where.** The step of
:func:`selected_attention` — score product of both key parts, scale,
mask, running max, ``exp``, row sum, rescale of the accumulator, value
product — has two forms with one set of semantics, and
:func:`attention_path` chooses between them from shapes, dtype and
backend alone:

- ``"fused"``: one Pallas TPU kernel a block of queries
  (:func:`_attend_fused`, ``selected_attention_step`` in a trace), a
  grid over groups of ``HEAD_TILE`` heads and tiles of 1,024 or 512
  keys (:func:`key_tile_for`), the float32 (queries, keys) score and
  probability tiles and the running max, sum and accumulator in VMEM.
  The heads of a group are unrolled, one head's softmax beside the next
  one's products. A head's running max and sum are (queries, 128), a
  row's value in every lane, so that they meet the score tile 128 keys
  at a time and the (queries, Dv) accumulator lane for lane, with no
  column broadcast. The mask becomes an additive ``0 / -inf`` bias once
  a step for the group's heads: one select a step, and none after the
  ``exp``, because the running max starts at the finite ``_NEG`` and a
  masked key's ``exp(-inf - max)`` is exactly 0, also for a query that
  has seen no key yet. Jitted, so that the full layers of a step
  program share one trace and lowering of it. It runs where the backend
  is a TPU, the arrays are bfloat16 and the shapes tile: head widths
  multiples of 128, the shared width of 64, the heads a multiple of
  ``HEAD_TILE``, the chunk of a key tile, the block of 32. Written in
  XLA the same step sends a float32 (heads, block, chunk) tile through
  HBM five times (268 MB at 128 heads, 256 queries, 2,048 keys) and is
  bound by that; the kernel reads the chunk's keys and values and
  writes the block's output, nothing else.
- ``"xla"``: :func:`_attend_xla`, a ``fori_loop`` over chunks of keys.
  It runs everywhere else — the CPU of the tests, toy widths, float32
  arrays — and is the oracle of the kernel's parity test.

Both visit every key tile that holds a key ``s <= t`` of the block and
none wholly in its future (such a tile adds exactly nothing: its scores
are all masked, so the running max, the sum and the accumulator stay as
they are); a masked key contributes exactly zero mass in both.

**Which window step runs where.** The score-softmax-value step of
:func:`windowed_attention` has the same two forms under the same
contract — ``(q.k + q_shared.k_shared) * scale``, products of the
arrays' dtype into float32, float32 statistics, probabilities cast to
``v.dtype`` for the value product, a key outside the window exactly
zero mass — and :func:`window_path` chooses between them from heads,
block, span, the three widths, dtype and backend alone:

- ``"fused"``: one Pallas TPU kernel a block of queries
  (:func:`_window_fused`, ``windowed_attention_step`` in a trace), a
  grid over groups of ``HEAD_TILE`` heads and tiles of ``WINDOW_Q_TILE``
  queries. A step fetches ONE span of keys — the tile's own and the
  ``window - 1`` before them, rounded up to whole lanes of 128: 768 keys
  for 256 queries under a window of 513 — at an element offset computed
  from the block's position (a prefetched scalar), and takes one softmax
  over it in VMEM: no running max, sum or rescaling (with tiles of keys
  and an online softmax the per-row statistics, one lane in 128 used,
  cost as much as the scores: PERF.md §6, PR 36), and the mask comes
  from position iotas: none is fetched. Both key parts are scored as ONE
  product ``d + d_shared`` deep: a per-head part 192 wide would cost two
  128-deep passes of the MXU and the shared 64 a third, side by side
  they are two. The layer's keys come with the length last, (B, H, D, L)
  and (B, Dr, L): how XLA lays out an expansion 192 wide anyway, so the
  transposes this function asks for are free and the kernel puts the
  shared part under every head's own in VMEM. It runs on a TPU,
  bfloat16, heads a multiple of ``HEAD_TILE``, ``d + d_shared`` and
  ``d_v`` multiples of 128, the block whole tiles of queries, the span
  whole lanes and no more than ``WINDOW_MAX_SPAN``. Written in XLA the
  step sends a float32 (heads, block, span) tile through HBM five times
  (134 MB at 64 heads, 512 queries, 1,024 keys).
- ``"xla"``: :func:`_window_xla`, one softmax over the span of whole
  blocks the window touches (:func:`window_span`): everywhere else, and
  the oracle of the kernel's parity test (``tests/test_window_fused.py``).

What each query saw (``n_keys``, ``first_key``) is computed in both
forms by the same lines from the same :func:`window_keys`.

**Which top-k runs where.** :func:`top_k_mask` has two forms of one
result — the same set of keys, bit for bit — and :func:`topk_path`
chooses between them from the scores' shape, dtype and backend alone:

- ``"fused"``: one Pallas TPU kernel a block of queries
  (:func:`_top_k_fused`, ``radix_top_k_step`` in a trace) finds each
  row's k-th value and last tie; the mask is the shared last line
  (:func:`_cut_at`) over them. A grid over tiles of ``TOPK_ROWS`` rows,
  the tile's whole row of scores in VMEM; its ordered keys are made
  once, and the value search reads them ``RADIX_BITS`` bits of the
  threshold a pass, counting lane by lane in ``(rows, TOPK_PIECE)``
  int32 with one sum across lanes a pass, over the columns up to the
  tile's last causal one and none beyond (a dynamic trip count from the
  rows' positions, prefetched). The search by position runs only in a
  tile where a row has more ties at its k-th value than it still needs;
  a tile whose rows have no more than ``top_k`` causal keys searches for
  nothing. It runs on a TPU for float32 scores of whole tiles of rows
  and whole lanes of columns: ``selected_attention``'s ``(256, L)``
  buffers. In XLA each of the eight value passes compares every element
  of the whole buffer, the columns after the causal ones too, against 15
  candidates and reads it from HBM; in VMEM a pass's fixed cost (the
  load of a piece, the loop, the sums across lanes) weighs about four
  compares, so 16 passes of 2-bit digits beat 32 of one bit and 8 of
  four (PERF.md §6, the forms of the top-k).
- ``"xla"``: :func:`_top_k_xla`, the radix search over whole rows
  (:func:`_radix_search`, four bits a pass), then the search by
  position on every row. It runs everywhere else — the CPU, the few
  named rows of ``selected_rows``, ``choose_blocks``' block scores
  (whose columns are not whole lanes) — and is the oracle of the
  kernel's parity test (``tests/test_topk_fused.py``).
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _scores(q, q_shared, k, k_shared, scale):
    """(H, Q, K) float32."""
    s = jnp.einsum("qhd,khd->hqk", q, k,
                   preferred_element_type=jnp.float32)
    s = s + jnp.einsum("qhd,kd->hqk", q_shared, k_shared,
                       preferred_element_type=jnp.float32)
    return s * scale


def _key_taps(keys):
    return (keys.sum(-1).astype(jnp.int32),
            jnp.argmax(keys, -1).astype(jnp.int32))


def window_keys(t, s, window: int):
    """Whether the query at position ``t`` sees the key at ``s``: the
    one expression both forms of the window step mask by."""
    return (s <= t) & (s > t - window)


def window_span(length: int, block: int, window: int) -> int:
    """Keys that the XLA form scores a block of queries against: the
    whole blocks its window touches (the length where that is less)."""
    return min(((window - 2) // block + 2) * block, length)


def _window_xla(q, q_shared, k, k_shared, v, keys, scale: float):
    """One block of queries against the span of keys its window touches,
    one softmax over the span: q (Q, H, D), q_shared (Q, H, Dr), k (S,
    H, D), k_shared (S, Dr), v (S, H, Dv), keys (Q, S) bool → (Q, H, Dv)
    float32."""
    s = jnp.where(keys[None], _scores(q, q_shared, k, k_shared, scale), _NEG)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def windowed_attention(q_fn: Callable, k, k_shared, v, *, window: int,
                       scale: float, block: int = 512):
    """→ (out (B, L, H, Dv), n_keys (B, L), first_key (B, L)). ``L``
    must be a multiple of ``block`` (or smaller than it)."""
    b_sz, length, heads, d = k.shape
    block = min(block, length)
    if length % block:
        raise ValueError(f"length {length} is not a multiple of {block}")
    n_blk = length // block
    span = window_span(length, block, window)
    fused = window_path(heads, block, span, d, k_shared.shape[-1],
                        v.shape[-1], k.dtype) == "fused"
    if fused:       # by head, the keys with the length last, once a layer
        k_heads, ks_t = k.transpose(0, 2, 3, 1), k_shared.transpose(0, 2, 1)
        v_heads = v.transpose(0, 2, 1, 3)

    def one(n):
        b, i = n // n_blk, n % n_blk
        q, q_shared = q_fn(b, i * block)
        start = jnp.clip((i + 1) * block - span, 0, length - span)
        t = i * block + jnp.arange(block)[:, None]
        keys = window_keys(t, start + jnp.arange(span)[None, :], window)
        if fused:
            out = _window_fused(jnp.concatenate([q, q_shared], -1), k_heads,
                                ks_t, v_heads, b, i * block, window=window,
                                scale=scale).transpose(1, 0, 2)
        else:
            out = _window_xla(
                q, q_shared, jax.lax.dynamic_slice_in_dim(k[b], start, span),
                jax.lax.dynamic_slice_in_dim(k_shared[b], start, span),
                jax.lax.dynamic_slice_in_dim(v[b], start, span), keys, scale)
        n_keys, first = _key_taps(keys)
        return out.astype(v.dtype), n_keys, first + start

    out, n_keys, first = jax.lax.map(one, jnp.arange(b_sz * n_blk))
    return (out.reshape(b_sz, length, heads, -1),
            n_keys.reshape(b_sz, length), first.reshape(b_sz, length))


# ── the selector ─────────────────────────────────────────────────────


def _radix_search(holds: Callable, n_bits: int, rows: int):
    """Per row the largest ``r < 2**n_bits`` for which ``holds(r)``,
    where ``holds`` is true for 0 and, once false, stays false as ``r``
    grows. Four bits a pass: ``holds`` is asked about 15 candidates
    (rows, 15) at once, so the data behind it is read once a pass."""
    r = jnp.zeros((rows,), jnp.uint32)
    digits = jnp.arange(1, 16, dtype=jnp.uint32)
    for shift in range(n_bits - 4, -1, -4):
        ok = holds(r[:, None] | (digits << shift)[None, :])
        r = r | (ok.sum(-1).astype(jnp.uint32) << shift)
    return r


TOPK_ROWS = 16             # rows of one grid step of the top-k kernel
TOPK_PIECE = 1024          # columns of one trip of its counting loops
TOPK_MAX_COLS = 131072     # a step's scores and keys fit VMEM twice over
RADIX_BITS = 2             # of the threshold a value pass decides
# (readings of the forms: PERF.md §6, the top-k kernel)
_INT_MIN = -2 ** 31        # the key of a column past the query: u = 0
SELECTOR_DTYPE = jnp.float32   # a block's scores that top_k_mask cuts


def topk_path(rows: int, cols: int, dtype, backend: str = "") -> str:
    """The form :func:`top_k_mask` runs for ``(rows, cols)`` scores:
    ``"fused"`` (the Pallas kernel) on a TPU for float32 scores in whole
    tiles of ``TOPK_ROWS`` rows and whole lanes of columns, ``"xla"``
    everywhere else. A route no longer than ``top_k`` takes neither: its
    mask is every causal key. ``backend`` defaults to JAX's own."""
    tiles = (rows % TOPK_ROWS == 0 and cols % _LANES == 0
             and cols <= TOPK_MAX_COLS)
    on_tpu = (backend or jax.default_backend()) == "tpu"
    return ("fused" if on_tpu and tiles and jnp.dtype(dtype) == jnp.float32
            else "xla")


def _ordered(scores):
    """int32 keys whose signed order is the float32 scores' order."""
    bits = jax.lax.bitcast_convert_type(
        jnp.where(scores == 0, 0.0, scores), jnp.int32)   # -0.0 is 0.0
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def _sortable(scores, causal):
    """(Q, K) uint32 in the order of the float32 scores, 0 off the
    causal columns: no finite score maps to 0."""
    u = jax.lax.bitcast_convert_type(
        _ordered(scores.astype(jnp.float32)), jnp.uint32) ^ jnp.uint32(
        0x80000000)
    return jnp.where(causal, u, jnp.uint32(0))


def _cut_at(u, causal, s_pos, kth, last_tie):
    """The keys above the k-th value and those equal to it up to
    position ``last_tie``: both forms' last line."""
    above = u > kth
    tie = (u == kth) & causal
    return (above & causal) | (tie & (s_pos.astype(jnp.uint32) <= last_tie))


def top_k_mask(scores, t_pos, top_k: int):
    """(Q, K) bool: the ``top_k`` largest of ``scores[q, s]`` over ``s <=
    t_pos[q]``, ties to the lower s; every such s where there are no
    more than ``top_k``. Exact, without a sort: the float32 bit patterns
    are mapped to unsigned integers of the same order, the value of the
    k-th largest is found by a radix search on counts, then the cut
    among the keys equal to it by a second search on their positions.
    Two forms of it, chosen by :func:`topk_path` from the shapes, dtype
    and backend: :func:`_top_k_fused` finds each row's k-th value and
    last tie in one kernel, :func:`_top_k_xla` everything in XLA."""
    n_q, n_k = scores.shape
    if n_k <= top_k or topk_path(n_q, n_k, scores.dtype) == "xla":
        return _top_k_xla(scores, t_pos, top_k)
    s_pos = jnp.arange(n_k, dtype=jnp.int32)[None, :]
    causal = s_pos <= t_pos[:, None]
    kth, last_tie = _top_k_fused(scores, t_pos, top_k=top_k)
    return _cut_at(_sortable(scores, causal), causal, s_pos, kth[:, None],
                   last_tie[:, None])


def _top_k_xla(scores, t_pos, top_k: int):
    """:func:`top_k_mask` in XLA: the CPU's form, the kernel's oracle,
    and the form of every shape the kernel does not tile."""
    n_q, n_k = scores.shape
    s_pos = jnp.arange(n_k, dtype=jnp.int32)[None, :]
    causal = s_pos <= t_pos[:, None]
    if n_k <= top_k:
        return causal
    u = _sortable(scores, causal)
    want = jnp.minimum(top_k, t_pos + 1).astype(jnp.int32)

    def at_least_k(cand):
        n = (u[:, None, :] >= cand[:, :, None]).sum(-1, dtype=jnp.int32)
        return n >= want[:, None]

    kth = _radix_search(at_least_k, 32, n_q)[:, None]
    above = u > kth
    tie = (u == kth) & causal
    need = want - above.sum(-1, dtype=jnp.int32)

    def fewer_before(cand):
        n = (tie[:, None, :] & (s_pos[None].astype(jnp.uint32)
                                < cand[:, :, None])).sum(-1,
                                                         dtype=jnp.int32)
        return n < need[:, None]

    pos_bits = 4 * math.ceil(max(n_k - 1, 1).bit_length() / 4)
    last_tie = _radix_search(fewer_before, pos_bits, n_q)[:, None]
    return _cut_at(u, causal, s_pos, kth, last_tie)


def _causal_pieces(t_max, n_k: int, piece: int):
    """The pieces of ``piece`` columns, from the first, that hold a
    column ``s <= t_max``: the only ones a tile of rows whose last
    position is ``t_max`` reads."""
    return jnp.minimum(t_max // piece + 1, n_k // piece)


def _count_keys(key_ref, n_pieces, piece: int, preds):
    """Per row of the step, the number of its first ``n_pieces * piece``
    keys for which each of ``preds(keys, first column)`` holds: a
    ``(rows, piece)`` int32 count a predicate, added to lane by lane
    each trip and summed across lanes once at the end."""
    rows = key_ref.shape[0]

    def trip(j, accs):
        at = pl.multiple_of(j * piece, piece)
        keys = key_ref[:, pl.ds(at, piece)]
        return tuple(a + p(keys, at).astype(jnp.int32)
                     for a, p in zip(accs, preds))

    accs = jax.lax.fori_loop(0, n_pieces, trip, tuple(
        jnp.zeros((rows, piece), jnp.int32) for _ in preds))
    return [a.sum(-1, keepdims=True) for a in accs]


def _topk_kernel(t_sm, t_ref, s_ref, kth_ref, last_ref, key_ref, *,
                 top_k: int, piece: int):
    """One grid step: ``TOPK_ROWS`` rows of scores. ``t_sm`` (Q,) the
    rows' positions in SMEM, ``t_ref`` the step's (rows, 128), a row's
    in every lane. Writes each row's k-th value as a signed key (the
    order of ``_sortable``'s u, shifted by 2**31) and its last tie, n_k
    where every tie is taken; a row with no more than ``top_k`` causal
    keys takes them all (k-th value u = 0)."""
    rows, n_k = s_ref.shape
    base = pl.program_id(0) * rows
    t_max = jax.lax.fori_loop(
        0, rows, lambda r, m: jnp.maximum(m, t_sm[base + r]), jnp.int32(0))
    kth_ref[...] = jnp.full(kth_ref.shape, _INT_MIN, jnp.int32)
    last_ref[...] = jnp.full(last_ref.shape, n_k, jnp.int32)

    @pl.when(t_max >= top_k)
    def _():
        n_pieces = _causal_pieces(t_max, n_k, piece)
        t = t_ref[:, :1]
        want = jnp.minimum(t + 1, top_k)
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, piece), 1)

        def to_keys(j, c):
            at = pl.multiple_of(j * piece, piece)
            key_ref[:, pl.ds(at, piece)] = jnp.where(
                col + at <= t, _ordered(s_ref[:, pl.ds(at, piece)]), _INT_MIN)
            return c

        jax.lax.fori_loop(0, n_pieces, to_keys, 0)
        n_dig = 2 ** RADIX_BITS - 1

        def value_pass(p, carry):
            # the threshold so far as a signed key, and how many keys of
            # the row are at or above it
            r, n_r = carry
            shift = 32 - RADIX_BITS * (p + 1)
            cands = [jnp.broadcast_to(r ^ jnp.left_shift(jnp.int32(d), shift),
                                      (rows, piece))
                     for d in range(1, n_dig + 1)]
            counts = _count_keys(key_ref, n_pieces, piece, [
                functools.partial(lambda c, k, at: k >= c, c) for c in cands])
            digit = jnp.zeros_like(r)
            for n in counts:        # fewer keys at each larger digit
                ok = n >= want
                digit, n_r = digit + ok.astype(jnp.int32), jnp.where(ok, n,
                                                                     n_r)
            return r ^ jnp.left_shift(digit, shift), n_r

        kth, n_kth = jax.lax.fori_loop(
            0, 32 // RADIX_BITS, value_pass,
            (jnp.full((rows, 1), _INT_MIN, jnp.int32),
             jnp.zeros((rows, 1), jnp.int32)))
        kth_ref[...] = jnp.broadcast_to(kth, kth_ref.shape)

        # more keys tie at the k-th value than a row still needs: the
        # cut among them by position, ties to the lower s
        @pl.when(jnp.any(n_kth != want))
        def _():
            kth_b = jnp.broadcast_to(kth, (rows, piece))
            above, = _count_keys(key_ref, n_pieces, piece,
                                 [lambda k, at: k > kth_b])
            need = want - above
            pos_bits = max(n_k - 1, 1).bit_length()

            def pos_pass(p, last):
                cand = last | jnp.left_shift(1, pos_bits - 1 - p)
                n, = _count_keys(key_ref, n_pieces, piece, [
                    lambda k, at: (k == kth_b) & (col + at < cand)])
                return jnp.where(n < need, cand, last)

            last = jax.lax.fori_loop(0, pos_bits, pos_pass,
                                     jnp.zeros((rows, 1), jnp.int32))
            last_ref[...] = jnp.broadcast_to(last, last_ref.shape)


@functools.partial(jax.jit, static_argnames=("top_k", "interpret"))
def _top_k_fused(scores, t_pos, *, top_k: int, interpret: bool = False):
    """Each row's k-th value and last tie as one kernel
    (``radix_top_k_step`` in a trace): scores (Q, K) float32, t_pos (Q,)
    → (kth, last_tie), (Q,) uint32 each, kth in ``_sortable``'s order
    (what ``_cut_at`` takes). A grid over tiles of ``TOPK_ROWS`` rows, each
    held whole in VMEM: its keys are made once and counted ``RADIX_BITS``
    of the threshold a pass over the columns up to the tile's last causal
    one, none beyond; the position search runs only in a tile where
    some row has more ties at its k-th value than it needs. Jitted, so
    that the full layers of a step program share one trace and lowering
    of it."""
    n_q, n_k = scores.shape
    piece = math.gcd(n_k, TOPK_PIECE)
    if n_q % TOPK_ROWS or piece % _LANES:
        raise ValueError(f"{n_q} rows in tiles of {TOPK_ROWS}, {n_k} "
                         f"columns in lanes of {_LANES}: not whole tiles")
    t = t_pos.astype(jnp.int32)
    row = lambda i, t: (i, 0)   # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_q // TOPK_ROWS,),
        in_specs=[pl.BlockSpec((TOPK_ROWS, _LANES), row),
                  pl.BlockSpec((TOPK_ROWS, n_k), row)],
        out_specs=[pl.BlockSpec((TOPK_ROWS, _LANES), row)] * 2,
        scratch_shapes=[pltpu.VMEM((TOPK_ROWS, n_k), jnp.int32)])
    kth, last = pl.pallas_call(
        functools.partial(_topk_kernel, top_k=top_k, piece=piece),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_q, _LANES), jnp.int32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_BYTES),
        name="radix_top_k_step",
        interpret=interpret,
    )(t, jnp.broadcast_to(t[:, None], (n_q, _LANES)),
      scores.astype(jnp.float32))
    return (jax.lax.bitcast_convert_type(kth[:, 0], jnp.uint32)
            ^ jnp.uint32(0x80000000), last[:, 0].astype(jnp.uint32))


def selector_scores(q_idx, w_idx, k_idx):
    """I(t, s) for a block: q_idx (Q, J, Di), w_idx (Q, J) float32,
    k_idx (K, Di) → (Q, K) float32."""
    s = jnp.einsum("qjd,kd->jqk", q_idx, k_idx,
                   preferred_element_type=jnp.float32)
    return (jax.nn.relu(s) * w_idx.T[:, :, None]).sum(0)


# ── the online-softmax step of selected_attention, in two forms ──────

HEAD_TILE = 8               # heads of one grid step of the kernel
KEY_TILES = (1024, 512)     # keys of one: the first that divides the chunk
_VMEM_BYTES = 64 * 2 ** 20  # (readings of both: PERF.md §6, PR 28)
_LANES = 128


def key_tile_for(chunk: int) -> int:
    """Keys of one grid step of the kernel where the loop steps by
    ``chunk`` (which divides the length); 0 where no tile divides it."""
    return next((t for t in KEY_TILES if chunk % t == 0), 0)


def attention_path(heads: int, block: int, chunk: int, d: int,
                   d_shared: int, d_v: int, dtype, backend: str = "") -> str:
    """The step :func:`selected_attention` runs at these shapes:
    ``"fused"`` (the Pallas kernel) on a TPU where bfloat16 arrays tile,
    ``"xla"`` everywhere else. ``chunk`` is the chunk the loop really
    steps by, ``block`` the block it really takes (both after the length
    cut them); ``backend`` defaults to JAX's own."""
    tiles = (heads % HEAD_TILE == 0 and block % 32 == 0
             and key_tile_for(chunk) > 0 and d % 128 == 0 and d_v % 128 == 0
             and d_shared % 64 == 0)
    on_tpu = (backend or jax.default_backend()) == "tpu"
    return ("fused" if on_tpu and tiles and jnp.dtype(dtype) == jnp.bfloat16
            else "xla")


def block_and_chunk(length: int, block: int, chunk: int):
    """The block of queries and the chunk of keys that
    :func:`selected_attention` steps by at this length."""
    block = min(block, length)
    return block, math.gcd(length, max(chunk, block))


def chunk_steps(length: int, block: int, chunk: int) -> int:
    """Online-softmax steps over chunks of keys that one route of this
    (padded) length takes in one selecting layer: the sum over its
    blocks of queries of the causal chunks each visits."""
    block, chunk = block_and_chunk(length, block, chunk)
    return sum(((i + 1) * block + chunk - 1) // chunk
               for i in range(length // block))


def topk_blocks(length: int, block: int, top_k: int,
                backend: str = "") -> Tuple[str, int]:
    """For one route of (padded) ``length`` in one selecting layer: the
    form of :func:`top_k_mask` (:func:`topk_path` at the block's
    ``SELECTOR_DTYPE`` scores, as :func:`selected_attention` hands them
    over) and how many blocks of queries run it, none where the route is
    no longer than ``top_k`` (every causal key, no selection)."""
    block, _ = block_and_chunk(length, block, block)
    return (topk_path(block, length, SELECTOR_DTYPE, backend),
            length // block if length > top_k else 0)


def _attend_xla(q, q_shared, k, k_shared, v, keys, b, n_chunks, *,
                chunk: int, scale: float):
    """One block of queries over the first ``n_chunks`` chunks of the
    keys of route ``b``: q (Q, H, D), q_shared (Q, H, Dr), k (B, L, H,
    D), k_shared (B, L, Dr), v (B, L, H, Dv), keys (Q, L) bool → (H, Q,
    Dv) float32."""
    block, heads, _ = q.shape

    def attend_chunk(j, carry):
        acc, m, den = carry
        kc = jax.lax.dynamic_slice_in_dim(k[b], j * chunk, chunk, 0)
        ks = jax.lax.dynamic_slice_in_dim(k_shared[b], j * chunk, chunk, 0)
        vc = jax.lax.dynamic_slice_in_dim(v[b], j * chunk, chunk, 0)
        seen = jax.lax.dynamic_slice_in_dim(keys, j * chunk, chunk, 1)[None]
        s = jnp.where(seen, _scores(q, q_shared, kc, ks, scale), _NEG)
        m_new = jnp.maximum(m, s.max(-1))
        # the mask multiply: in a chunk where a query sees nothing
        # exp(NEG - NEG) = 1 would add mass that is not there
        p = jnp.exp(s - m_new[..., None]) * seen
        fix = jnp.exp(m - m_new)
        acc = acc * fix[..., None] + jnp.einsum(
            "hqk,khd->hqd", p.astype(v.dtype), vc,
            preferred_element_type=jnp.float32)
        return acc, m_new, den * fix + p.sum(-1)

    acc, _, den = jax.lax.fori_loop(
        0, n_chunks, attend_chunk,
        (jnp.zeros((heads, block, v.shape[-1]), jnp.float32),
         jnp.full((heads, block), _NEG, jnp.float32),
         jnp.zeros((heads, block), jnp.float32)))
    return acc / den[..., None]


def _attend_kernel(at_ref, q_ref, qs_ref, k_ref, ks_ref, v_ref, seen_ref,
                   o_ref, acc_ref, m_ref, den_ref, bias_ref, *, scale: float):
    """One grid step: ``HEAD_TILE`` heads of the block against one tile
    of keys. A head's running max and sum are (Q, 128), a row's value in
    every lane, so that they meet the (Q, keys) score tile one 128-key
    chunk at a time and the (Q, Dv) accumulator lane for lane, with no
    column broadcast. ``at_ref`` (2,): the route and the number of key
    tiles the block visits; beyond it a step does nothing and fetches
    nothing."""
    j, n_tiles = pl.program_id(1), at_ref[1]
    n_k, d_v = k_ref.shape[1], acc_ref.shape[-1]
    nt = (((1,), (1,)), ((), ()))

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        den_ref[...] = jnp.zeros_like(den_ref)

    @pl.when(j < n_tiles)
    def _():
        # the mask once a step for the group's heads, as a bias: a masked
        # score is -inf and the running max starts at the finite NEG, so
        # exp(s - m_new) is exactly 0 at a masked key, also where a query
        # has seen no key yet
        bias_ref[...] = jnp.where(seen_ref[...].astype(jnp.int32) != 0, 0.0,
                                  -jnp.inf)
        # unrolled: one head's softmax beside the next one's products
        for h in range(q_ref.shape[0]):
            s = jax.lax.dot_general(q_ref[h], k_ref[h], nt,
                                    preferred_element_type=jnp.float32)
            s = s + jax.lax.dot_general(qs_ref[h], ks_ref[...], nt,
                                        preferred_element_type=jnp.float32)
            s = s * scale + bias_ref[...]
            m = m_ref[h]
            m_new = jnp.maximum(m, s.max(-1, keepdims=True))
            p = jnp.exp(s - jnp.tile(m_new, (1, n_k // _LANES)))
            fix = jnp.exp(m - m_new)
            acc_ref[h] = acc_ref[h] * jnp.tile(fix, (1, d_v // _LANES)) \
                + jnp.dot(p.astype(v_ref.dtype), v_ref[h],
                          preferred_element_type=jnp.float32)
            den_ref[h] = den_ref[h] * fix + p.sum(-1, keepdims=True)
            m_ref[h] = m_new

    @pl.when(j == n_tiles - 1)
    def _():
        den = jnp.tile(den_ref[...], (1, 1, d_v // _LANES))
        o_ref[...] = (acc_ref[...] / den).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "key_tile", "head_tile",
                                             "interpret", "name"))
def _attend_fused(q, q_shared, k, k_shared, v, keys, b, n_tiles, *,
                  scale: float, key_tile: int, head_tile: int = HEAD_TILE,
                  interpret: bool = False,
                  name: str = "selected_attention_step"):
    """The same block of queries as :func:`_attend_xla` over the first
    ``n_tiles`` tiles of ``key_tile`` keys of route ``b``, as one kernel:
    q (Q, H, D), q_shared (Q, H, Dr) as ``q_fn`` makes them; k (B, H, L,
    D), v (B, H, L, Dv) laid out by head; k_shared (B, L, Dr); keys (Q,
    L) bool → (H, Q, Dv) in ``v.dtype``. Keys and values are fetched
    tile by tile straight from the whole arrays (``b`` is a prefetched
    scalar: no route is sliced out in HBM). Jitted, so that the full
    layers of a step program share one trace and lowering of it.
    ``name``: what a device trace calls the kernel."""
    n_q, heads, d = q.shape
    d_r, d_v, length = q_shared.shape[-1], v.shape[-1], v.shape[2]
    at = jnp.stack([b, n_tiles]).astype(jnp.int32)

    def tile(j, at):                      # no tile beyond the last one
        return jnp.minimum(j, at[1] - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(heads // head_tile, length // key_tile),
        in_specs=[
            pl.BlockSpec((head_tile, n_q, d), lambda g, j, at: (g, 0, 0)),
            pl.BlockSpec((head_tile, n_q, d_r), lambda g, j, at: (g, 0, 0)),
            pl.BlockSpec((None, head_tile, key_tile, d),
                         lambda g, j, at: (at[0], g, tile(j, at), 0)),
            pl.BlockSpec((None, key_tile, d_r),
                         lambda g, j, at: (at[0], tile(j, at), 0)),
            pl.BlockSpec((None, head_tile, key_tile, d_v),
                         lambda g, j, at: (at[0], g, tile(j, at), 0)),
            pl.BlockSpec((n_q, key_tile),
                         lambda g, j, at: (0, tile(j, at)))],
        out_specs=pl.BlockSpec((head_tile, n_q, d_v),
                               lambda g, j, at: (g, 0, 0)),
        scratch_shapes=[pltpu.VMEM((head_tile, n_q, d_v), jnp.float32),
                        pltpu.VMEM((head_tile, n_q, _LANES), jnp.float32),
                        pltpu.VMEM((head_tile, n_q, _LANES), jnp.float32),
                        pltpu.VMEM((n_q, key_tile), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_attend_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((heads, n_q, d_v), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        name=name,
        interpret=interpret,
    )(at, q.transpose(1, 0, 2), q_shared.transpose(1, 0, 2), k, k_shared, v,
      keys.astype(jnp.int8))


# ── the window step of windowed_attention, as a kernel ───────────────

WINDOW_Q_TILE = 256         # queries of one grid step of the window kernel
WINDOW_MAX_SPAN = 2048      # (readings of the tilings: PERF.md §6, PR 36)


def window_path(heads: int, block: int, span: int, d: int, d_shared: int,
                d_v: int, dtype, backend: str = "") -> str:
    """The step :func:`windowed_attention` runs at these shapes:
    ``"fused"`` (the Pallas kernel) on a TPU where bfloat16 arrays tile,
    ``"xla"`` everywhere else. ``block`` is the block of queries it
    really takes and ``span`` the keys :func:`window_span` gives it (both
    after the length cut them; the kernel's own span of keys is no
    longer, and a float32 tile of ``WINDOW_Q_TILE`` queries by more than
    ``WINDOW_MAX_SPAN`` keys a head would not fit VMEM); the kernel
    scores the two key parts as one, ``d + d_shared`` wide; ``backend``
    defaults to JAX's own."""
    tiles = (heads % HEAD_TILE == 0 and block % WINDOW_Q_TILE == 0
             and span % _LANES == 0 and span <= WINDOW_MAX_SPAN
             and (d + d_shared) % _LANES == 0 and d_v % _LANES == 0)
    on_tpu = (backend or jax.default_backend()) == "tpu"
    return ("fused" if on_tpu and tiles and jnp.dtype(dtype) == jnp.bfloat16
            else "xla")


def _window_first_key(t0, back: int, n_k: int, length: int):
    """The first of the ``n_k`` keys fetched for the tile of queries
    from position ``t0`` on, ``back`` keys before it where the route has
    them: in whole lanes, so that the compiler sees an aligned offset."""
    return jnp.clip((t0 - back) // _LANES, 0, (length - n_k) // _LANES) \
        * _LANES


def _window_kernel(at_ref, q_ref, k_ref, ks_ref, v_ref, o_ref, kk_ref, *,
                   scale: float, window: int, back: int, length: int):
    """One grid step: ``HEAD_TILE`` heads of one tile of queries against
    the one span of keys their windows lie in, one softmax over it.
    ``at_ref`` (2,): the route and the position of the block's first
    query; which keys a query sees comes from positions alone."""
    n_q, d, n_k = q_ref.shape[1], k_ref.shape[2], k_ref.shape[3]
    t0 = at_ref[1] + pl.program_id(1) * n_q
    # both key parts as one, the shared part under every head's own: one
    # product of whole 128-deep passes
    kk_ref[:, :d, :] = k_ref[0]
    kk_ref[:, d:, :] = jnp.broadcast_to(
        ks_ref[...], kk_ref.shape[:1] + ks_ref.shape[1:])
    t = t0 + jax.lax.broadcasted_iota(jnp.int32, (n_q, n_k), 0)
    s_pos = _window_first_key(t0, back, n_k, length) \
        + jax.lax.broadcasted_iota(jnp.int32, (n_q, n_k), 1)
    s = jnp.einsum("hqd,hdk->hqk", q_ref[...], kk_ref[...],
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(window_keys(t, s_pos, window)[None], s, _NEG)
    # a query sees its own key, so the max is a real score and a masked
    # key's exp(NEG - max) is exactly 0
    p = jnp.exp(s - s.max(-1, keepdims=True))
    out = jnp.einsum("hqk,hkd->hqd", p.astype(v_ref.dtype), v_ref[0],
                     preferred_element_type=jnp.float32)
    o_ref[...] = (out / p.sum(-1, keepdims=True)).astype(o_ref.dtype)


def _window_fused(q, k, k_shared, v, b, t0, *, window: int, scale: float,
                  q_tile: int = WINDOW_Q_TILE, head_tile: int = HEAD_TILE,
                  interpret: bool = False):
    """The block of queries from position ``t0`` of route ``b`` on
    against the keys its window sees, as one kernel: q (Q, H, D + Dr),
    both parts of a query side by side; k (B, H, D, L) and k_shared (B,
    Dr, L), the length last (how XLA lays out a key part of width 192
    anyway, so the expansion writes it at no cost); v (B, H, L, Dv) by
    head → (H, Q, Dv) in ``v.dtype``. A grid over groups of heads and
    tiles of ``q_tile`` queries; a step fetches ONE span of keys, the
    tile's own and the ``window - 1`` before them rounded up to whole
    lanes (the whole route where it is shorter), at an element offset
    straight from the whole arrays (``b`` and ``t0`` are prefetched
    scalars), and takes one softmax over it: no running statistics, no
    mask fetched."""
    n_q, heads, d_q = q.shape
    d, d_r, d_v, length = k.shape[2], k_shared.shape[1], v.shape[-1], v.shape[2]
    back = -(-(window - 1) // _LANES) * _LANES
    n_k = min(q_tile + back, length)
    if n_q % q_tile or q_tile % _LANES or length % _LANES or heads % head_tile:
        raise ValueError(f"{n_q} queries in tiles of {q_tile}, {length} keys, "
                         f"{heads} heads in tiles of {head_tile}: not whole "
                         f"tiles, or not whole lanes of {_LANES}")
    at = jnp.stack([b, t0]).astype(jnp.int32)

    def first_key(i, at):
        return _window_first_key(at[1] + i * q_tile, back, n_k, length)

    el = pl.Element         # offsets in elements: a span starts anywhere
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(heads // head_tile, n_q // q_tile),
        in_specs=[
            pl.BlockSpec((head_tile, q_tile, d_q), lambda g, i, at: (g, i, 0)),
            pl.BlockSpec((el(1), el(head_tile), el(d), el(n_k)),
                         lambda g, i, at: (at[0], g * head_tile, 0,
                                           first_key(i, at))),
            pl.BlockSpec((el(1), el(d_r), el(n_k)),
                         lambda g, i, at: (at[0], 0, first_key(i, at))),
            pl.BlockSpec((el(1), el(head_tile), el(n_k), el(d_v)),
                         lambda g, i, at: (at[0], g * head_tile,
                                           first_key(i, at), 0))],
        out_specs=pl.BlockSpec((head_tile, q_tile, d_v),
                               lambda g, i, at: (g, i, 0)),
        scratch_shapes=[pltpu.VMEM((head_tile, d_q, n_k), k.dtype)])
    return pl.pallas_call(
        functools.partial(_window_kernel, scale=scale, window=window,
                          back=back, length=length),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((heads, n_q, d_v), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_BYTES),
        name="windowed_attention_step",
        interpret=interpret,
    )(at, q.transpose(1, 0, 2), k, k_shared, v)


def selected_attention(q_fn: Callable, k, k_shared, v, idx_fn: Callable,
                       k_idx, *, top_k: int, scale: float, block: int = 256,
                       chunk: int = 2048, scope: str = ""):
    """→ (out (B, L, H, Dv), n_keys (B, L), first_key (B, L)).
    ``idx_fn(b, t0)`` makes a block's selector queries ``(q_idx (block,
    J, Di), w_idx (block, J))``; ``k_idx`` (B, L, Di) are the selector's
    keys. ``L`` must be a multiple of ``block`` (or smaller)."""
    b_sz, length, heads, _ = k.shape
    d_v = v.shape[-1]
    block, chunk = block_and_chunk(length, block, chunk)
    if length % block:
        raise ValueError(f"length {length} is not a multiple of {block}")
    n_blk = length // block
    fused = attention_path(heads, block, chunk, k.shape[-1],
                           k_shared.shape[-1], d_v, k.dtype) == "fused"
    if fused:       # by head, once a layer: what the kernel tiles
        k_heads, v_heads = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)

    def one(n):
        b, i = n // n_blk, n % n_blk
        t_pos = i * block + jnp.arange(block, dtype=jnp.int32)
        n_chunks = ((i + 1) * block + chunk - 1) // chunk
        if length > top_k:
            with jax.named_scope(scope + ".selector"):
                q_idx, w_idx = idx_fn(b, i * block)

                def score_chunk(j, buf):
                    kc = jax.lax.dynamic_slice_in_dim(k_idx[b], j * chunk,
                                                      chunk, 0)
                    return jax.lax.dynamic_update_slice_in_dim(
                        buf, selector_scores(q_idx, w_idx, kc), j * chunk, 1)

                scores = jax.lax.fori_loop(
                    0, n_chunks, score_chunk,
                    jnp.full((block, length), -jnp.inf, SELECTOR_DTYPE))
            with jax.named_scope(scope + ".topk"):
                keys = top_k_mask(scores, t_pos, top_k)
        else:
            keys = jnp.arange(length, dtype=jnp.int32)[None, :] \
                <= t_pos[:, None]
        q, q_shared = q_fn(b, i * block)
        if fused:
            tile = key_tile_for(chunk)
            out = _attend_fused(
                q, q_shared, k_heads, k_shared, v_heads, keys, b,
                ((i + 1) * block + tile - 1) // tile, scale=scale,
                key_tile=tile)
        else:
            out = _attend_xla(q, q_shared, k, k_shared, v, keys, b,
                              n_chunks, chunk=chunk, scale=scale)
        return (out.transpose(1, 0, 2).astype(v.dtype),) + _key_taps(keys)

    out, n_keys, first = jax.lax.map(one, jnp.arange(b_sz * n_blk))
    return (out.reshape(b_sz, length, heads, d_v),
            n_keys.reshape(b_sz, length), first.reshape(b_sz, length))


def selected_rows(q_idx, w_idx, k_idx, t_pos, top_k: int):
    """The key sets of a few named queries, (P, L) bool: the same scores
    and the same cut as :func:`selected_attention` applies to them."""
    return top_k_mask(selector_scores(q_idx, w_idx, k_idx), t_pos, top_k)


# ── block selection: grouped-query heads over a learned choice of blocks ─
#
# Keys and values have G heads, queries G * Hg (a group of Hg query heads
# shares one key-value head). A query sees the keys s <= t of ``top``
# blocks of ``block`` keys, chosen per (query, group) in two stages:
#
# 1. Compressed keys ``kc_j = mean(k[stride * j : stride * j + window])``,
#    visible to query t iff ``stride * j + window - 1 <= t``
#    (:func:`compressed_visible`). Each query head's softmax over the
#    visible ones, summed over the group's heads, max-pooled to blocks
#    (block m takes j in [r m - 1, r m + r - 1], r = block // stride:
#    kernel r + 1, stride r, one pad on the left); the first ``init``
#    blocks and every block that meets the keys t - local + 1 .. t are
#    forced, blocks past t // block are never chosen, ties go to the
#    lower block (:func:`top_k_mask` on the block scores).
# 2. The softmax over the chosen blocks' keys s <= t, applied as a mask
#    over the causal chunks of keys (:func:`_attend_group_xla`), the
#    mask shared by the group's heads. One form, XLA: at 16 heads a
#    group its float32 tiles cost 0.199 s a layer on a 47k route, a
#    Pallas kernel of the same step 0.153 s (PERF.md §6, PR 32): 0.25% of
#    a pass, not worth a second form.
#
# A route shorter than ``dense_len`` skips stage 1: every causal key.


def compress_keys(k, window: int, stride: int):
    """k (L, G, d) → (L // stride, G, d) in ``k.dtype``: the means of
    overlapping windows (float32 sums), ``window`` a multiple of
    ``stride`` which divides L. The last ``window // stride - 1`` rows
    would reach past the end: they are zero and never visible."""
    length, groups, d = k.shape
    n, per = length // stride, window // stride
    part = k.astype(jnp.float32).reshape(n, stride, groups, d).sum(1)
    part = jnp.pad(part, ((0, per - 1), (0, 0), (0, 0)))
    total = sum(part[i:i + n] for i in range(per))
    whole = (jnp.arange(n) + per <= n)[:, None, None]
    return jnp.where(whole, total / window, 0.0).astype(k.dtype)


def compressed_visible(t_pos, n_comp: int, window: int, stride: int):
    """(Q, J) bool: compressed key j lies wholly at or before query t."""
    j = jnp.arange(n_comp, dtype=jnp.int32)[None, :]
    return stride * j + (window - 1) <= t_pos[:, None]


def forced_blocks(t_pos, n_blocks: int, block: int, init: int, local: int):
    """(Q, M) bool: the first ``init`` blocks and those that meet the
    keys ``t - local + 1 .. t``."""
    m = jnp.arange(n_blocks, dtype=jnp.int32)[None, :]
    t = t_pos[:, None]
    near = (m <= t // block) & (m >= jnp.maximum(t - (local - 1), 0) // block)
    return near | (m < init)


def block_scores(q, kc, t_pos, *, scale: float, window: int, stride: int,
                 block: int):
    """Stage 1 up to the pooling: q (Q, G, Hg, d), kc (J, G, d) →
    ((G, Q, M) float32 block scores, (Q,) int32 visible compressed
    keys), M = J * stride // block."""
    n_comp, per = kc.shape[0], block // stride
    vis = compressed_visible(t_pos, n_comp, window, stride)
    s = jnp.einsum("qghd,jgd->ghqj", q, kc,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(vis[None, None], s, _NEG)
    p = jnp.where(vis[None, None], jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    a = p.sum(1)                                              # (G, Q, J)
    a = jnp.pad(a, ((0, 0), (0, 0), (1, per - 1)))
    n_blk = n_comp // per
    first = a[..., :n_comp].reshape(a.shape[:2] + (n_blk, per)).max(-1)
    return (jnp.maximum(first, a[..., per:n_comp + per:per]),
            vis.sum(-1).astype(jnp.int32))


def choose_blocks(scores, t_pos, *, top: int, block: int, init: int,
                  local: int):
    """(G, Q, M) block scores → (G, Q, M) bool: the ``top`` blocks a
    (query, group) among those at or before ``t // block``, the forced
    ones first."""
    groups, n_q, n_blk = scores.shape
    forced = forced_blocks(t_pos, n_blk, block, init, local)
    scores = jnp.where(forced[None], jnp.inf, scores)
    chosen = top_k_mask(scores.reshape(groups * n_q, n_blk),
                        jnp.tile(t_pos // block, groups), top)
    return chosen.reshape(groups, n_q, n_blk)


def _attend_group_xla(q, k, v, keys, b, n_chunks, *, chunk: int,
                      scale: float):
    """One block of queries over the first ``n_chunks`` chunks of the
    keys of route ``b``, the mask shared by a group's heads: q (Q, G,
    Hg, d), k (B, L, G, d), v (B, L, G, dv), keys (G, Q, L) bool → (G,
    Hg, Q, dv) float32."""
    n_q, groups, per, _ = q.shape

    def attend_chunk(j, carry):
        acc, m, den = carry
        kc = jax.lax.dynamic_slice_in_dim(k[b], j * chunk, chunk, 0)
        vc = jax.lax.dynamic_slice_in_dim(v[b], j * chunk, chunk, 0)
        seen = jax.lax.dynamic_slice_in_dim(keys, j * chunk, chunk,
                                            2)[:, None]
        s = jnp.einsum("qghd,kgd->ghqk", q, kc,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(seen, s, _NEG)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None]) * seen
        fix = jnp.exp(m - m_new)
        acc = acc * fix[..., None] + jnp.einsum(
            "ghqk,kgd->ghqd", p.astype(v.dtype), vc,
            preferred_element_type=jnp.float32)
        return acc, m_new, den * fix + p.sum(-1)

    acc, _, den = jax.lax.fori_loop(
        0, n_chunks, attend_chunk,
        (jnp.zeros((groups, per, n_q, v.shape[-1]), jnp.float32),
         jnp.full((groups, per, n_q), _NEG, jnp.float32),
         jnp.zeros((groups, per, n_q), jnp.float32)))
    return acc / den[..., None]


def block_sparse_attention(q, k, v, lengths, rows_at, *, scale: float,
                           dense_len: int, top: int, block: int, window: int,
                           stride: int, init: int, local: int,
                           q_block: int = 128, chunk: int = 2048,
                           scope: str = ""):
    """q (B, L, G, Hg, d), k (B, L, G, d), v (B, L, G, dv), lengths (B,),
    rows_at (B, P) → (out (B, L, G, Hg, dv) in ``v.dtype``, n_keys (B,
    L, G) int32: the keys each (query, group) saw, n_visible (B, L)
    int32: the compressed keys visible to each query, blocks (B, P, G,
    M) bool: the blocks of the queries named in ``rows_at``). ``L`` must
    be a multiple of ``q_block`` and of ``block`` (or smaller than
    ``q_block``)."""
    b_sz, length, groups, per, _ = q.shape
    d_v = v.shape[-1]
    q_block, chunk = block_and_chunk(length, q_block, chunk)
    if length % q_block or length % block:
        raise ValueError(f"length {length} is not a multiple of {q_block} "
                         f"and {block}")
    n_blk, n_key_blocks = length // q_block, length // block
    selecting = length >= dense_len           # else no route here selects
    s_pos = jnp.arange(length, dtype=jnp.int32)
    pick = dict(top=top, block=block, init=init, local=local)
    sizes = dict(scale=scale, window=window, stride=stride, block=block)
    if selecting:
        with jax.named_scope(scope + ".compress"):
            kc = jax.vmap(lambda x: compress_keys(x, window, stride))(k)

    def blocks_of(b, q_rows, t_pos):
        """(G, Q, M) bool and (Q,) visible compressed keys."""
        causal = jnp.broadcast_to(
            jnp.arange(n_key_blocks)[None, None, :] <= (t_pos // block)[
                None, :, None], (groups, len(t_pos), n_key_blocks))
        if not selecting:
            return causal, compressed_visible(
                t_pos, length // stride, window, stride).sum(-1).astype(
                    jnp.int32)
        with jax.named_scope(scope + ".select"):
            scores, n_vis = block_scores(q_rows, kc[b], t_pos, **sizes)
            chosen = choose_blocks(scores, t_pos, **pick)
        return jnp.where(lengths[b] >= dense_len, chosen, causal), n_vis

    def one(n):
        b, i = n // n_blk, n % n_blk
        t_pos = i * q_block + jnp.arange(q_block, dtype=jnp.int32)
        qb = jax.lax.dynamic_slice_in_dim(q[b], i * q_block, q_block, 0)
        chosen, n_vis = blocks_of(b, qb, t_pos)
        keys = (jnp.repeat(chosen, block, axis=-1)
                & (s_pos[None, None, :] <= t_pos[None, :, None]))
        with jax.named_scope(scope + ".attend"):
            out = _attend_group_xla(
                qb, k, v, keys, b, ((i + 1) * q_block + chunk - 1) // chunk,
                chunk=chunk, scale=scale)
        return (out.transpose(2, 0, 1, 3).astype(v.dtype),
                keys.sum(-1).astype(jnp.int32).T, n_vis)

    out, n_keys, n_vis = jax.lax.map(one, jnp.arange(b_sz * n_blk))

    def named(b, at):
        return blocks_of(b, q[b][at], at)[0].transpose(1, 0, 2)

    blocks = jax.vmap(named)(jnp.arange(b_sz), rows_at)
    return (out.reshape(b_sz, length, groups, per, d_v),
            n_keys.reshape(b_sz, length, groups),
            n_vis.reshape(b_sz, length), blocks)

