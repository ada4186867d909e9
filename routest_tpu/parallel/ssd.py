"""A state-space scan whose decay depends on the data, token by token
and head by head (the Mamba-2 recurrence), run a chunk of tokens at a
time, and the causal depthwise convolution that feeds it.

Per head h of width P, with a state of N per group of heads (head h
reads the B and C of group :func:`b_c_group`), ``dt_t > 0`` and ``A_h <
0``:

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t^T B_t      (P x N, float32, S_{-1} = 0)
    y_t = S_t C_t + D_h x_t

:func:`recurrent` is that, token by token (the oracle of the tests).
:func:`chunked` is its exact rewrite over chunks of C tokens (the state
space duality): with ``a_j = dt_j A``, ``s_i`` their cumulative sum
inside the chunk up to and including i, and ``S_in`` the state before
the chunk's first token,

    y_i   = sum_{j<=i} exp(s_i - s_j) (C_i . B_j) dt_j x_j
            + exp(s_i) C_i S_in^T + D x_i
    S_out = exp(s_last) S_in + sum_j exp(s_last - s_j) dt_j x_j^T B_j

Every exponent is a difference of float32 cumulative sums that is at
most 0 (``a <= 0``; rounding is clamped), so no ratio of exponentials
is formed and nothing overflows: a fast head's decays underflow to 0,
which is their value to float32. A padded position has ``dt = 0`` (the
caller's :func:`live_step`): it decays nothing and writes nothing, so
the state carried out of the last chunk is the state at the route's
last real token, whatever padding follows.

Dtypes: x, B and C in the activations' dtype (bfloat16 in the scorer);
``dt``, the decays and the state float32. Every product takes the
activations' dtype and accumulates in float32: the score product ``C
B^T``, the decayed scores (float32, cast) times x, ``C S^T`` with the
state cast for that product alone, and the state's update from ``x``
scaled by its float32 weights. The state itself is only ever summed in
float32.

Two forms, chosen by :func:`ssd_path` from shapes, dtype and backend
alone:

- ``"fused"``: ONE Pallas kernel a layer and step (:func:`_scan_fused`,
  ``ssd_scan_step`` in a device trace). Its grid is (route, group of
  ``HEAD_TILE`` heads inside one B/C group, chunk); the chunk axis runs
  in order and each head's P x N float32 state stays in VMEM across a
  route's chunks (the state's output block, written once a route). It
  reads x, B and C where the convolution wrote them, one array of the
  lanes ``[x (H P) | B (G N) | C (G N)]``: no slice of it is copied.
- ``"xla"``: a ``lax.scan`` over chunks, all routes and heads batched in
  a step (the CPU, float32, toy widths; the kernel's oracle).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL = "ssd_scan_step"
HEAD_TILE = 8               # heads of one grid step of the kernel
_VMEM_BYTES = 64 * 2 ** 20
_HIGHEST = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))          # a . b^T
_TN = (((0,), (0,)), ((), ()))          # a^T . b


def b_c_group(head, heads: int, groups: int):
    """The group whose B and C head ``head`` reads: heads ``0 .. H/G -
    1`` the first. ``head`` may be traced (the kernel's index maps)."""
    return head // (heads // groups)


def causal_conv(x, w, b):
    """The causal depthwise convolution of ``w.shape[0]`` taps over the
    length: x (B, L, C), w (K, C), b (C,) → (B, L, C) float32, position
    t the sum over k of ``w[k] * x[t - K + 1 + k]`` plus ``b`` (a
    position before the route's first reads 0)."""
    k = w.shape[0]
    xf = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    length = x.shape[1]
    wf = w.astype(jnp.float32)
    out = b.astype(jnp.float32)
    for i in range(k):
        out = out + wf[i] * xf[:, i:i + length]
    return out


def live_step(dt, live):
    """``dt`` where the position is a route's real token, 0 elsewhere: a
    padded position neither decays nor writes the state."""
    return jnp.where(live[..., None], dt, 0.0)


def log_decay(dt, a):
    """``dt_t A_h`` (B, L, H) float32 from dt (B, L, H) and A (H,): the
    log of the decay at each token, 0 where ``dt`` is."""
    return dt * a.astype(jnp.float32)


def chunk_count(length: int, chunk: int) -> int:
    """Steps of the scan for a route padded to ``length``."""
    return -(-length // min(chunk, length))


def ssd_path(heads: int, head_dim: int, state: int, dtype,
             backend: str = "", *, groups: int = 1,
             chunk: int = 128) -> str:
    """The form :func:`scan` runs at these shapes: ``"fused"`` on a TPU
    where bfloat16 arrays tile for the kernel (a group's heads whole
    tiles of ``HEAD_TILE``, head and state widths whole lanes, the x
    lanes whole state widths, the chunk whole lanes), ``"xla"``
    everywhere else. ``backend`` defaults to JAX's own."""
    tiles = (heads % groups == 0 and (heads // groups) % HEAD_TILE == 0
             and head_dim % 128 == 0 and state % 128 == 0
             and (heads * head_dim) % state == 0 and chunk % 128 == 0)
    on_tpu = (backend or jax.default_backend()) == "tpu"
    return ("fused" if on_tpu and tiles and jnp.dtype(dtype) == jnp.bfloat16
            else "xla")


def recurrent(x, dt, a, b, c, d):
    """The recurrence as written, one token a step: x (B, L, H, P), dt
    (B, L, H), a (H,), b and c (B, L, G, N), d (H,) → (y (B, L, H, P)
    float32, state (B, H, P, N) float32 after the last token). Padded
    positions must come with ``dt = 0``. Everything float32 at
    ``highest``."""
    f32 = jnp.float32
    b_sz, length, heads, _ = x.shape
    groups = b.shape[2]
    grp = np.asarray([b_c_group(h, heads, groups) for h in range(heads)])
    a, d = a.astype(f32), d.astype(f32)

    def step(s, row):
        x_t, dt_t, b_t, c_t = row            # (B, H, P), (B, H), (B, G, N)
        b_h, c_h = b_t[:, grp], c_t[:, grp]   # (B, H, N)
        s = (jnp.exp(dt_t * a)[..., None, None] * s
             + (dt_t[..., None] * x_t)[..., None] * b_h[:, :, None, :])
        y = jnp.einsum("bhpn,bhn->bhp", s, c_h, precision=_HIGHEST)
        return s, y + d[None, :, None] * x_t

    xs = tuple(jnp.moveaxis(v.astype(f32), 1, 0) for v in (x, dt, b, c))
    state, y = jax.lax.scan(step, jnp.zeros(
        (b_sz, heads, x.shape[-1], b.shape[-1]), f32), xs)
    return jnp.moveaxis(y, 0, 1), state


def chunked(x, dt, a, b, c, d, *, chunk: int = 128,
            scope: str = "") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The same numbers chunk by chunk, in XLA: → (y (B, L, H, P) in
    ``x.dtype``, state (B, H, P, N) float32 at each route's last real
    token). ``L`` must be a multiple of ``chunk`` (or smaller than it).
    Outputs at padded positions are finite and mean nothing."""
    f32 = jnp.float32
    b_sz, length, heads, p = x.shape
    groups, n = b.shape[2:]
    cc = min(chunk, length)
    if length % cc:
        raise ValueError(f"length {length} is not a multiple of {cc}")
    per = heads // groups
    # the B and C that each run of ``per`` heads reads, by group
    order = np.asarray([b_c_group(g * per, heads, groups)
                        for g in range(groups)])
    b, c = b[:, :, order], c[:, :, order]
    dt = dt.astype(f32)
    log_a = log_decay(dt, a)
    n_c = length // cc
    idx = jnp.arange(cc)
    causal = idx[None, :] <= idx[:, None]                     # (i, j)

    def by_chunk(v):            # (B, L, ...) → (n, B, C, ...)
        return jnp.moveaxis(v.reshape((b_sz, n_c, cc) + v.shape[2:]), 1, 0)

    def step(s, row):
        x_c, dt_c, la_c, b_c, c_c = row
        x_g = x_c.reshape(b_sz, cc, groups, per, p)
        with jax.named_scope("intra"):
            cum = jnp.cumsum(la_c, axis=1)                    # (B, C, H)
            cum_g = cum.reshape(b_sz, cc, groups, per)
            diff = cum_g[:, :, None] - cum_g[:, None, :]      # (B, i, j, G, h)
            decay = jnp.where(causal[None, :, :, None, None],
                              jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
            scores = jnp.einsum("bign,bjgn->bijg", c_c, b_c,
                                preferred_element_type=f32)
            m = (scores[..., None] * decay
                 * dt_c.reshape(b_sz, 1, cc, groups, per))
            y = jnp.einsum("bijgh,bjghp->bighp", m.astype(x.dtype), x_g,
                           preferred_element_type=f32)
        with jax.named_scope("state"):
            s_g = s.reshape(b_sz, groups, per, p, n)
            y = y + jnp.exp(cum_g)[..., None] * jnp.einsum(
                "bign,bghpn->bighp", c_c, s_g.astype(x.dtype),
                preferred_element_type=f32)
            last = cum[:, -1]                                 # (B, H)
            w = (jnp.exp(jnp.minimum(last[:, None] - cum, 0.0))
                 * dt_c).reshape(b_sz, cc, groups, per)
            xs = (x_g.astype(f32) * w[..., None]).astype(x.dtype)
            s = (jnp.exp(last)[..., None, None] * s
                 + jnp.einsum("bjghp,bjgn->bghpn", xs, b_c,
                              preferred_element_type=f32).reshape(s.shape))
        y = y.reshape(b_sz, cc, heads, p) + d.astype(f32)[:, None] \
            * x_c.astype(f32)
        return s, y.astype(x.dtype)

    with jax.named_scope(scope):
        state, y = jax.lax.scan(
            step, jnp.zeros((b_sz, heads, p, n), f32),
            tuple(by_chunk(v) for v in (x, dt, log_a, b, c)))
    return jnp.moveaxis(y, 0, 1).reshape(b_sz, length, heads, p), state


def scan(xbc, dt, a, d, *, heads: int, groups: int, state: int,
         chunk: int = 128, scope: str = ""):
    """The mixer's scan over what the convolution wrote: xbc (B, L, H P
    + 2 G N) with the lanes ``[x | B | C]``, dt (B, L, H) float32 (0 at
    padded positions), a and d (H,) → (y (B, L, H P) in ``xbc.dtype``,
    state (B, H, P, N) float32 at each route's last real token), by
    the form :func:`ssd_path` names."""
    b_sz, length, width = xbc.shape
    p = (width - 2 * groups * state) // heads
    path = ssd_path(heads, p, state, xbc.dtype, groups=groups, chunk=chunk)
    with jax.named_scope(scope):
        if path == "fused":
            cc = min(chunk, length)
            if length % cc:
                raise ValueError(f"length {length} is not a multiple of {cc}")
            la = log_decay(dt.astype(jnp.float32), a)
            cum = jnp.cumsum(la.reshape(b_sz, length // cc, cc, heads),
                             axis=2).reshape(b_sz, length, heads)
            tiles = jnp.asarray([b_c_group(g * HEAD_TILE, heads, groups)
                                 for g in range(heads // HEAD_TILE)],
                                jnp.int32)
            return _scan_fused(
                xbc, dt.astype(jnp.float32).transpose(0, 2, 1),
                cum.transpose(0, 2, 1), d.astype(jnp.float32), tiles,
                heads=heads, groups=groups, state=state, chunk=cc)
    hp, gn = heads * p, groups * state
    y, s = chunked(
        xbc[..., :hp].reshape(b_sz, length, heads, p), dt, a,
        xbc[..., hp:hp + gn].reshape(b_sz, length, groups, state),
        xbc[..., hp + gn:].reshape(b_sz, length, groups, state), d,
        chunk=chunk, scope=scope)
    return y.reshape(b_sz, length, hp), s


# ── the kernel ───────────────────────────────────────────────────────


def _scan_kernel(d_ref, grp_ref, x_ref, b_ref, c_ref, dt_ref, cum_ref,
                 y_ref, s_ref):
    """One grid step: the chunk of one route for a tile of heads that
    share their B and C. ``s_ref`` (the state's output block, the same
    block for every chunk of the route) holds the heads' states from
    chunk to chunk."""
    f32 = jnp.float32
    g, ci = pl.program_id(1), pl.program_id(2)
    n_c = x_ref.shape[0]
    ht = dt_ref.shape[0]
    p = x_ref.shape[1] // ht

    @pl.when(ci == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    c_blk = c_ref[...]
    scores = jax.lax.dot_general(c_blk, b_ref[...], _NT,
                                 preferred_element_type=f32)   # (i, j)
    row = jax.lax.broadcasted_iota(jnp.int32, (n_c, n_c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n_c, n_c), 1)
    causal = col <= row
    at_end = jax.lax.broadcasted_iota(jnp.int32, (1, n_c), 1) == n_c - 1
    cum_t = cum_ref[...].T                                       # (C, HT)
    dt_t = dt_ref[...].T
    for h in range(ht):
        cum_j = cum_ref[h:h + 1, :]                             # (1, C)
        cum_i = cum_t[:, h:h + 1]                               # (C, 1)
        last = jnp.sum(jnp.where(at_end, cum_j, 0.0))           # scalar
        decay = jnp.where(causal, jnp.exp(jnp.minimum(cum_i - cum_j, 0.0)),
                          0.0)
        m = scores * decay * dt_ref[h:h + 1, :]
        x_h = x_ref[:, h * p:(h + 1) * p]                       # (C, P)
        s_h = s_ref[h]                                          # (P, N)
        y = jnp.dot(m.astype(x_h.dtype), x_h, preferred_element_type=f32)
        y = y + jnp.exp(cum_i) * jax.lax.dot_general(
            c_blk, s_h.astype(x_h.dtype), _NT, preferred_element_type=f32)
        y = y + d_ref[g * ht + h] * x_h.astype(f32)
        y_ref[:, h * p:(h + 1) * p] = y.astype(y_ref.dtype)
        w = jnp.exp(jnp.minimum(last - cum_i, 0.0)) * dt_t[:, h:h + 1]
        xs = (x_h.astype(f32) * w).astype(x_h.dtype)
        s_ref[h] = jnp.exp(last) * s_h + jax.lax.dot_general(
            xs, b_ref[...], _TN, preferred_element_type=f32)


@functools.partial(jax.jit, static_argnames=("heads", "groups", "state",
                                             "chunk", "interpret"))
def _scan_fused(xbc, dt, cum, d, tile_groups, *, heads: int, groups: int,
                state: int, chunk: int, interpret: bool = False):
    """Every chunk of every route for every tile of heads, as one
    kernel: xbc (B, L, H P + 2 G N), dt and cum (B, H, L) float32 (the
    step and its cumulative ``dt A`` inside each chunk), d (H,),
    ``tile_groups`` (H / HEAD_TILE,) int32 the group of B and C each
    tile of heads reads, prefetched with d → (y (B, L, H P) in
    ``xbc.dtype``, state (B, H, P, N) float32). Jitted, so that the
    layers of a step program share one trace and lowering of it."""
    b_sz, length, width = xbc.shape
    p = (width - 2 * groups * state) // heads
    x_lanes = heads * p // state          # B's first lane block of N

    def rows(b, g, c, d_ref, grp_ref):
        return b, c, g

    def heads_of(b, g, c, d_ref, grp_ref):
        return b, g, c

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b_sz, heads // HEAD_TILE, length // chunk),
        in_specs=[
            pl.BlockSpec((None, chunk, HEAD_TILE * p), rows),
            pl.BlockSpec((None, chunk, state),
                         lambda b, g, c, d_ref, grp_ref: (
                             b, c, x_lanes + grp_ref[g])),
            pl.BlockSpec((None, chunk, state),
                         lambda b, g, c, d_ref, grp_ref: (
                             b, c, x_lanes + groups + grp_ref[g])),
            pl.BlockSpec((None, HEAD_TILE, chunk), heads_of),
            pl.BlockSpec((None, HEAD_TILE, chunk), heads_of)],
        out_specs=[
            pl.BlockSpec((None, chunk, HEAD_TILE * p), rows),
            pl.BlockSpec((None, HEAD_TILE, p, state),
                         lambda b, g, c, d_ref, grp_ref: (b, g, 0, 0))])
    return pl.pallas_call(
        _scan_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b_sz, length, heads * p),
                                        xbc.dtype),
                   jax.ShapeDtypeStruct((b_sz, heads, p, state),
                                        jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        name=KERNEL,
        interpret=interpret,
    )(d, tile_groups, xbc, xbc, xbc, dt, cum)
