"""Decayed linear attention: a recurrence over a per-head state, run a
chunk of tokens at a time.

Per head h with decay ``lam_h`` in (0, 1), keys and queries of width d
and values of width dv:

    S_t = lam_h * S_{t-1} + k_t^T v_t          (d x dv, float32, S_{-1} = 0)
    o_t = scale * q_t S_t = scale * sum_{s<=t} lam_h^(t-s) (q_t . k_s) v_s

:func:`recurrent` is that, token by token (the oracle of the tests).
:func:`chunked` is its exact rewrite over chunks of C tokens: with
``S_in`` the state before the chunk's first token and i, j indices
inside the chunk,

    o_i   = scale * ( lam^(i+1) q_i S_in + sum_{j<=i} lam^(i-j) (q_i.k_j) v_j )
    S_out = lam^e S_in + sum_{j<e} lam^(e-1-j) k_j^T v_j

where ``e`` is the number of the route's real tokens in the chunk (C in
a whole chunk; 0 past the route's end, where the state stands still).
So the state carried out of the last chunk is the state at the route's
last real token, whatever padding follows: padded positions write
nothing and decay nothing. Every decay power is ``exp(n * log lam)``
with ``n >= 0`` — no ``lam^i / lam^j`` — so nothing overflows at the
fastest head (lam = 0.55: powers underflow to 0, which is their value
to float32) nor loses the slowest (lam = 0.997).

Dtypes: q, k, v in the activations' dtype (bfloat16 in the scorer);
the intra-chunk score product and the value product take that dtype
and accumulate in float32, the decay matrix is float32 and multiplies
the float32 scores; the state is float32 and both products that touch
it (``q S_in`` and ``k^T v``) are float32 at ``highest`` precision (a
quarter of the mixer's products: the state is what 184 chunks build
on). One path, XLA: a ``lax.scan`` over chunks with all routes and
heads batched in a step.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def decay_slopes(heads: int) -> jnp.ndarray:
    """``s_h = 2^(-8 h / heads)``, h = 1..heads: the published slopes of
    lightning attention."""
    return 2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32)
                   / heads)


def log_decay(heads: int, layer: int, n_layers: int) -> jnp.ndarray:
    """``log lam_h`` of the PUBLISHED layer ``layer`` of ``n_layers``:
    ``-s_h * (1 - layer / (n_layers - 1) + 1e-5)``, (heads,) float32."""
    return -decay_slopes(heads) * (1.0 - layer / (n_layers - 1) + 1e-5)


def chunk_count(length: int, chunk: int) -> int:
    """Steps of the scan for a route padded to ``length``."""
    return -(-length // min(chunk, length))


def recurrent(q, k, v, log_lam, lengths, scale: float):
    """The recurrence as written, one token a step: q, k (B, L, H, d),
    v (B, L, H, dv), log_lam (H,), lengths (B,) → (out (B, L, H, dv)
    float32, state (B, H, d, dv) float32 at each route's last token).
    Everything float32 at ``highest``."""
    f32 = jnp.float32
    lam = jnp.exp(log_lam.astype(f32))[None, :, None, None]

    def step(s, x):
        q_t, k_t, v_t, live = x
        new = lam * s + jnp.einsum("bhd,bhe->bhde", k_t, v_t,
                                   precision=_HIGHEST)
        s = jnp.where(live[:, None, None, None], new, s)
        return s, scale * jnp.einsum("bhd,bhde->bhe", q_t, s,
                                     precision=_HIGHEST)

    b_sz, length, heads, d = q.shape
    live = jnp.arange(length)[:, None] < lengths[None, :]
    xs = (jnp.moveaxis(q.astype(f32), 1, 0), jnp.moveaxis(k.astype(f32), 1, 0),
          jnp.moveaxis(v.astype(f32), 1, 0), live)
    state, out = jax.lax.scan(
        step, jnp.zeros((b_sz, heads, d, v.shape[-1]), f32), xs)
    return jnp.moveaxis(out, 0, 1), state


def chunked(q, k, v, log_lam, lengths, scale: float, chunk: int = 256,
            scope: str = "") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The same numbers chunk by chunk: → (out (B, L, H, dv) in
    ``v.dtype``, state (B, H, d, dv) float32 at each route's last real
    token). ``L`` must be a multiple of ``chunk`` (or smaller than it).
    Outputs at padded positions are finite and mean nothing."""
    f32 = jnp.float32
    b_sz, length, heads, d = q.shape
    d_v = v.shape[-1]
    c = min(chunk, length)
    if length % c:
        raise ValueError(f"length {length} is not a multiple of {c}")
    n = length // c
    log_lam = log_lam.astype(f32)
    idx = jnp.arange(c, dtype=f32)
    # (H, C, C): lam^(i-j) where j <= i, else 0
    gap = idx[:, None] - idx[None, :]
    intra = jnp.where(gap >= 0,
                      jnp.exp(jnp.maximum(gap, 0.0)[None]
                              * log_lam[:, None, None]), 0.0)
    into = jnp.exp((idx + 1.0)[None, :] * log_lam[:, None])     # (H, C)

    def by_chunk(x):            # (B, L, H, w) → (n, B, H, C, w)
        return x.reshape(b_sz, n, c, heads, -1).transpose(1, 0, 3, 2, 4)

    def step(s_in, x):
        q_c, k_c, v_c, c0 = x
        with jax.named_scope(scope + ".intra"):
            a = jnp.einsum("bhid,bhjd->bhij", q_c, k_c,
                           preferred_element_type=f32) * intra[None]
            o = jnp.einsum("bhij,bhje->bhie", a.astype(v_c.dtype), v_c,
                           preferred_element_type=f32)
        with jax.named_scope(scope + ".state"):
            o = o + into[None, :, :, None] * jnp.einsum(
                "bhid,bhde->bhie", q_c.astype(f32), s_in, precision=_HIGHEST)
            # e: the route's real tokens in this chunk
            e = jnp.clip(lengths - c0, 0, c).astype(f32)        # (B,)
            left = e[:, None] - 1.0 - idx[None, :]              # (B, C)
            w = jnp.where(left[:, None, :] >= 0,
                          jnp.exp(jnp.maximum(left, 0.0)[:, None, :]
                                  * log_lam[None, :, None]), 0.0)
            s_out = (jnp.exp(e[:, None] * log_lam[None, :])[..., None, None]
                     * s_in
                     + jnp.einsum("bhjd,bhje->bhde",
                                  k_c.astype(f32) * w[..., None],
                                  v_c.astype(f32), precision=_HIGHEST))
        return s_out, (scale * o).astype(v.dtype)

    with jax.named_scope(scope):
        state, out = jax.lax.scan(
            step, jnp.zeros((b_sz, heads, d, d_v), f32),
            (by_chunk(q), by_chunk(k), by_chunk(v),
             jnp.arange(n, dtype=jnp.int32) * c))
    out = out.transpose(1, 0, 3, 2, 4).reshape(b_sz, length, heads, d_v)
    return out, state

