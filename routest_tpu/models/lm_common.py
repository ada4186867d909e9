"""What the route-sequence language models share (``route_lm.RouteLM``,
``route_lm_sala.RouteLMSala``, ``route_lm_kexaone.RouteLMKExaone`` and
``route_lm_gigachat.RouteLMGigaChat``): the norm, the rotary embedding
and YaRN's frequency table for it, the float32-accumulating product,
the chunked next-arc head, the prediction module's column, the settled
stream, the row-wise map and the expert layers' pass counts."""

from __future__ import annotations

import math
from typing import Callable, Dict, List, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, w, eps: float):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def rope(x, pos, theta: float, inv_freq=None):
    """Rotate-half RoPE over the last axis; ``pos`` has the shape of
    ``x`` less its last axis, or broadcasts to it from the left. The
    pair i turns by ``pos * theta ** (-i / half)``, or by ``pos *
    inv_freq[i]`` where a table is given (:func:`yarn_inv_freq`)."""
    half = x.shape[-1] // 2
    if inv_freq is None:
        freq = jnp.float32(theta) ** (-jnp.arange(half, dtype=jnp.float32)
                                      / half)
    else:
        freq = jnp.asarray(inv_freq, jnp.float32)
    ang = pos.astype(jnp.float32)[..., None] * freq
    ang = ang.reshape(pos.shape + (1,) * (x.ndim - 1 - pos.ndim) + (half,))
    xf = x.astype(jnp.float32)
    a, b = xf[..., :half], xf[..., half:]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def yarn_inv_freq(dim: int, theta: float, scaling: Mapping) -> np.ndarray:
    """YaRN's frequencies of the ``dim / 2`` rotary pairs, float64:
    pair i keeps ``theta ** (-2 i / dim)`` where it turns more than
    ``beta_fast`` times over the ``original_max_position_embeddings``,
    takes ``1 / factor`` of it where it turns fewer than ``beta_slow``
    times, and a linear blend between the two pairs that those counts
    name (the lower rounded down, the higher up)."""
    base, original = float(theta), scaling["original_max_position_embeddings"]

    def pair_turning(turns):
        return (dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(pair_turning(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair_turning(scaling["beta_slow"])), dim - 1)
    plain = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low)
                   / ((high - low) or 0.001), 0.0, 1.0)
    return plain * (1.0 - ramp) + plain / scaling["factor"] * ramp


def yarn_mscale(factor: float, coefficient: float) -> float:
    """``0.1 * coefficient * ln(factor) + 1``; 1 where nothing is
    stretched."""
    return 1.0 if factor <= 1 else 0.1 * coefficient * math.log(factor) + 1.0


def dot32(x, w):
    return jnp.matmul(x, w, preferred_element_type=jnp.float32)


def next_arc_head(params, h, ids, lengths, rows_at, eps: float,
                  logit_scale: float = 1.0, shift: int = 1,
                  scope: str = "lm.head"):
    """``params["final_norm"]`` and ``params["head"]`` over the trunk's
    output h (B, L, d) → next_logit (B, L) (the logit of ids[t + shift];
    0 where there is none), lse (B, L), rows (B, P, V): the whole logit
    rows at ``rows_at``. The logits are ``logit_scale`` times the
    product; they exist 4,096 tokens at a time (or the largest divisor
    of the tokens below that). ``shift`` 2 is a prediction module's
    head: the arc after next."""
    b_sz, length, d = h.shape
    x = rms_norm(h, params["final_norm"], eps)
    nxt = jnp.concatenate([ids[:, shift:], ids[:, :shift]], 1).reshape(-1)
    tokens = b_sz * length
    rows = math.gcd(tokens, 4096)

    def chunk(i):
        xc = jax.lax.dynamic_slice_in_dim(x.reshape(tokens, d), i * rows,
                                          rows, 0)
        nc = jax.lax.dynamic_slice_in_dim(nxt, i * rows, rows, 0)
        logits = dot32(xc, params["head"])
        if logit_scale != 1.0:
            logits = logits * logit_scale
        return (jnp.take_along_axis(logits, nc[:, None], -1)[:, 0],
                jax.nn.logsumexp(logits, axis=-1))

    with jax.named_scope(scope):
        next_logit, lse = jax.lax.map(chunk, jnp.arange(tokens // rows))
        named = jnp.take_along_axis(x, rows_at[..., None], axis=1)
        full_rows = dot32(named, params["head"])
        if logit_scale != 1.0:
            full_rows = full_rows * logit_scale
    has_next = (jnp.arange(length)[None, :] + shift) < lengths[:, None]
    next_logit = jnp.where(has_next, next_logit.reshape(b_sz, length), 0.0)
    return next_logit, lse.reshape(b_sz, length), full_rows


def prediction_column(params, h, ids, next_ids, lengths, rows_at,
                      eps: float, block: Callable) -> Dict:
    """A prediction module's likelihood column, the arc AFTER next:
    ``u_t = W_p [RMSNorm(h_t) ; RMSNorm(E[next_ids_t])]`` from the
    trunk's last hidden state h (B, L, d) and the trunk's embedding,
    ``block(layer params, u, valid)`` (one residual block of the
    model's own kind over a route's n - 1 positions), then the trunk's
    head behind the module's own norm. ``params``: the model's, with
    ``mtp`` = ``h_norm``, ``e_norm``, ``w_proj`` (2d, d), ``layer``,
    ``final_norm``. → ``mtp_next_logit`` (the logit of ids[t + 2]) and
    ``mtp_lse`` (1, B, L), ``mtp_loglik`` (1, B): a leading axis of one
    entry a module."""
    m, d, dt = params["mtp"], h.shape[-1], h.dtype
    at = jnp.arange(h.shape[1])[None, :]
    with jax.named_scope("lm.mtp.proj"):
        e = params["embed"][next_ids].astype(dt)
        u = (dot32(rms_norm(h, m["h_norm"], eps), m["w_proj"][:d])
             + dot32(rms_norm(e, m["e_norm"], eps),
                     m["w_proj"][d:])).astype(dt)
    h2 = block(m["layer"], u, at + 1 < lengths[:, None])
    logit2, lse2, _ = next_arc_head(
        {"final_norm": m["final_norm"], "head": params["head"]}, h2,
        ids, lengths, rows_at, eps, shift=2, scope="lm.mtp.head")
    return {"mtp_next_logit": logit2[None], "mtp_lse": lse2[None],
            "mtp_loglik": jnp.sum(jnp.where(
                at + 2 < lengths[:, None], logit2 - lse2, 0.0), -1)[None]}


def settled(h):
    """The stream written out where it is updated: left to itself XLA
    keeps every block's addend and sums them anew at each use, so all
    of them (368 MiB each at 47k tokens of width 4,096) stay live to
    the end."""
    return jax.lax.optimization_barrier(h)


def map_rows(fn, x, block: int):
    """``fn`` over blocks of ``block`` rows of x (T, d), one after the
    other, the results joined: what ``fn(x)`` gives where ``fn`` works
    row by row, without its intermediates for all T rows at once. T is
    padded to whole blocks (the padding's rows are dropped)."""
    t = x.shape[0]
    if t <= block:
        return fn(x)
    n = -(-t // block)
    xp = jnp.pad(x, ((0, n * block - t), (0, 0)))
    out = jax.lax.map(fn, xp.reshape(n, block, -1))
    return out.reshape(n * block, -1)[:t]


def expert_pass_counts(counts: Sequence, assignments: float,
                       row_tile: int = 1) -> List[Tuple]:
    """(family, labels, value) of one pass's expert layers for the
    scorer's gauges and counters. ``counts``: for each step the tokens
    every held expert got in each expert layer, (layers, experts_held);
    ``assignments``: the (token, slot) choices the pass's real tokens
    made in those layers, on held experts or not; ``row_tile``: the rows
    an expert's part of the grouped product's layout is rounded up to
    (``parallel/expert.row_tile_of``)."""
    import numpy as np

    from routest_tpu.parallel.expert import rows_visited

    per_layer = np.concatenate([np.asarray(c, np.int64) for c in counts],
                               0)                    # (steps·layers, E)
    visited = float(rows_visited(per_layer, row_tile).sum())
    per_layer = per_layer.astype(np.float64)
    means = per_layer.mean(1)
    busy = means > 0
    out = [("expert_tokens", {"stat": "max"}, per_layer.max()),
           ("expert_tokens", {"stat": "mean"}, per_layer.mean())]
    if busy.any():
        out.append(("load", {}, float(np.mean(
            per_layer[busy].max(1) / means[busy]))))
    out.append(("held_share", {}, per_layer.sum() / max(1, assignments)))
    out += [("expert_rows", {"kind": "visited"}, visited),
            ("expert_rows", {"kind": "held"}, float(per_layer.sum()))]
    return out
