"""Plain reference of the route-sequence language model (catalog name
``dots3-note-prev``): float32 ``jax.numpy`` at ``highest`` matmul
precision, one route at a time, the full (t, s) score matrix of every
attention with masks, no kernels, no length ladder, no batching.

Equations (d = hidden_size, pre-norm residual, RMSNorm eps from the
config; positions are a token's index within its own route):

- ``h = embed[ids]``; per layer ``h += Attn(norm(h))``,
  ``h += FFN(norm(h))``; ``logits = norm(h) @ head``.
- Latent attention (both kinds): ``c_q = s_q * RMSNorm(x W_dq)`` with
  ``s_q = sqrt(d / r_q)``; ``[q_nope ; q_rope]_h = c_q W_uq,h``;
  ``[c_kv ; k_rope] = x W_dkv``, ``c_kv <- s_kv * RMSNorm(c_kv)``,
  ``s_kv = sqrt(d / r_kv)``, ``k_rope`` shared by all heads;
  ``[k_nope ; v]_h = c_kv W_ukv,h``; RoPE (rotate-half) on ``q_rope``
  and ``k_rope``; score ``(q_nope.k_nope + q_rope.k_rope) / sqrt(d_nope
  + d_rope)``; head h's output times ``sigmoid(x W_g)_h``; then ``W_o``.
- Full layer: the key set of query t is the ``index_topk`` largest
  ``I(t, s) = sum_j w_j(t) relu(qI_j(t).kI(s))`` over ``s <= t`` (every
  ``s <= t`` while ``t < index_topk``; equal scores go to the lower s),
  ``qI_j = c_q W_Iq,j`` and ``kI = LayerNorm(x W_Ik)`` with RoPE on the
  first ``qk_rope_head_dim`` of their width, ``w = (x W_w) *
  index_n_heads**-0.5 * index_head_dim**-0.5``.
- Sliding layer: the ``swa_*`` sizes, keys ``t - window + 1 <= s <= t``.
- MoE: ``p = sigmoid(x W_r)``; chosen = top-k of ``p + b``; ``g_e = p_e /
  sum_chosen p`` times ``routed_scaling_factor``; ``y = sum_chosen g_e
  E_e(x) + E_shared(x)``, ``E(x) = (silu(x W_gate) * x W_up) W_down``.
  ``share = (first, count)`` keeps only the terms of the experts
  ``first .. first + count - 1``; ``shared=False`` leaves the shared
  expert out (so that the parts of all shares add up to the layer).

The parameters are the artifact's pytree (``routest_tpu.train.checkpoint
.save_route_lm`` writes it; the layout is in ``PARAM_LAYOUT``); the
expert arrays hold the experts ``first .. first + count - 1`` of the
share they were drawn for, the embedding and the head the held rows of
the vocabulary. ``precision="fp8"`` is the control: the operands of
every product rounded to float8 (e4m3, scaled per tensor), as the next
precision below the configuration's bfloat16 would compute it.

Memory: :class:`Blocks` says how many queries, heads, rows and keys are
computed at a time, so that a route of 26k arcs fits the device and few
shapes compile; none of them changes a number.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

PARAM_LAYOUT = """
embed (V_held, d); head (d, V_held); final_norm (d,)
layers[l]: attn_norm (d,), ffn_norm (d,),
  attn: w_dq (d, r_q), q_norm (r_q,), w_uq (r_q, H*(d_nope+d_rope)),
        w_dkv (d, r_kv+d_rope), kv_norm (r_kv,),
        w_ukv (r_kv, H*(d_nope+d_v)), w_gate (d, H), w_o (H*d_v, d),
        full layers: idx: w_q (r_q, Hi*di), w_k (d, di), k_norm_w (di,),
                          k_norm_b (di,), w_w (d, Hi)
  ffn (dense): w_gate (d, F), w_up (d, F), w_down (F, d)
  ffn (moe): router (d, E), bias (E,), w_gate (E_held, d, m),
             w_up (E_held, d, m), w_down (E_held, m, d),
             shared: w_gate (d, m_s), w_up (d, m_s), w_down (m_s, d)
"""

LN_EPS = 1e-6


def attention_sizes(cfg: Dict, kind: str) -> Dict:
    """The sizes of one kind of attention layer, from the published
    keys (``swa_*`` for ``sliding_attention``)."""
    d = cfg["hidden_size"]
    if kind == "sliding_attention":
        a = {"heads": cfg["swa_num_attention_heads"],
             "d_nope": cfg["swa_qk_nope_head_dim"],
             "d_rope": cfg["swa_qk_rope_head_dim"],
             "d_v": cfg["swa_v_head_dim"], "r_q": cfg["swa_q_lora_rank"],
             "r_kv": cfg["swa_kv_lora_rank"],
             "theta": float(cfg["swa_rope_theta"]),
             "window": cfg["sliding_window_size"], "top_k": None}
    else:
        a = {"heads": cfg["num_attention_heads"],
             "d_nope": cfg["qk_nope_head_dim"],
             "d_rope": cfg["qk_rope_head_dim"],
             "d_v": cfg["v_head_dim"], "r_q": cfg["q_lora_rank"],
             "r_kv": cfg["kv_lora_rank"],
             "theta": float(cfg["rope_theta"]), "window": None,
             "top_k": cfg["index_topk"],
             "index_heads": cfg["index_n_heads"],
             "index_dim": cfg["index_head_dim"]}
    rescale = bool(cfg.get("apply_mla_qkv_lora_rescale", False))
    a["s_q"] = math.sqrt(d / a["r_q"]) if rescale else 1.0
    a["s_kv"] = math.sqrt(d / a["r_kv"]) if rescale else 1.0
    a["scale"] = 1.0 / math.sqrt(a["d_nope"] + a["d_rope"])
    return a


def _fp8(x):
    import jax.numpy as jnp

    s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _operand(x, precision: Optional[str]):
    import jax.numpy as jnp

    x = jnp.asarray(x).astype(jnp.float32)
    if precision == "fp8":
        return _fp8(x)
    if precision:
        raise ValueError(f"unknown control precision {precision!r}")
    return x


def mm(x, w, precision: Optional[str] = None):
    import jax
    import jax.numpy as jnp

    return jnp.matmul(_operand(x, precision), _operand(w, precision),
                      precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, w, eps: float):
    import jax
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * jnp.asarray(w).astype(jnp.float32))


def layer_norm(x, w, b, eps: float = LN_EPS):
    import jax
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps)
            * jnp.asarray(w).astype(jnp.float32)
            + jnp.asarray(b).astype(jnp.float32))


def rope(x, pos, theta: float):
    """Rotate-half RoPE over the last axis of ``x`` (L, ..., D), the
    position of row t being ``pos[t]``."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    freq = jnp.asarray(theta, jnp.float32) ** (
        -jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def gated_mlp(x, p, precision=None):
    import jax

    return mm(jax.nn.silu(mm(x, p["w_gate"], precision))
              * mm(x, p["w_up"], precision), p["w_down"], precision)


# ── attention ────────────────────────────────────────────────────────


def selected_keys(scores, pos_q, pos_k, top_k: int):
    """(Q, K) bool: key s is in the set of query t — the ``top_k``
    largest scores over ``s <= t``, ties to the lower s, and every
    ``s <= t`` where there are no more than ``top_k``."""
    import jax
    import jax.numpy as jnp

    causal = pos_k[None, :] <= pos_q[:, None]
    if scores.shape[-1] <= top_k:
        return causal
    # -0.0 and 0.0 are one score (a sort may tell them apart)
    masked = jnp.where(causal, jnp.where(scores == 0, 0.0, scores), -jnp.inf)
    _, idx = jax.lax.top_k(masked, top_k)
    rows = jnp.arange(scores.shape[0])[:, None]
    chosen = jnp.zeros(scores.shape, bool).at[rows, idx].set(True)
    return chosen & causal


def window_keys(pos_q, pos_k, window: int):
    causal = pos_k[None, :] <= pos_q[:, None]
    return causal & (pos_k[None, :] >= pos_q[:, None] - (window - 1))


def attend(q, k, v, keys, scale):
    """softmax(q.k * scale over the keys of each query) v, for a block
    of queries: q (Q, H, D), k (K, H, D), v (K, H, Dv), keys (Q, K)
    bool."""
    import jax
    import jax.numpy as jnp

    s = jnp.einsum("qhd,khd->hqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) * scale
    s = jnp.where(keys[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v,
                      precision=jax.lax.Precision.HIGHEST)


class Blocks:
    """How much is computed at a time, so that a route of 26k arcs fits
    the device; ``None`` is everything at once. None of them changes a
    number: a row of a score matrix is always whole over all keys.

    ``q_block`` / ``sel_block``: queries of one attention / selector
    product; ``head_group``: heads of one attention product;
    ``row_block``: tokens of one feed-forward or head product;
    ``expert_cap``: an expert's tokens are gathered into ``tokens //
    expert_cap`` rows (``forward`` raises where an expert got more);
    ``pad_to``: the route is padded to a multiple of it, or to the least
    of a list of lengths that holds it (tokens past its end come after
    every real query, so causality masks them; few padded lengths are
    few compilations); the other blocks have to divide it."""

    def __init__(self, q_block=None, sel_block=None, head_group=None,
                 row_block=None, expert_cap=1, pad_to=1):
        self.q_block, self.sel_block = q_block, sel_block or q_block
        self.head_group, self.row_block = head_group, row_block
        self.expert_cap = expert_cap
        self.pad_to = tuple(pad_to) if isinstance(pad_to, (list, tuple)) \
            else pad_to

    def key(self):
        return (self.q_block, self.sel_block, self.head_group,
                self.row_block, self.expert_cap, self.pad_to)

    def padded(self, n: int) -> int:
        if isinstance(self.pad_to, tuple):
            return min(p for p in self.pad_to if p >= n)
        return -(-n // self.pad_to) * self.pad_to


WHOLE = Blocks()


def by_blocks(fn, xs, block):
    """``fn(*xs)`` over blocks of ``block`` leading rows of every array
    in ``xs`` (one after the other: ``lax.map``), the results joined."""
    import jax

    n = xs[0].shape[0]
    if not block or n <= block:
        return fn(*xs)
    if n % block:
        raise ValueError(f"{n} rows are not whole blocks of {block}")
    cut = tuple(x.reshape((n // block, block) + x.shape[1:]) for x in xs)
    out = jax.lax.map(lambda a: fn(*a), cut)
    return jax.tree_util.tree_map(
        lambda o: o.reshape((n,) + o.shape[2:]), out)


def attention(p, a: Dict, x, pos, *, precision=None, blocks: Blocks = WHOLE,
              rows_at=None):
    """One attention block over one route: x (L, d) the block's normed
    input. Returns (output (L, d), taps): ``n_keys`` and ``first_key``
    of every query, and for a full layer ``selected`` (the key set of
    each query named in ``rows_at``, (P, L) bool)."""
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    h, dn, dr, dv = a["heads"], a["d_nope"], a["d_rope"], a["d_v"]
    c_q = rms_norm(mm(x, p["w_dq"], precision), p["q_norm"],
                   a["eps"]) * a["s_q"]
    kv = mm(x, p["w_dkv"], precision)
    c_kv = rms_norm(kv[:, :a["r_kv"]], p["kv_norm"], a["eps"]) * a["s_kv"]
    k_rope = rope(kv[:, a["r_kv"]:], pos, a["theta"])
    gate = jax.nn.sigmoid(mm(x, p["w_gate"], precision))

    if a["window"] is not None:
        keys = window_keys(pos, pos, a["window"])
    elif n <= a["top_k"]:
        keys = pos[None, :] <= pos[:, None]
    else:
        keys = by_blocks(
            lambda xq, cq, pq: selected_keys(
                index_scores(p["idx"], a, xq, cq, pq, x, pos, precision),
                pq, pos, a["top_k"]),
            (x, c_q, pos), blocks.sel_block)
    taps = {"n_keys": keys.sum(-1).astype(jnp.int32),
            "first_key": jnp.argmax(keys, -1).astype(jnp.int32)}
    if a["window"] is None and rows_at is not None:
        taps["selected"] = keys[rows_at]

    hg = blocks.head_group or h
    w_uq = jnp.asarray(p["w_uq"]).reshape(a["r_q"], h // hg, hg, dn + dr)
    w_ukv = jnp.asarray(p["w_ukv"]).reshape(a["r_kv"], h // hg, hg, dn + dv)

    def one_group(w_q, w_kv, gate_g):
        q = jnp.einsum("lr,rhd->lhd", _operand(c_q, precision),
                       _operand(w_q, precision),
                       precision=jax.lax.Precision.HIGHEST)
        q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos,
                                               a["theta"])], -1)
        kvh = jnp.einsum("lr,rhd->lhd", _operand(c_kv, precision),
                         _operand(w_kv, precision),
                         precision=jax.lax.Precision.HIGHEST)
        k = _operand(jnp.concatenate([kvh[..., :dn], jnp.broadcast_to(
            k_rope[:, None, :], (n, hg, dr))], -1), precision)
        v = _operand(kvh[..., dn:], precision)
        o = by_blocks(lambda qb, kb: attend(qb, k, v, kb, a["scale"]),
                      (_operand(q, precision), keys), blocks.q_block)
        return o * gate_g[:, :, None]

    groups = (jnp.moveaxis(w_uq, 1, 0), jnp.moveaxis(w_ukv, 1, 0),
              jnp.moveaxis(gate.reshape(n, h // hg, hg), 1, 0))
    if h == hg:
        out = one_group(*(g[0] for g in groups))[None]
    else:
        out = jax.lax.map(lambda g: one_group(*g), groups)
    out = jnp.moveaxis(out, 0, 1).reshape(n, h * dv)
    return mm(out, p["w_o"], precision), taps


def index_scores(p, a, x_q, c_q, pos_q, x_k, pos_k, precision=None):
    """I(t, s) of the learned selector for a block of queries against
    all keys: (Q, K) float32."""
    import jax
    import jax.numpy as jnp

    hi, di, dr = a["index_heads"], a["index_dim"], a["d_rope"]
    q = mm(c_q, p["w_q"], precision).reshape(-1, hi, di)
    q = jnp.concatenate([rope(q[..., :dr], pos_q, a["theta"]), q[..., dr:]],
                        -1)
    k = layer_norm(mm(x_k, p["w_k"], precision), p["k_norm_w"],
                   p["k_norm_b"])
    k = jnp.concatenate([rope(k[:, :dr], pos_k, a["theta"]), k[:, dr:]], -1)
    w = mm(x_q, p["w_w"], precision) * (hi ** -0.5) * (di ** -0.5)
    s = jnp.einsum("qjd,kd->qjk", _operand(q, precision),
                   _operand(k, precision),
                   precision=jax.lax.Precision.HIGHEST)
    return jnp.einsum("qj,qjk->qk", w, jax.nn.relu(s),
                      precision=jax.lax.Precision.HIGHEST)


# ── the expert layer ─────────────────────────────────────────────────


def route(p, x, top_k: int, scaling: float = 1.0, precision=None):
    """(chosen (L, k) int32, weights (L, k) float32) over ALL experts."""
    import jax
    import jax.numpy as jnp

    prob = jax.nn.sigmoid(mm(x, p["router"], precision))
    _, chosen = jax.lax.top_k(prob + jnp.asarray(p["bias"], jnp.float32),
                              top_k)
    picked = jnp.take_along_axis(prob, chosen, axis=-1)
    return chosen.astype(jnp.int32), (
        picked / picked.sum(-1, keepdims=True) * scaling)


def moe(p, x, top_k: int, share: Tuple[int, int], scaling: float = 1.0,
        shared: bool = True, precision=None, blocks: Blocks = WHOLE,
        n_live=None):
    """The share's part of the expert layer: the terms of the held
    experts (``p``'s expert arrays hold exactly those), each computed
    for the tokens that chose it, and, with ``shared``, the shared
    expert. Returns (y, chosen, fullest): ``fullest`` is the most
    tokens any held expert got (what ``expert_cap`` has to hold). Rows
    from ``n_live`` on are padding: no expert computes them."""
    import jax
    import jax.numpy as jnp

    first, count = share
    n = x.shape[0]
    cap = max(1, n // blocks.expert_cap)
    chosen, weights = route(p, x, top_k, scaling, precision)
    live = jnp.arange(n) < (n if n_live is None else n_live)

    def add_expert(e, carry):
        y, fullest = carry
        hit = (chosen == first + e) & live[:, None]
        g = jnp.where(hit, weights, 0.0).sum(-1)
        mine = hit.any(-1)
        rows = jnp.nonzero(mine, size=cap, fill_value=0)[0]
        g = jnp.where(jnp.arange(cap) < mine.sum(), g[rows], 0.0)
        expert = {k: p[k][e] for k in ("w_gate", "w_up", "w_down")}
        y = y.at[rows].add(g[:, None] * gated_mlp(x[rows], expert, precision))
        return y, jnp.maximum(fullest, mine.sum())

    y, fullest = jax.lax.fori_loop(
        0, count, add_expert,
        (jnp.zeros(x.shape, jnp.float32), jnp.zeros((), jnp.int32)))
    if shared:
        y = y + by_blocks(lambda r: gated_mlp(r, p["shared"], precision),
                          (x,), blocks.row_block)
    return y, chosen, fullest


# ── the model ────────────────────────────────────────────────────────


def layer_kinds(cfg: Dict):
    """(attention kind, ffn kind) of each layer that is held."""
    n = cfg["num_hidden_layers"]
    dense = cfg.get("first_k_dense_replace", 0)
    return [(cfg["layer_types"][i], "dense" if i < dense else "moe")
            for i in range(n)]


def layer(p, cfg: Dict, kinds: Tuple[str, str], h, rows_at,
          share: Tuple[int, int], precision=None, blocks: Blocks = WHOLE,
          n_live=None):
    """One pre-norm residual layer over one route: h (L, d) → (h, taps);
    rows from ``n_live`` on are padding."""
    import jax.numpy as jnp

    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(h.shape[0], dtype=jnp.int32)
    a = dict(attention_sizes(cfg, kinds[0]), eps=eps)
    y, taps = attention(p["attn"], a, rms_norm(h, p["attn_norm"], eps), pos,
                        precision=precision, blocks=blocks, rows_at=rows_at)
    h = h + y
    x = rms_norm(h, p["ffn_norm"], eps)
    if kinds[1] == "dense":
        return h + by_blocks(lambda r: gated_mlp(r, p["ffn"], precision),
                             (x,), blocks.row_block), taps
    y, taps["chosen"], taps["fullest"] = moe(
        p["ffn"], x, cfg["num_experts_per_tok"], share,
        cfg.get("routed_scaling_factor", 1.0), precision=precision,
        blocks=blocks, n_live=n_live)
    return h + y, taps


def head(params, cfg: Dict, h, ids, rows_at, precision=None,
         blocks: Blocks = WHOLE):
    """→ (next_logit (L,), lse (L,), rows (P, V)): the logit of ids[t + 1]
    and the log-sum-exp at every position, the named rows whole."""
    import jax
    import jax.numpy as jnp

    x = rms_norm(h, params["final_norm"], cfg["rms_norm_eps"])
    nxt = jnp.concatenate([ids[1:], ids[:1]])

    def rows(xr, target):
        logits = mm(xr, params["head"], precision)
        return (jnp.take_along_axis(logits, target[:, None], -1)[:, 0],
                jax.nn.logsumexp(logits, axis=-1))

    next_logit, lse = by_blocks(rows, (x, nxt), blocks.row_block)
    return next_logit, lse, mm(x[rows_at], params["head"], precision)


@functools.lru_cache(maxsize=None)
def _jitted(cfg_json: str, share, precision, blocks_key):
    """The layer and the head under ``jax.jit`` (a long route is far too
    many operations to dispatch one by one); one compilation a padded
    length and kind of layer."""
    import json

    import jax

    cfg, blocks = json.loads(cfg_json), Blocks(*blocks_key)
    return (jax.jit(lambda p, h, rows_at, kinds, n_live: layer(
                p, cfg, kinds, h, rows_at, share, precision, blocks, n_live),
                static_argnums=(3,)),
            jax.jit(lambda params, h, ids, rows_at: head(
                params, cfg, h, ids, rows_at, precision, blocks)))


def forward(params: Dict, cfg: Dict, ids, share: Tuple[int, int],
            rows_at: Sequence[int] = (), *, precision=None,
            blocks: Blocks = WHOLE) -> Dict:
    """One route: ids (L,) within the held slice of the vocabulary.

    Returns host arrays: ``next_logit`` (L,) (the logit of ids[t + 1] at
    position t; 0 at the last), ``lse`` (L,), ``loglik`` (sum over t < L
    - 1 of next_logit - lse), ``rows`` (P, V_held) the logit rows at
    ``rows_at``, and the taps: ``chosen`` [(L, k)] per expert layer,
    ``n_keys`` / ``first_key`` [(L,)] per layer, ``selected`` [(P, L)]
    per full layer."""
    import json

    import jax.numpy as jnp

    n = len(ids)
    sizes = {k: v for k, v in cfg.items()
             if isinstance(v, (int, float, bool, list))}
    layer_fn, head_fn = _jitted(json.dumps(sizes, sort_keys=True),
                                tuple(share), precision or None,
                                blocks.key())
    ids = jnp.pad(jnp.asarray(ids, jnp.int32), (0, blocks.padded(n) - n))
    named = jnp.asarray(list(rows_at) or [0], jnp.int32)
    h = jnp.asarray(params["embed"])[ids].astype(jnp.float32)
    taps = {"chosen": [], "n_keys": [], "first_key": [], "selected": []}
    for l, kinds in enumerate(layer_kinds(cfg)):
        h, t = layer_fn(params["layers"][l], h, named, kinds, jnp.int32(n))
        taps["n_keys"].append(np.asarray(t["n_keys"])[:n])
        taps["first_key"].append(np.asarray(t["first_key"])[:n])
        if "selected" in t:
            taps["selected"].append(np.asarray(t["selected"])[:, :n])
        if "chosen" in t:
            taps["chosen"].append(np.asarray(t["chosen"])[:n])
            if int(t["fullest"]) > max(1, ids.shape[0] // blocks.expert_cap):
                raise ValueError(
                    f"an expert of layer {l} got {int(t['fullest'])} "
                    f"tokens: more than expert_cap holds")
    next_logit, lse, rows = (np.asarray(v) for v in head_fn(
        params, h, ids, named))
    next_logit = next_logit[:n].copy()
    next_logit[n - 1] = 0.0
    lse = lse[:n]
    return {"next_logit": next_logit, "lse": lse,
            "loglik": float(np.sum((next_logit - lse)[:n - 1],
                                   dtype=np.float64)),
            "rows": rows if len(rows_at) else rows[:0], **taps}
