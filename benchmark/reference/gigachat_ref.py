"""Plain reference of the fourth route-sequence language model (catalog
name ``GigaChat3.1-702B-A36B``, ``model_type`` ``deepseek_v3``): float32
``jax.numpy`` at ``highest`` matmul precision, one route at a time,
every attention by brute force over all keys under the causal mask, the
experts by a loop over the held ones; no kernels, no chunks of keys, no
length ladder, no batching.

Equations (d = hidden_size, H heads, dn = qk_nope_head_dim, dr =
qk_rope_head_dim, dv = v_head_dim, r_q = q_lora_rank, r_kv =
kv_lora_rank; eps from the config; positions are a token's index within
its own route):

- Trunk: ``h = embed[ids]``; per layer ``h += Attn(RMSNorm_d(h))``, then
  ``h += FFN(RMSNorm_d(h))``; ``logits = RMSNorm_d(h) @ head``; the head
  is not tied. Held layer 0 is ``dense`` where ``first_k_dense_replace``
  is at least 1 (the published leading dense layers are one layer
  several times: one is held), the others ``sparse``.
- Latent attention on x (L, d): ``c_q = RMSNorm_rq(x W_dq)``; ``[q_n ;
  q_r]_h = c_q W_uq,h`` (dn + dr); ``[c_kv ; k_r] = x W_dkv`` (r_kv +
  dr), ``c_kv <- RMSNorm_rkv(c_kv)``, ``k_r`` ONE rotary key for all
  heads; ``[k_n ; v]_h = c_kv W_ukv,h`` (dn + dv); RoPE (rotate-half,
  YaRN's frequencies) on ``q_r`` and ``k_r``; ``o_{t,h} = sum_{s <= t}
  softmax_s((q_n.k_n + q_r.k_r) * scale) v_{s,h}``; ``y = concat_h(o)
  W_o``. No gate, no bias, no selector, no window.
- YaRN (``rope_scaling``; dr rotary dimensions, base ``rope_theta``, L0
  ``original_max_position_embeddings``): pair i < dr / 2 has ``f_i =
  base ** (-2 i / dr)``; ``cd(r) = dr ln(L0 / (2 pi r)) / (2 ln base)``,
  ``low = max(floor(cd(beta_fast)), 0)``, ``high = min(ceil(cd(
  beta_slow)), dr - 1)``, ``ramp_i = clip((i - low) / (high - low), 0,
  1)``; the pair turns by ``pos * (f_i (1 - ramp_i) + f_i / factor *
  ramp_i)``; ``m(a) = 0.1 a ln(factor) + 1``; cos and sin times
  ``m(mscale) / m(mscale_all_dim)``; ``scale = (dn + dr) ** -0.5 *
  m(mscale_all_dim) ** 2``.
- FFN: ``dense``: ``W_down(silu(x W_gate) * x W_up)`` at
  ``intermediate_size``. ``sparse`` (``noaux_tc``): ``p = sigmoid(x
  W_r)`` over ALL experts, ``c = p + b``; the experts lie in
  ``n_group`` groups of consecutive ones, a group's score the sum of
  its two largest ``c``; the ``topk_group`` best groups are kept (ties
  to the lower group), ``c`` of the others set to 0; chosen = top
  ``num_experts_per_tok`` of that (ties to the lower expert); weights
  ``routed_scaling_factor * p_e / (sum_chosen p + 1e-20)``; ``y =
  sum_{e chosen and held} w_e E_e(x) + E_shared(x)``, every expert the
  gated form at ``moe_intermediate_size``; ``share = (first, count)``
  keeps only the terms of the experts ``first .. first + count - 1``.
- Prediction module: for t + 1 < n, ``u_t = [RMSNorm_d(h_t) ;
  RMSNorm_d(embed[id_{t+1}])] W_p`` (2d → d; ``h_t`` the trunk's last
  hidden state before the final norm), one ``sparse`` block as above
  over ``u_0 .. u_{n-2}``, ``logits2 = RMSNorm_d(.) @ head`` with a norm
  of the module's own: ``logits2_t`` is the distribution of
  ``id_{t+2}``.

The parameters are the artifact's pytree (``PARAM_LAYOUT``); the expert
arrays hold the experts of the share they were drawn for, the embedding
and the head the held rows of the vocabulary. ``precision="fp8"`` is
the control: the operands of every product rounded to float8 (e4m3,
scaled per tensor).

Memory: ``dots3_ref.Blocks`` says how many queries, heads and rows are
computed at a time (a group of heads with its rows of ``W_o``, summed
over the groups), so that a route of 26k arcs fits the device beside
the parameters and few shapes compile; none of them changes a number (a
row of a score matrix is always whole over all keys). A matrix is cast
to float32 where it is multiplied, an expert at a time, and a layer's
attention and feed-forward halves are two programs. A padded route's
extra tokens come after every real one, so causality masks them; the
module runs over the padded length too and its rows from n - 1 on are
dropped.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from benchmark.reference.dots3_ref import (WHOLE, Blocks, _operand, attend,
                                           by_blocks, gated_mlp, mm,
                                           rms_norm)

PARAM_LAYOUT = """
embed (V_held, d); head (d, V_held); final_norm (d,)
layers[l]: attn_norm (d,), ffn_norm (d,),
  attn: w_dq (d, r_q), q_norm (r_q,), w_uq (r_q, H*(dn+dr)),
        w_dkv (d, r_kv+dr), kv_norm (r_kv,), w_ukv (r_kv, H*(dn+dv)),
        w_o (H*dv, d)
  ffn (dense): w_gate (d, F), w_up (d, F), w_down (F, d)
  ffn (sparse): router (d, E), bias (E,), w_gate (E_held, d, m),
             w_up (E_held, d, m), w_down (E_held, m, d),
             shared: w_gate (d, m_s), w_up (d, m_s), w_down (m_s, d)
mtp: h_norm (d,), e_norm (d,), w_proj (2d, d), layer (a sparse layer as
     above), final_norm (d,)
"""


def layer_kinds(cfg: Dict):
    """The ffn kind of each layer that is held."""
    n = cfg["num_hidden_layers"]
    dense = min(1, cfg["first_k_dense_replace"], n)
    return ["dense"] * dense + ["sparse"] * (n - dense)


def yarn(cfg: Dict) -> Tuple[np.ndarray, float, float]:
    """(the angle a position turns each rotary pair by, what cos and sin
    are multiplied by, the softmax scale), pair by pair from the
    formulas above."""
    sc, dr = cfg["rope_scaling"], cfg["qk_rope_head_dim"]
    base, l0 = float(cfg["rope_theta"]), sc["original_max_position_embeddings"]

    def cd(turns):
        return dr * math.log(l0 / (2 * math.pi * turns)) / (2 * math.log(base))

    low = max(math.floor(cd(sc["beta_fast"])), 0)
    high = min(math.ceil(cd(sc["beta_slow"])), dr - 1)
    freq = []
    for i in range(dr // 2):
        f = base ** (-2.0 * i / dr)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        freq.append(f * (1.0 - ramp) + f / sc["factor"] * ramp)

    def m(a):
        return 0.1 * a * math.log(sc["factor"]) + 1.0

    scale = ((cfg["qk_nope_head_dim"] + dr) ** -0.5
             * m(sc["mscale_all_dim"]) ** 2)
    return (np.asarray(freq, np.float32),
            m(sc["mscale"]) / m(sc["mscale_all_dim"]), scale)


def yarn_rope(x, pos, cfg: Dict):
    """Rotate-half RoPE at YaRN's frequencies over the last axis of
    ``x`` (L, ..., dr), the position of row t being ``pos[t]``."""
    import jax.numpy as jnp

    freq, amplitude, _ = yarn(cfg)
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(freq)[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos = (jnp.cos(ang) * amplitude).reshape(shape)
    sin = (jnp.sin(ang) * amplitude).reshape(shape)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def attention(p, cfg: Dict, x, pos, precision=None, blocks: Blocks = WHOLE):
    """One attention over one route: x (L, d) the block's normed input
    → (output (L, d), n_keys (L,), first_key (L,))."""
    import jax
    import jax.numpy as jnp

    n, eps = x.shape[0], cfg["rms_norm_eps"]
    h, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    r_q, r_kv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    scale = yarn(cfg)[2]
    c_q = rms_norm(mm(x, p["w_dq"], precision), p["q_norm"], eps)
    kv = mm(x, p["w_dkv"], precision)
    c_kv = rms_norm(kv[:, :r_kv], p["kv_norm"], eps)
    k_r = yarn_rope(kv[:, r_kv:], pos, cfg)

    hg = blocks.head_group or h
    w_uq = jnp.asarray(p["w_uq"]).reshape(r_q, h // hg, hg, dn + dr)
    w_ukv = jnp.asarray(p["w_ukv"]).reshape(r_kv, h // hg, hg, dn + dv)

    def seen(pb):
        """(Q, L) bool: the keys the queries at ``pb`` see."""
        return pos[None, :] <= pb[:, None]

    def one_group(w_q, w_kv):
        q = jnp.einsum("lr,rhd->lhd", _operand(c_q, precision),
                       _operand(w_q, precision),
                       precision=jax.lax.Precision.HIGHEST)
        q = jnp.concatenate([q[..., :dn], yarn_rope(q[..., dn:], pos, cfg)],
                            -1)
        kvh = jnp.einsum("lr,rhd->lhd", _operand(c_kv, precision),
                         _operand(w_kv, precision),
                         precision=jax.lax.Precision.HIGHEST)
        k = _operand(jnp.concatenate([kvh[..., :dn], jnp.broadcast_to(
            k_r[:, None, :], (n, hg, dr))], -1), precision)
        v = _operand(kvh[..., dn:], precision)
        return by_blocks(lambda qb, pb: attend(qb, k, v, seen(pb), scale),
                         (_operand(q, precision), pos), blocks.q_block)

    # a group of heads at a time, its part of the output projection
    # added up: no (L, H, dv) float32 array of all heads
    w_o = jnp.asarray(p["w_o"]).reshape(h // hg, hg * dv, -1)

    def add_group(y, group):
        w_q, w_kv, w_out = group
        o = one_group(w_q, w_kv).reshape(n, hg * dv)
        return y + mm(o, w_out, precision), None

    y, _ = jax.lax.scan(
        add_group, jnp.zeros((n, w_o.shape[-1]), jnp.float32),
        (jnp.moveaxis(w_uq, 1, 0), jnp.moveaxis(w_ukv, 1, 0), w_o))
    n_keys, first = by_blocks(
        lambda pb: (seen(pb).sum(-1).astype(jnp.int32),
                    jnp.argmax(seen(pb), -1).astype(jnp.int32)),
        (pos,), blocks.q_block)
    return y, n_keys, first


def route(p, x, cfg: Dict, precision=None):
    """(chosen (L, k) int32, weights (L, k) float32) over ALL experts,
    group-limited. A group is kept where fewer than ``topk_group``
    groups come before it: a higher score, or the same and a lower
    index."""
    import jax
    import jax.numpy as jnp

    n_group, keep_n = cfg["n_group"], cfg["topk_group"]
    prob = jax.nn.sigmoid(mm(x, p["router"], precision))
    c = prob + jnp.asarray(p["bias"], jnp.float32)
    n, n_experts = c.shape
    grouped = c.reshape(n, n_group, n_experts // n_group)
    ordered = jnp.sort(grouped, -1)
    score = ordered[..., -1] + ordered[..., -2]
    idx = jnp.arange(n_group)
    other, mine = score[:, None, :], score[:, :, None]
    before = (other > mine) | ((other == mine)
                               & (idx[None, None, :] < idx[None, :, None]))
    kept = before.sum(-1) < keep_n
    c = jnp.where(kept[:, :, None], grouped, 0.0).reshape(n, n_experts)
    _, chosen = jax.lax.top_k(c, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(prob, chosen, axis=-1)
    return chosen.astype(jnp.int32), (
        picked / (picked.sum(-1, keepdims=True) + 1e-20)
        * cfg["routed_scaling_factor"])


def moe(p, x, cfg: Dict, share: Tuple[int, int], shared: bool = True,
        precision=None, blocks: Blocks = WHOLE, n_live=None):
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
    chosen, weights = route(p, x, cfg, precision)
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


def attention_half(p, cfg: Dict, h, precision=None, blocks: Blocks = WHOLE):
    """``h + Attn(RMSNorm(h))`` over one route: h (L, d) → (h, n_keys,
    first_key)."""
    import jax.numpy as jnp

    pos = jnp.arange(h.shape[0], dtype=jnp.int32)
    y, n_keys, first = attention(
        p["attn"], cfg, rms_norm(h, p["attn_norm"], cfg["rms_norm_eps"]),
        pos, precision, blocks)
    return h + y, n_keys, first


def ffn_half(p, cfg: Dict, kind: str, h, share: Tuple[int, int],
             precision=None, blocks: Blocks = WHOLE, n_live=None):
    """``h + FFN(RMSNorm(h))``: h (L, d) → (h, taps); rows from
    ``n_live`` on are padding."""
    x = rms_norm(h, p["ffn_norm"], cfg["rms_norm_eps"])
    if kind == "dense":
        return h + by_blocks(lambda r: gated_mlp(r, p["ffn"], precision),
                             (x,), blocks.row_block), {}
    y, chosen, fullest = moe(p["ffn"], x, cfg, share, precision=precision,
                             blocks=blocks, n_live=n_live)
    return h + y, {"chosen": chosen, "fullest": fullest}


def module_input(m, embed, cfg: Dict, h, ids, precision=None):
    """u (L, d): row t from ``h_t`` and the embedding of ``id_{t+1}``
    (the last row wraps round: it is dropped)."""
    import jax.numpy as jnp

    eps = cfg["rms_norm_eps"]
    e = jnp.asarray(embed)[jnp.roll(ids, -1)].astype(jnp.float32)
    both = jnp.concatenate([rms_norm(h, m["h_norm"], eps),
                            rms_norm(e, m["e_norm"], eps)], -1)
    return mm(both, m["w_proj"], precision)


def head(norm_w, head_w, cfg: Dict, h, targets, rows_at, precision=None,
         blocks: Blocks = WHOLE):
    """→ (the logit of ``targets[t]`` (L,), lse (L,), rows (P, V))."""
    import jax
    import jax.numpy as jnp

    x = rms_norm(h, norm_w, cfg["rms_norm_eps"])

    def rows(xr, target):
        logits = mm(xr, head_w, precision)
        return (jnp.take_along_axis(logits, target[:, None], -1)[:, 0],
                jax.nn.logsumexp(logits, axis=-1))

    logit, lse = by_blocks(rows, (x, targets), blocks.row_block)
    return logit, lse, mm(x[rows_at], head_w, precision)


@functools.lru_cache(maxsize=None)
def _jitted(cfg_json: str, share, precision, blocks_key):
    """The two halves of a layer, the module's input and the head under
    ``jax.jit``; one compilation a padded length and kind of layer."""
    import json

    import jax

    cfg, blocks = json.loads(cfg_json), Blocks(*blocks_key)
    return (jax.jit(lambda p, h: attention_half(p, cfg, h, precision,
                                                blocks)),
            jax.jit(lambda p, h, kind, n_live: ffn_half(
                p, cfg, kind, h, share, precision, blocks, n_live),
                static_argnums=(2,)),
            jax.jit(lambda m, embed, h, ids: module_input(
                m, embed, cfg, h, ids, precision)),
            jax.jit(lambda norm_w, head_w, h, targets, rows_at: head(
                norm_w, head_w, cfg, h, targets, rows_at, precision,
                blocks)))


def forward(params: Dict, cfg: Dict, ids, share: Tuple[int, int],
            rows_at: Sequence[int] = (), *, precision: Optional[str] = None,
            blocks: Blocks = WHOLE) -> Dict:
    """One route: ids (L,) within the held slice of the vocabulary.

    Returns host arrays: ``next_logit`` (L,) (the logit of ids[t + 1] at
    position t; 0 at the last), ``lse`` (L,), ``loglik``, ``rows`` (P,
    V_held); with a module in ``params``, over its L - 1 positions,
    ``mtp_next_logit`` (the logit of ids[t + 2]; 0 at the last),
    ``mtp_lse`` and ``mtp_loglik``; and the taps, the module's block
    last and L - 1 long: ``chosen`` [(L, k)] per expert block,
    ``n_keys`` / ``first_key`` [(L,)] per block."""
    import json

    import jax.numpy as jnp

    n = len(ids)
    sizes = {k: v for k, v in cfg.items()
             if isinstance(v, (int, float, bool, list, dict))
             and k not in ("limits", "limit_reasons")}
    attn_fn, ffn_fn, input_fn, head_fn = _jitted(
        json.dumps(sizes, sort_keys=True), tuple(share), precision or None,
        blocks.key())
    padded = blocks.padded(n)
    ids = jnp.pad(jnp.asarray(ids, jnp.int32), (0, padded - n))
    named = jnp.asarray(list(rows_at) or [0], jnp.int32)
    cap = max(1, padded // blocks.expert_cap)
    taps = {"chosen": [], "n_keys": [], "first_key": []}

    def run(p, h, kind, live, where):
        h, n_keys, first = attn_fn({k: p[k] for k in ("attn", "attn_norm")},
                                   h)
        h, t = ffn_fn({k: p[k] for k in ("ffn", "ffn_norm")}, h, kind,
                      jnp.int32(live))
        taps["n_keys"].append(np.asarray(n_keys)[:live])
        taps["first_key"].append(np.asarray(first)[:live])
        if t:
            taps["chosen"].append(np.asarray(t["chosen"])[:live])
            if int(t["fullest"]) > cap:
                raise ValueError(
                    f"an expert of {where} got {int(t['fullest'])} tokens: "
                    f"more than expert_cap holds")
        return h

    h = jnp.asarray(params["embed"])[ids].astype(jnp.float32)
    for l, kind in enumerate(layer_kinds(cfg)):
        h = run(params["layers"][l], h, kind, n, f"layer {l}")

    def column(norm_w, h, shift, live):
        """A head's column over ``live`` positions; those from ``n -
        shift`` on have no target."""
        logit, lse, rows = (np.asarray(v) for v in head_fn(
            norm_w, params["head"], h, jnp.roll(ids, -shift), named))
        logit = logit[:live].copy()
        logit[max(n - shift, 0):] = 0.0
        lse = lse[:live]
        return logit, lse, float(np.sum((logit - lse)[:max(n - shift, 0)],
                                        dtype=np.float64)), rows

    next_logit, lse, loglik, rows = column(params["final_norm"], h, 1, n)
    out = {"next_logit": next_logit, "lse": lse, "loglik": loglik,
           "rows": rows if len(rows_at) else rows[:0]}
    if "mtp" in params:
        m = params["mtp"]
        u = input_fn({k: v for k, v in m.items() if k != "layer"},
                     params["embed"], h, ids)
        h2 = run(m["layer"], u, "sparse", n - 1, "the module")
        (out["mtp_next_logit"], out["mtp_lse"], out["mtp_loglik"],
         _) = column(m["final_norm"], h2, 2, n - 1)
    return {**out, **taps}
