"""Plain reference of the third route-sequence language model (catalog
name ``K-EXAONE-236B-A23B``): float32 ``jax.numpy`` at ``highest``
matmul precision, one route at a time, every attention by brute force
over all keys under its mask, the experts by a loop over the held ones;
no kernels, no blocks of keys, no length ladder, no batching.

Equations (d = hidden_size, H query heads over G key-value heads of dh =
head_dim, query head h reads key-value head ``h // (H / G)``; eps from
the config; positions are a token's index within its own route; held
layers l with ``layer_types[l]`` and ``mlp_layer_types[l]``):

- Trunk: ``h = embed[ids]``; per layer ``h += RMSNorm_d(Attn_l(h))``,
  then ``h += RMSNorm_d(FFN_l(h))``: the norm on each sub-block's
  OUTPUT, none on its input; ``logits = RMSNorm_d(h) @ head``; the head
  is not tied.
- Attention on x (L, d): ``q = RMSNorm_dh(x W_q)`` by head with a
  learned weight, ``k = RMSNorm_dh(x W_k)``, ``v = x W_v``; in a
  ``sliding_attention`` layer RoPE (``rope_parameters.rope_theta``,
  rotate-half over the whole head) on q and k and the keys ``t -
  sliding_window + 1 .. t`` (the window counts the query's own
  position); in a ``full_attention`` layer no RoPE and the keys ``0 ..
  t``; ``o_{t,h} = sum_s softmax_s(q_{t,h} . k_{s,g(h)} / sqrt(dh))
  v_{s,g(h)}``; ``y = concat_h(o) W_o``. No gate, no bias.
- FFN: ``dense``: ``W_down(silu(x W_gate) * x W_up)`` at
  ``intermediate_size``. ``sparse``: ``p = sigmoid(x W_r)`` over all
  ``num_experts``, chosen = top ``num_experts_per_tok`` of ``p + b``
  (ties to the lower expert), weights ``routed_scaling_factor * p_e /
  sum_chosen p``, ``y = sum_{e chosen and held} w_e E_e(x) +
  E_shared(x)``, every expert the same gated form at
  ``moe_intermediate_size`` (``dots3_ref.moe``: the two models' expert
  layers are one layer); ``share = (first, count)`` keeps only the
  terms of the experts ``first .. first + count - 1``.
- Prediction module: for t + 1 < n, ``u_t = [RMSNorm_d(h_t) ;
  RMSNorm_d(embed[id_{t+1}])] W_p`` (2d → d; ``h_t`` the trunk's last
  hidden state before the final norm), one ``full_attention`` /
  ``sparse`` block as above over ``u_0 .. u_{n-2}``, ``logits2 =
  RMSNorm_d(.) @ head`` with a norm of the module's own: ``logits2_t``
  is the distribution of ``id_{t+2}``.

The parameters are the artifact's pytree (``PARAM_LAYOUT``); the expert
arrays hold the experts of the share they were drawn for, the embedding
and the head the held rows of the vocabulary. ``precision="fp8"`` is
the control: the operands of every product rounded to float8 (e4m3,
scaled per tensor).

Memory: ``dots3_ref.Blocks`` says how many queries and rows are
computed at a time, so that a route of 26k arcs fits the device and few
shapes compile; none of them changes a number (a row of a score matrix
is always whole over all keys). A padded route's extra tokens come
after every real one, so causality masks them; the module runs over the
padded length too and its rows from n - 1 on are dropped. A layer's
weights are cast to float32 as the layer is reached.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from benchmark.reference.dots3_ref import (WHOLE, Blocks, _operand, by_blocks,
                                           gated_mlp, mm, moe, rms_norm, rope)

PARAM_LAYOUT = """
embed (V_held, d); head (d, V_held); final_norm (d,)
layers[l]: post_attn_norm (d,), post_ffn_norm (d,),
  attn: w_q (d, H*dh), w_k (d, G*dh), w_v (d, G*dh), q_norm (dh,),
        k_norm (dh,), w_o (H*dh, d)
  ffn (dense): w_gate (d, F), w_up (d, F), w_down (F, d)
  ffn (sparse): router (d, E), bias (E,), w_gate (E_held, d, m),
             w_up (E_held, d, m), w_down (E_held, m, d),
             shared: w_gate (d, m_s), w_up (d, m_s), w_down (m_s, d)
mtp: h_norm (d,), e_norm (d,), w_proj (2d, d), layer (a full / sparse
     layer as above), final_norm (d,)
"""

FULL, SLIDING = "full_attention", "sliding_attention"


def layer_kinds(cfg: Dict):
    """(attention kind, ffn kind) of each layer that is held."""
    return [(cfg["layer_types"][l], cfg["mlp_layer_types"][l])
            for l in range(cfg["num_hidden_layers"])]


def key_sets(cfg: Dict, kind: str, pos_q, pos_k):
    """(Q, K) bool: the keys each query sees in a layer of ``kind``."""
    keys = pos_k[None, :] <= pos_q[:, None]
    if kind == SLIDING:
        keys = keys & (pos_k[None, :] > pos_q[:, None]
                       - cfg["sliding_window"])
    return keys


def attention(p, cfg: Dict, kind: str, x, pos, precision=None,
              blocks: Blocks = WHOLE):
    """One attention over one route: x (L, d) the stream → (output (L,
    d), n_keys (L,), first_key (L,))."""
    import jax
    import jax.numpy as jnp

    n, eps = x.shape[0], cfg["rms_norm_eps"]
    heads, groups, dh = (cfg["num_attention_heads"],
                         cfg["num_key_value_heads"], cfg["head_dim"])
    q = rms_norm(mm(x, p["w_q"], precision).reshape(n, heads, dh),
                 p["q_norm"], eps)
    k = rms_norm(mm(x, p["w_k"], precision).reshape(n, groups, dh),
                 p["k_norm"], eps)
    v = mm(x, p["w_v"], precision).reshape(n, groups, dh)
    if kind == SLIDING:
        theta = float(cfg["rope_parameters"]["rope_theta"])
        q, k = rope(q, pos, theta), rope(k, pos, theta)
    q = q.reshape(n, groups, heads // groups, dh)
    q, k, v = (_operand(a, precision) for a in (q, k, v))

    def rows(qb, pb):
        keys = key_sets(cfg, kind, pb, pos)
        s = jnp.einsum("qghd,kgd->qghk", qb, k,
                       precision=jax.lax.Precision.HIGHEST) / math.sqrt(dh)
        s = jnp.where(keys[:, None, None, :], s, -jnp.inf)
        o = jnp.einsum("qghk,kgd->qghd", jax.nn.softmax(s, axis=-1), v,
                       precision=jax.lax.Precision.HIGHEST)
        return (o, keys.sum(-1).astype(jnp.int32),
                jnp.argmax(keys, -1).astype(jnp.int32))

    o, n_keys, first = by_blocks(rows, (q, pos), blocks.q_block)
    return mm(o.reshape(n, heads * dh), p["w_o"], precision), n_keys, first


def layer(p, cfg: Dict, kinds: Tuple[str, str], h, share: Tuple[int, int],
          precision=None, blocks: Blocks = WHOLE, n_live=None):
    """One residual layer over one route, the norm on each sub-block's
    output: h (L, d) → (h, taps); rows from ``n_live`` on are padding."""
    import jax
    import jax.numpy as jnp

    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), p)
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(h.shape[0], dtype=jnp.int32)
    y, n_keys, first = attention(p["attn"], cfg, kinds[0], h, pos, precision,
                                 blocks)
    h = h + rms_norm(y, p["post_attn_norm"], eps)
    taps = {"n_keys": n_keys, "first_key": first}
    if kinds[1] == "dense":
        y = by_blocks(lambda r: gated_mlp(r, p["ffn"], precision), (h,),
                      blocks.row_block)
    else:
        y, taps["chosen"], taps["fullest"] = moe(
            p["ffn"], h, cfg["num_experts_per_tok"], share,
            cfg["routed_scaling_factor"], precision=precision, blocks=blocks,
            n_live=n_live)
    return h + rms_norm(y, p["post_ffn_norm"], eps), taps


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
    """The layer, the module's input and the head under ``jax.jit``;
    one compilation a padded length and kind of layer."""
    import json

    import jax

    cfg, blocks = json.loads(cfg_json), Blocks(*blocks_key)
    return (jax.jit(lambda p, h, kinds, n_live: layer(
                p, cfg, kinds, h, share, precision, blocks, n_live),
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
    layer_fn, input_fn, head_fn = _jitted(
        json.dumps(sizes, sort_keys=True), tuple(share), precision or None,
        blocks.key())
    padded = blocks.padded(n)
    ids = jnp.pad(jnp.asarray(ids, jnp.int32), (0, padded - n))
    named = jnp.asarray(list(rows_at) or [0], jnp.int32)
    cap = max(1, padded // blocks.expert_cap)
    taps = {"chosen": [], "n_keys": [], "first_key": []}

    def run(p, h, kinds, live, where):
        h, t = layer_fn(p, h, kinds, jnp.int32(live))
        taps["n_keys"].append(np.asarray(t["n_keys"])[:live])
        taps["first_key"].append(np.asarray(t["first_key"])[:live])
        if "chosen" in t:
            taps["chosen"].append(np.asarray(t["chosen"])[:live])
            if int(t["fullest"]) > cap:
                raise ValueError(
                    f"an expert of {where} got {int(t['fullest'])} tokens: "
                    f"more than expert_cap holds")
        return h

    h = jnp.asarray(params["embed"])[ids].astype(jnp.float32)
    for l, kinds in enumerate(layer_kinds(cfg)):
        h = run(params["layers"][l], h, kinds, n, f"layer {l}")

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
        h2 = run(m["layer"], u, (FULL, "sparse"), n - 1, "the module")
        (out["mtp_next_logit"], out["mtp_lse"], out["mtp_loglik"],
         _) = column(m["final_norm"], h2, 2, n - 1)
    return {**out, **taps}
