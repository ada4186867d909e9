"""Plain reference of the fifth route-sequence language model (catalog
name ``Falcon-H1-34B-Instruct``, ``model_type`` ``falcon_h1``): float32
``jax.numpy`` at ``highest`` matmul precision, one route at a time, the
state-space mixer as the recurrence it is defined by, token by token,
the attention by a plain softmax over all causal keys; no kernels, no
chunking of the scan, no length ladder, no batching.

Equations (d = hidden_size; eps from the config; positions are a
token's index within its own route; the held blocks are the published
``share.layers_first ..``; every block is the same):

- Trunk: ``h = embedding_multiplier * embed[ids]``; per block ``x =
  RMSNorm(h)``, ``h += ssm_out_multiplier * Mamba2(x) +
  attention_out_multiplier * Attn(attention_in_multiplier * x)``, then
  ``x' = RMSNorm(h)``, ``h += down_mult * W_down(silu(gate_mult * x'
  W_gate) * x' W_up)`` with ``(gate_mult, down_mult) =
  mlp_multipliers``; ``logits = lm_head_multiplier * RMSNorm(h) @
  head``; the head is not tied.
- Mamba2 on x (L, d), H = ``mamba_n_heads`` heads of P =
  ``mamba_d_head``, G = ``mamba_n_groups`` groups of a state N =
  ``mamba_d_state``: ``u = ssm_in_multiplier * x``; ``z = m_z u W_z``,
  ``xBC = [m_x | m_B | m_C] * (u W_xBC)``, ``dt = m_dt u W_dt`` with
  ``(m_z, m_x, m_B, m_C, m_dt) = ssm_multipliers``; ``xBC_t = silu(b +
  sum_k w_k xBC_{t-3+k})`` (zeros before the route's first token); x,
  B, C its lanes, head h reading group ``h // (H / G)``; ``dt_t =
  softplus(dt_t + dt_bias)`` at a real token, 0 at a padded one; ``A =
  -exp(A_log)``; ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t^T B_t`` (P x N
  a head, ``S_{-1} = 0``), ``y_t = S_t C_t + D x_t``; ``y = RMSNorm_g(y
  * silu(z))`` over each group's ``mamba_d_ssm / G`` lanes with a
  learned weight (the gate before the norm); ``W_out``.
- Attention on u: ``q = u W_q``, ``k = key_multiplier * u W_k``, ``v = u
  W_v`` by head (``num_attention_heads`` query heads over
  ``num_key_value_heads``, query head h reading ``h // (H / G)``);
  RoPE (rotate-half, ``rope_theta``, the whole head) on q and k;
  ``o_{t,h} = sum_{s<=t} softmax_s(q_{t,h} . k_s / sqrt(head_dim))
  v_s``; ``W_o``.

Departures from the published description: the in-projection is kept
as its three column blocks (z, xBC, dt), the same matrix; ``A_log``,
``dt_bias`` and ``D`` are float32; ``dt`` has no clamp
(``time_step_limit`` is (0, inf) in the family's code). The parameters
are the artifact's pytree (``PARAM_LAYOUT``). ``precision="fp8"`` is
the control: the operands of every product, and x, B and C of the
recurrence, rounded to float8 (e4m3, scaled per tensor) by :func:`e4m3`,
in integer arithmetic on the bits: a cast to float8 and back may be
dropped by the compiler as excess precision where it feeds element-wise
work, as the recurrence's inputs are.

Memory: ``sala_ref.Blocks`` says how many queries and rows are computed
at a time and what a route is padded to; none of them changes a number
(a row of a score matrix is always whole over all keys; a padded token
comes after every real query, and its ``dt`` is 0, so the state read at
the end is the state at the last real token). A layer's weights are
cast to float32 as the layer is reached.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Sequence

import numpy as np

from benchmark.reference.dots3_ref import by_blocks, rms_norm, rope
from benchmark.reference.sala_ref import WHOLE, Blocks

PARAM_LAYOUT = """
embed (V_held, d); head (d, V_held); final_norm (d,)
layers[i]: input_norm (d,), ffn_norm (d,),
  ssm: w_z (d, H_s P), w_xbc (d, H_s P + 2 G N), w_dt (d, H_s),
       conv_w (K, H_s P + 2 G N), conv_b (H_s P + 2 G N,),
       dt_bias (H_s,), a_log (H_s,), d (H_s,), norm (H_s P,),
       w_out (H_s P, d)
  attn: w_q (d, H dh), w_k (d, G_a dh), w_v (d, G_a dh), w_o (H dh, d)
  ffn: w_gate (d, F), w_up (d, F), w_down (F, d)
"""


def e4m3(x):
    """x scaled per tensor so that its largest magnitude is 448, rounded
    to the nearest float8 e4m3 (ties to even; below 2^-6 in steps of
    2^-9) and scaled back, by integer arithmetic on the float32 bits."""
    import jax
    import jax.numpy as jnp

    x = jnp.asarray(x).astype(jnp.float32)
    s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    y = x * s
    bits = jax.lax.bitcast_convert_type(y, jnp.uint32)
    bits = (bits + 0x7FFFF + ((bits >> 20) & 1)) & jnp.uint32(0xFFF00000)
    normal = jax.lax.bitcast_convert_type(bits, jnp.float32)
    return jnp.where(jnp.abs(y) < 2.0 ** -6, jnp.round(y * 512.0) / 512.0,
                     normal) / s


def _operand(x, precision: Optional[str]):
    import jax.numpy as jnp

    if precision == "fp8":
        return e4m3(x)
    if precision:
        raise ValueError(f"unknown control precision {precision!r}")
    return jnp.asarray(x).astype(jnp.float32)


def mm(x, w, precision: Optional[str] = None):
    import jax
    import jax.numpy as jnp

    return jnp.matmul(_operand(x, precision), _operand(w, precision),
                      precision=jax.lax.Precision.HIGHEST)


def layer_indices(cfg: Dict):
    """The published index of each held block."""
    first = int(cfg.get("share", {}).get("layers_first", 0))
    return list(range(first, first + int(cfg["num_hidden_layers"])))


def conv(x, w, b):
    """x (L, C), w (K, C), b (C,): ``b + sum_k w_k x_{t - K + 1 + k}``."""
    import jax.numpy as jnp

    k, n = w.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return b + sum(w[i] * xp[i:i + n] for i in range(k))


def ssm_mixer(p, cfg: Dict, x, n_live, precision=None):
    """x (L, d) the block's normed input → (output (L, d) before
    ``ssm_out_multiplier``, the state (H, P, N) at the last of the
    ``n_live`` real tokens)."""
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    heads, p_dim = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    groups, n_st = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    m_z, m_x, m_b, m_c, m_dt = cfg["ssm_multipliers"]
    wide = heads * p_dim
    u = cfg["ssm_in_multiplier"] * x
    z = m_z * mm(u, p["w_z"], precision)
    xbc = mm(u, p["w_xbc"], precision)
    xbc = jnp.concatenate([m_x * xbc[:, :wide],
                           m_b * xbc[:, wide:wide + groups * n_st],
                           m_c * xbc[:, wide + groups * n_st:]], -1)
    xbc = jax.nn.silu(conv(xbc, p["conv_w"], p["conv_b"]))
    xs = xbc[:, :wide].reshape(n, heads, p_dim)
    b = xbc[:, wide:wide + groups * n_st].reshape(n, groups, n_st)
    c = xbc[:, wide + groups * n_st:].reshape(n, groups, n_st)
    xs, b, c = (_operand(a, precision) for a in (xs, b, c))
    dt = jax.nn.softplus(m_dt * mm(u, p["w_dt"], precision) + p["dt_bias"])
    dt = jnp.where((jnp.arange(n) < n_live)[:, None], dt, 0.0)
    a = -jnp.exp(p["a_log"])
    grp = np.arange(heads) // (heads // groups)

    def token(s, row):
        x_t, dt_t, b_t, c_t = row
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[grp][:, None, :])
        y = jnp.einsum("hpn,hn->hp", s, c_t[grp],
                       precision=jax.lax.Precision.HIGHEST)
        return s, y + p["d"][:, None] * x_t

    state, y = jax.lax.scan(token, jnp.zeros((heads, p_dim, n_st),
                                             jnp.float32), (xs, dt, b, c))
    g = (y.reshape(n, wide) * jax.nn.silu(z)).reshape(n, groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                          + cfg["rms_norm_eps"])
    return mm(g.reshape(n, wide) * p["norm"], p["w_out"], precision), state


def attention(p, cfg: Dict, x, pos, precision=None, blocks: Blocks = WHOLE):
    """x (L, d) the attention's input → (output (L, d) before
    ``attention_out_multiplier``, n_keys (L,), first_key (L,))."""
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    heads, groups, dh = (cfg["num_attention_heads"],
                         cfg["num_key_value_heads"], cfg["head_dim"])
    theta = float(cfg["rope_theta"])
    q = rope(mm(x, p["w_q"], precision).reshape(n, heads, dh), pos, theta)
    k = rope(cfg["key_multiplier"] * mm(x, p["w_k"], precision).reshape(
        n, groups, dh), pos, theta)
    v = mm(x, p["w_v"], precision).reshape(n, groups, dh)
    q = q.reshape(n, groups, heads // groups, dh)
    q, k, v = (_operand(a, precision) for a in (q, k, v))

    def rows(qb, pb):
        keys = pos[None, :] <= pb[:, None]
        s = jnp.einsum("qghd,kgd->qghk", qb, k,
                       precision=jax.lax.Precision.HIGHEST) / math.sqrt(dh)
        s = jnp.where(keys[:, None, None, :], s, -jnp.inf)
        o = jnp.einsum("qghk,kgd->qghd", jax.nn.softmax(s, axis=-1), v,
                       precision=jax.lax.Precision.HIGHEST)
        return (o, keys.sum(-1).astype(jnp.int32),
                jnp.argmax(keys, -1).astype(jnp.int32))

    o, n_keys, first = by_blocks(rows, (q, pos), blocks.q_block)
    return mm(o.reshape(n, heads * dh), p["w_o"], precision), n_keys, first


def mlp(p, cfg: Dict, x, precision=None):
    import jax

    gate_mult, down_mult = cfg["mlp_multipliers"]
    return down_mult * mm(
        jax.nn.silu(gate_mult * mm(x, p["w_gate"], precision))
        * mm(x, p["w_up"], precision), p["w_down"], precision)


def layer(p, cfg: Dict, h, n_live, precision=None, blocks: Blocks = WHOLE):
    """One hybrid block over one route: h (L, d) → (h, taps); rows from
    ``n_live`` on are padding."""
    import jax
    import jax.numpy as jnp

    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), p)
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(h.shape[0], dtype=jnp.int32)
    x = rms_norm(h, p["input_norm"], eps)
    y_ssm, state = ssm_mixer(p["ssm"], cfg, x, n_live, precision)
    y_attn, n_keys, first = attention(
        p["attn"], cfg, cfg["attention_in_multiplier"] * x, pos, precision,
        blocks)
    h = (h + cfg["ssm_out_multiplier"] * y_ssm
         + cfg["attention_out_multiplier"] * y_attn)
    x = rms_norm(h, p["ffn_norm"], eps)
    h = h + by_blocks(lambda r: mlp(p["ffn"], cfg, r, precision), (x,),
                      blocks.row_block)
    return h, {"state": state, "n_keys": n_keys, "first_key": first}


def head(params, cfg: Dict, h, ids, rows_at, precision=None,
         blocks: Blocks = WHOLE):
    """→ (next_logit (L,), lse (L,), rows (P, V))."""
    import jax
    import jax.numpy as jnp

    x = rms_norm(h, params["final_norm"], cfg["rms_norm_eps"])
    nxt = jnp.concatenate([ids[1:], ids[:1]])
    scale = cfg["lm_head_multiplier"]

    def rows(xr, target):
        logits = scale * mm(xr, params["head"], precision)
        return (jnp.take_along_axis(logits, target[:, None], -1)[:, 0],
                jax.nn.logsumexp(logits, axis=-1))

    next_logit, lse = by_blocks(rows, (x, nxt), blocks.row_block)
    return next_logit, lse, scale * mm(x[rows_at], params["head"], precision)


@functools.lru_cache(maxsize=None)
def _jitted(cfg_json: str, precision, blocks_key):
    import json

    import jax

    cfg, blocks = json.loads(cfg_json), Blocks(*blocks_key)
    return (jax.jit(lambda p, h, n_live: layer(p, cfg, h, n_live, precision,
                                                blocks)),
            jax.jit(lambda params, h, ids, rows_at: head(
                params, cfg, h, ids, rows_at, precision, blocks)))


def forward(params: Dict, cfg: Dict, ids, rows_at: Sequence[int] = (), *,
            precision: Optional[str] = None, blocks: Blocks = WHOLE) -> Dict:
    """One route: ids (L,) within the held slice of the vocabulary.
    Returns host arrays: ``next_logit`` (L,) (the logit of ids[t + 1] at
    position t; 0 at the last), ``lse`` (L,), ``loglik``, ``rows`` (P,
    V_held) the logit rows at ``rows_at``, and per held block
    ``n_keys`` and ``first_key`` [(L,)] and ``state`` [(H, P, N)]."""
    import json

    import jax.numpy as jnp

    n = len(ids)
    sizes = {k: v for k, v in cfg.items()
             if isinstance(v, (int, float, bool, list, dict))
             and k not in ("limits", "limit_reasons")}
    layer_fn, head_fn = _jitted(json.dumps(sizes, sort_keys=True),
                                precision or None, blocks.key())
    ids = jnp.pad(jnp.asarray(ids, jnp.int32), (0, blocks.padded(n) - n))
    named = jnp.asarray(list(rows_at) or [0], jnp.int32)
    h = cfg["embedding_multiplier"] * jnp.asarray(params["embed"])[ids] \
        .astype(jnp.float32)
    taps = {"n_keys": [], "first_key": [], "state": []}
    for l in range(len(layer_indices(cfg))):
        h, t = layer_fn(params["layers"][l], h, jnp.int32(n))
        taps["n_keys"].append(np.asarray(t["n_keys"])[:n])
        taps["first_key"].append(np.asarray(t["first_key"])[:n])
        taps["state"].append(np.asarray(t["state"]))
    next_logit, lse, rows = (np.asarray(v) for v in head_fn(
        params, h, ids, named))
    next_logit = next_logit[:n].copy()
    next_logit[n - 1] = 0.0
    lse = lse[:n]
    return {"next_logit": next_logit, "lse": lse,
            "loglik": float(np.sum((next_logit - lse)[:n - 1],
                                   dtype=np.float64)),
            "rows": rows if len(rows_at) else rows[:0], **taps}
