"""Plain reference of the second route-sequence language model (catalog
name ``MiniCPM-SALA``): float32 ``jax.numpy`` at ``highest`` matmul
precision, one route at a time, the linear mixer as the token-by-token
recurrence it is defined by, the sparse mixer by brute force over all
causal keys under the block mask; no kernels, no chunked scan, no
length ladder, no batching.

Equations (d = hidden_size, L = the PUBLISHED number of layers, l the
PUBLISHED index of a layer, dh = head_dim; positions are a token's index
within its own route; pre-norm residual, RMSNorm with eps from the
config):

- Trunk: ``h = scale_emb * embed[ids]``; per layer ``h += r * Mixer_l(
  norm(h))``, ``h += r * W_down(silu(x W_gate) * x W_up)`` with ``x =
  norm(h)`` and ``r = scale_depth / sqrt(L)``; ``logits = (norm(h) @
  head) / (hidden_size / dim_model_base)``; the head is not tied.
- ``lightning-attn`` (``lightning_nh`` heads, as many key-value heads):
  ``q, k, v = x W_q, x W_k, x W_v`` by head; ``qk_norm``: RMSNorm over
  the head's width with a learned weight on q and on k; RoPE
  (rotate-half, ``rope_theta``, over the whole head) on q and k; decay
  ``lam_h = exp(-s_h (1 - l / (L - 1) + 1e-5))``, ``s_h = 2^(-8 h / H)``,
  h = 1..H; ``S_t = lam_h S_{t-1} + k_t^T v_t`` from ``S = 0``, ``o_t =
  q_t S_t / sqrt(dh)``; RMSNorm of the concatenated heads
  (``use_output_norm``), times ``sigmoid(x W_g)`` (``use_output_gate``),
  then ``W_o``.
- ``minicpm4`` (``num_attention_heads`` query heads over
  ``num_key_value_heads`` key-value heads, no RoPE): ``q = RMSNorm(x
  W_q)``, ``k = RMSNorm(x W_k)`` over the head's width, ``v = x W_v``.
  With the sizes of the ``sparse`` group (``kernel_size`` 32,
  ``kernel_stride`` 16, ``block_size`` 64, ``topk`` 64, ``init_blocks``
  1, ``window_size`` 2,048, ``dense_len`` 8,192): compressed keys
  ``kc_j = mean(k[16 j : 16 j + 32])``, visible to query t iff ``16 j +
  31 <= t``; ``p_{t,h,.} = softmax_j(q_{t,h} . kc_j / sqrt(dh))`` over
  the visible j; ``a_{t,g,j}`` its sum over the heads h of group g;
  block score ``b_{t,g,m} = max_{j in [4m - 1, 4m + 3]} a_{t,g,j}``;
  forced: block 0 and every block that meets the keys ``t - 2047 .. t``;
  never: blocks past ``t // 64``; chosen: the 64 best blocks a (t, g),
  ties to the lower block, every allowed block where there are no more
  than 64. Then for h in g the softmax over the keys ``s <= t`` of the
  chosen blocks of ``q_{t,h} . k_s / sqrt(dh)``, times ``v_s``. A route
  of fewer than ``dense_len`` tokens sees every causal key. Times
  ``sigmoid(x W_g)`` (``attn_use_output_gate``), then ``W_o``.

The parameters are the artifact's pytree (``PARAM_LAYOUT``). The held
layers are the published layers ``share.layers_first ..`` of
``mixer_types``. ``precision="fp8"`` is the control: the operands of
every product rounded to float8 (e4m3, scaled per tensor).

Memory: :class:`Blocks` says how many queries and rows are computed at
a time, so that a route of 47k arcs fits the device; none of them
changes a number (a row of a score matrix is always whole over all
keys). A layer's weights are cast to float32 as the layer is reached.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Sequence

import numpy as np

from benchmark.reference.dots3_ref import (_operand, by_blocks, gated_mlp, mm,
                                           rms_norm, rope)

PARAM_LAYOUT = """
embed (V, d); head (d, V); final_norm (d,)
layers[i]: attn_norm (d,), ffn_norm (d,),
  ffn: w_gate (d, F), w_up (d, F), w_down (F, d)
  attn (minicpm4): w_q (d, H*dh), w_k (d, G*dh), w_v (d, G*dh),
        q_norm (dh,), k_norm (dh,), w_gate (d, H*dh), w_o (H*dh, d)
  attn (lightning-attn): w_q, w_k, w_v (d, Hl*dl), q_norm (dl,),
        k_norm (dl,), o_norm (Hl*dl,), w_gate (d, Hl*dl), w_o (Hl*dl, d)
"""

SPARSE, LINEAR = "minicpm4", "lightning-attn"


class Blocks:
    """``q_block``: queries of one sparse-attention product;
    ``row_block``: tokens of one feed-forward or head product;
    ``pad_to``: the route is padded to a multiple of it, or to the least
    of a list of lengths that holds it (tokens past its end come after
    every real query, so causality masks them, and the recurrence's
    state is read at the last real token); the blocks have to divide
    it. ``None`` is everything at once."""

    def __init__(self, q_block=None, row_block=None, pad_to=1):
        self.q_block, self.row_block = q_block, row_block
        self.pad_to = tuple(pad_to) if isinstance(pad_to, (list, tuple)) \
            else pad_to

    def key(self):
        return (self.q_block, self.row_block, self.pad_to)

    def padded(self, n: int) -> int:
        if isinstance(self.pad_to, tuple):
            return min(p for p in self.pad_to if p >= n)
        return -(-n // self.pad_to) * self.pad_to


WHOLE = Blocks()


def layer_kinds(cfg: Dict):
    """(mixer kind, published index) of each layer that is held."""
    first = int(cfg.get("share", {}).get("layers_first", 0))
    return [(cfg["mixer_types"][first + i], first + i)
            for i in range(cfg["num_hidden_layers"])]


def published_layers(cfg: Dict) -> int:
    return int(cfg.get("published", {}).get("num_hidden_layers",
                                            cfg["num_hidden_layers"]))


def decay(cfg: Dict, layer: int):
    """lam_h of the published layer ``layer``, (H,) float32."""
    import jax.numpy as jnp

    heads, n = cfg["lightning_nh"], published_layers(cfg)
    slope = 2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=jnp.float32)
                    / heads)
    return jnp.exp(-slope * (1.0 - layer / (n - 1) + 1e-5))


# ── the linear mixer ─────────────────────────────────────────────────


def linear_mixer(p, cfg: Dict, layer: int, x, pos, n_live, precision=None):
    """x (L, d) the block's normed input → (output (L, d), final state
    (H, dl, dl): S at the last of the ``n_live`` real tokens)."""
    import jax
    import jax.numpy as jnp

    n, eps = x.shape[0], cfg["rms_norm_eps"]
    heads, dl = cfg["lightning_nh"], cfg["lightning_head_dim"]
    q = mm(x, p["w_q"], precision).reshape(n, heads, dl)
    k = mm(x, p["w_k"], precision).reshape(n, cfg["lightning_nkv"], dl)
    v = mm(x, p["w_v"], precision).reshape(n, cfg["lightning_nkv"], dl)
    if cfg["qk_norm"]:
        q, k = rms_norm(q, p["q_norm"], eps), rms_norm(k, p["k_norm"], eps)
    if cfg["lightning_use_rope"]:
        theta = float(cfg["rope_theta"])
        q, k = rope(q, pos, theta), rope(k, pos, theta)
    q, k, v = (_operand(a, precision) for a in (q, k, v))
    lam = decay(cfg, layer)[:, None, None]

    def token(s, row):
        q_t, k_t, v_t, t = row
        s_new = lam * s + k_t[:, :, None] * v_t[:, None, :]
        s = jnp.where(t < n_live, s_new, s)
        o = jnp.einsum("hd,hde->he", q_t, s,
                       precision=jax.lax.Precision.HIGHEST)
        return s, o / math.sqrt(dl)

    state, o = jax.lax.scan(token, jnp.zeros((heads, dl, dl), jnp.float32),
                            (q, k, v, jnp.arange(n)))
    o = o.reshape(n, heads * dl)
    if cfg["use_output_norm"]:
        o = rms_norm(o, p["o_norm"], eps)
    if cfg["use_output_gate"]:
        o = o * jax.nn.sigmoid(mm(x, p["w_gate"], precision))
    return mm(o, p["w_o"], precision), state


# ── the sparse mixer ─────────────────────────────────────────────────


def compressed_keys(k, size: int, stride: int):
    """k (L, G, dh) → (J, G, dh), J = (L - size) // stride + 1."""
    import jax.numpy as jnp

    n_comp = (k.shape[0] - size) // stride + 1
    idx = stride * jnp.arange(n_comp)[:, None] + jnp.arange(size)[None, :]
    return k[idx].mean(1)


def chosen_blocks(q, kc, pos_q, n_blocks: int, sp: Dict, scale: float):
    """Stage 1 for a block of queries: q (Q, G, Hg, dh), kc (J, G, dh) →
    (Q, G, M) bool."""
    import jax
    import jax.numpy as jnp

    size, stride, block = sp["kernel_size"], sp["kernel_stride"], \
        sp["block_size"]
    n_comp, per = kc.shape[0], block // stride
    j = jnp.arange(n_comp)
    visible = (stride * j[None, :] + size - 1) <= pos_q[:, None]   # (Q, J)
    s = jnp.einsum("qghd,jgd->qghj", q, kc,
                   precision=jax.lax.Precision.HIGHEST) * scale
    s = jnp.where(visible[:, None, None, :], s, -jnp.inf)
    # a query with no visible compressed key (t < size - 1) gets zeros
    p = jnp.exp(s - jnp.maximum(s.max(-1, keepdims=True), -1e30))
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    a = p.sum(2)                                               # (Q, G, J)
    # max-pool: block m takes j in [per * m - 1, per * m + per - 1]
    js = per * jnp.arange(n_blocks)[:, None] - 1 + jnp.arange(per + 1)[None]
    inside = (js >= 0) & (js < n_comp)
    pooled = jnp.where(inside, a[..., jnp.clip(js, 0, n_comp - 1)],
                       -jnp.inf).max(-1)                       # (Q, G, M)
    m = jnp.arange(n_blocks)[None, :]
    t = pos_q[:, None]
    allowed = m <= t // block
    forced = (m < sp["init_blocks"]) | (
        allowed & (block * m + block - 1 >= t - (sp["window_size"] - 1)))
    score = jnp.where(forced[:, None], jnp.inf, pooled)
    score = jnp.where(allowed[:, None], score, -jnp.inf)
    top = min(sp["topk"], n_blocks)
    _, idx = jax.lax.top_k(score, top)
    rows, grp = jnp.arange(q.shape[0])[:, None, None], \
        jnp.arange(q.shape[1])[None, :, None]
    picked = jnp.zeros(score.shape, bool).at[rows, grp, idx].set(True)
    return picked & allowed[:, None]


def sparse_mixer(p, cfg: Dict, x, pos, n_live, rows_at, precision=None,
                 blocks: Blocks = WHOLE):
    """x (L, d) → (output (L, d), taps): ``n_keys`` (L, G) the keys each
    (query, group) saw, ``n_visible`` (L,) the compressed keys visible
    to each query, ``blocks`` (P, G, M) the blocks of the named
    queries."""
    import jax
    import jax.numpy as jnp

    n, eps, sp = x.shape[0], cfg["rms_norm_eps"], cfg["sparse"]
    heads, groups, dh = (cfg["num_attention_heads"],
                         cfg["num_key_value_heads"], cfg["head_dim"])
    per, block = heads // groups, sp["block_size"]
    scale = 1.0 / math.sqrt(dh)
    q = mm(x, p["w_q"], precision).reshape(n, groups, per, dh)
    k = mm(x, p["w_k"], precision).reshape(n, groups, dh)
    v = mm(x, p["w_v"], precision).reshape(n, groups, dh)
    if cfg["qk_norm"]:
        q, k = rms_norm(q, p["q_norm"], eps), rms_norm(k, p["k_norm"], eps)
    if cfg["attn_use_rope"]:
        theta = float(cfg["rope_theta"])
        q = rope(q.reshape(n, heads, dh), pos, theta).reshape(q.shape)
        k = rope(k, pos, theta)
    n_blocks = -(-n // block)
    kc = _operand(compressed_keys(k, sp["kernel_size"],
                                  sp["kernel_stride"]), precision)
    q, k, v = (_operand(a, precision) for a in (q, k, v))
    key_block = jnp.arange(n) // block
    selecting = n_live >= sp["dense_len"]

    def rows(qb, pb):
        chosen = chosen_blocks(qb, kc, pb, n_blocks, sp, scale)   # (Q,G,M)
        causal_blocks = jnp.broadcast_to(
            (jnp.arange(n_blocks)[None, :] <= pb[:, None] // block)[:, None],
            chosen.shape)
        chosen = jnp.where(selecting, chosen, causal_blocks)
        keys = chosen[:, :, key_block] & (pos[None, None, :]
                                          <= pb[:, None, None])  # (Q,G,L)
        s = jnp.einsum("qghd,kgd->qghk", qb, k,
                       precision=jax.lax.Precision.HIGHEST) * scale
        s = jnp.where(keys[:, :, None, :], s, -jnp.inf)
        o = jnp.einsum("qghk,kgd->qghd", jax.nn.softmax(s, axis=-1), v,
                       precision=jax.lax.Precision.HIGHEST)
        return o, keys.sum(-1).astype(jnp.int32), chosen

    o, n_keys, chosen = by_blocks(rows, (q, pos), blocks.q_block)
    size, stride = sp["kernel_size"], sp["kernel_stride"]
    n_visible = jnp.clip((pos - (size - 1)) // stride + 1, 0, None)
    o = o.reshape(n, heads * dh)
    if cfg["attn_use_output_gate"]:
        o = o * jax.nn.sigmoid(mm(x, p["w_gate"], precision))
    return mm(o, p["w_o"], precision), {
        "n_keys": n_keys, "n_visible": n_visible.astype(jnp.int32),
        "blocks": chosen[rows_at]}


# ── the model ────────────────────────────────────────────────────────


def layer(p, cfg: Dict, kind: str, index: int, h, rows_at, n_live,
          precision=None, blocks: Blocks = WHOLE):
    """One pre-norm residual layer over one route: h (L, d) → (h,
    taps); rows from ``n_live`` on are padding."""
    import jax
    import jax.numpy as jnp

    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), p)
    eps = cfg["rms_norm_eps"]
    r = cfg["scale_depth"] / math.sqrt(published_layers(cfg))
    pos = jnp.arange(h.shape[0], dtype=jnp.int32)
    x = rms_norm(h, p["attn_norm"], eps)
    if kind == LINEAR:
        y, state = linear_mixer(p["attn"], cfg, index, x, pos, n_live,
                                precision)
        taps = {"state": state}
    else:
        y, taps = sparse_mixer(p["attn"], cfg, x, pos, n_live, rows_at,
                               precision, blocks)
    h = h + r * y
    x = rms_norm(h, p["ffn_norm"], eps)
    return h + r * by_blocks(lambda rows: gated_mlp(rows, p["ffn"],
                                                    precision),
                             (x,), blocks.row_block), taps


def head(params, cfg: Dict, h, ids, rows_at, precision=None,
         blocks: Blocks = WHOLE):
    """→ (next_logit (L,), lse (L,), rows (P, V))."""
    import jax
    import jax.numpy as jnp

    x = rms_norm(h, params["final_norm"], cfg["rms_norm_eps"])
    nxt = jnp.concatenate([ids[1:], ids[:1]])
    shrink = cfg["hidden_size"] / cfg["dim_model_base"]

    def rows(xr, target):
        logits = mm(xr, params["head"], precision) / shrink
        return (jnp.take_along_axis(logits, target[:, None], -1)[:, 0],
                jax.nn.logsumexp(logits, axis=-1))

    next_logit, lse = by_blocks(rows, (x, nxt), blocks.row_block)
    return next_logit, lse, mm(x[rows_at], params["head"], precision) / shrink


@functools.lru_cache(maxsize=None)
def _jitted(cfg_json: str, precision, blocks_key):
    import json

    import jax

    cfg, blocks = json.loads(cfg_json), Blocks(*blocks_key)
    return (jax.jit(lambda p, h, rows_at, kind, index, n_live: layer(
                p, cfg, kind, index, h, rows_at, n_live, precision, blocks),
                static_argnums=(3, 4)),
            jax.jit(lambda params, h, ids, rows_at: head(
                params, cfg, h, ids, rows_at, precision, blocks)))


def forward(params: Dict, cfg: Dict, ids, rows_at: Sequence[int] = (), *,
            precision: Optional[str] = None, blocks: Blocks = WHOLE) -> Dict:
    """One route: ids (L,). Returns host arrays: ``next_logit`` (L,)
    (the logit of ids[t + 1] at position t; 0 at the last), ``lse``
    (L,), ``loglik``, ``rows`` (P, V) the logit rows at ``rows_at``, and
    the taps: ``n_keys`` [(L, G)], ``n_visible`` [(L,)] and ``blocks``
    [(P, G, M)] per sparse layer (M the blocks of the real length),
    ``state`` [(H, dl, dl)] per linear layer."""
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
    h = cfg["scale_emb"] * jnp.asarray(params["embed"])[ids].astype(
        jnp.float32)
    taps = {"n_keys": [], "n_visible": [], "blocks": [], "state": []}
    n_blocks = -(-n // cfg["sparse"]["block_size"])
    for l, (kind, index) in enumerate(layer_kinds(cfg)):
        h, t = layer_fn(params["layers"][l], h, named, kind, index,
                        jnp.int32(n))
        if kind == LINEAR:
            taps["state"].append(np.asarray(t["state"]))
        else:
            taps["n_keys"].append(np.asarray(t["n_keys"])[:n])
            taps["n_visible"].append(np.asarray(t["n_visible"])[:n])
            taps["blocks"].append(np.asarray(t["blocks"])[..., :n_blocks])
    next_logit, lse, rows = (np.asarray(v) for v in head_fn(
        params, h, ids, named))
    next_logit = next_logit[:n].copy()
    next_logit[n - 1] = 0.0
    lse = lse[:n]
    return {"next_logit": next_logit, "lse": lse,
            "loglik": float(np.sum((next_logit - lse)[:n - 1],
                                   dtype=np.float64)),
            "rows": rows if len(rows_at) else rows[:0], **taps}
