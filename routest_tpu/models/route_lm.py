"""Route-sequence language model: next-arc likelihood over whole route
histories, at the widths of a published sparse-expert model.

A route history is a sequence of arc ids (map-matched, one token an
arc); the model gives every position the distribution of the next arc,
and a fleet planner ranks candidate tours by their likelihood. The
architecture is the one published as ``dots3-note-prev`` (its
``config.json`` keys are this model's ``sizes``; the vision and audio
towers and the multi-token-prediction module of that family are not in
those keys and are not built). Every line of this file speaks of that
architecture; the second model behind the same scorer (``MiniCPM-SALA``)
is ``models/route_lm_sala.py``, the third (``K-EXAONE-236B-A23B``)
``models/route_lm_kexaone.py``, and what the three share — the norm,
RoPE, the chunked next-arc head, the expert layers' pass counts — lives
in ``models/lm_common.py``:

- pre-norm residual blocks, RMSNorm, ``hidden_size`` wide;
- two kinds of latent attention in one model (``layer_types``): a
  **full** layer whose queries see a learned selection of at most
  ``index_topk`` keys (a 64-head selector scores every earlier key),
  and a **sliding** layer of its own head count, ranks, head widths
  and RoPE base that sees ``sliding_window_size`` keys; both with a
  per-head sigmoid gate on the output;
- a dense gated MLP in the first ``first_k_dense_replace`` layers, then
  ``n_routed_experts`` routed experts, top ``num_experts_per_tok`` by
  sigmoid score plus a correction bias, and a shared expert.

**One chip's share of a layer.** The model is told what it holds:
``layers_held`` leading layers, the routed experts ``experts_first ..
experts_first + experts_held - 1`` of every expert layer, and
``vocab_held`` rows of the vocabulary (ids are drawn from that slice,
logits and the log-sum-exp are over it); the attention weights are
whole. It routes over all experts and adds its own experts' terms and
the shared expert (``parallel/expert.py``); what the absent experts
would add is left out and that partial result goes on to the next
layer. Nothing here stands in for the other chips.

The equations are written out in ``benchmark/reference/dots3_ref.py``,
the plain float32 reference this model is tested against. Here the
parameters and activations are ``policy.compute_dtype`` (bfloat16),
products accumulate in float32, and the norms' statistics, the router's
and the selector's scores and every softmax are float32. Attention
runs a block of queries at a time (``parallel/select.py``) with the
queries up-projected from their latents inside the loop; the keys and
values are expanded once (not absorbed into the latents: under a mask
over all causal keys the absorbed form costs 3.4 times the products).

``apply`` takes a batch of routes padded to one length; a route's
outputs depend on nothing but its own tokens.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Mapping, Tuple

import jax
import jax.numpy as jnp

from routest_tpu.core.dtypes import BF16_POLICY, Policy
from routest_tpu.models.lm_common import dot32 as _dot
from routest_tpu.models.lm_common import (expert_pass_counts, next_arc_head,
                                          rms_norm, rope)
from routest_tpu.parallel.expert import (ExpertShare, expert_path, gated_mlp,
                                         moe_share, row_tile_of)
from routest_tpu.parallel.select import (attention_path, block_and_chunk,
                                         chunk_steps, selected_attention,
                                         selected_rows, topk_blocks,
                                         window_path, window_span,
                                         windowed_attention)

Params = Dict

LN_EPS = 1e-6
FULL, SLIDING = "full_attention", "sliding_attention"
# the published keys the model reads; an artifact's header carries them
SIZE_KEYS = (
    "apply_mla_qkv_lora_rescale", "first_k_dense_replace", "hidden_size",
    "index_head_dim", "index_n_heads", "index_topk", "intermediate_size",
    "kv_lora_rank", "layer_types", "moe_intermediate_size",
    "n_routed_experts", "n_shared_experts", "norm_topk_prob",
    "num_attention_heads", "num_experts_per_tok", "num_hidden_layers",
    "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "rms_norm_eps",
    "rope_theta", "routed_scaling_factor", "sliding_window_size",
    "swa_kv_lora_rank", "swa_num_attention_heads", "swa_q_lora_rank",
    "swa_qk_nope_head_dim", "swa_qk_rope_head_dim", "swa_rope_theta",
    "swa_v_head_dim", "v_head_dim", "vocab_size")


def layer_norm(x, w, b, eps: float = LN_EPS):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, -1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
            + b.astype(jnp.float32))


@dataclasses.dataclass(frozen=True)
class AttentionSizes:
    heads: int
    d_nope: int
    d_rope: int
    d_v: int
    r_q: int
    r_kv: int
    theta: float
    s_q: float
    s_kv: float
    window: int = 0          # sliding layers
    top_k: int = 0           # full layers
    index_heads: int = 0
    index_dim: int = 0

    @property
    def scale(self) -> float:
        return 1.0 / math.sqrt(self.d_nope + self.d_rope)


@dataclasses.dataclass(frozen=True)
class RouteLM:
    sizes: Mapping              # the published keys, published values
    layers_held: int
    experts_held: int
    vocab_held: int
    experts_first: int = 0
    chips_per_layer: int = 1
    policy: Policy = BF16_POLICY
    # rows of a block of queries (full / sliding layers) and of a chunk
    # of keys: lengths are padded to multiples of the larger block
    select_block: int = 256
    window_block: int = 512
    key_chunk: int = 2048

    @classmethod
    def from_config(cls, cfg: Mapping, policy: Policy = BF16_POLICY):
        """From a configuration that states the share: the published
        keys, where ``num_hidden_layers``, ``n_routed_experts`` and
        ``vocab_size`` give what is HELD and ``cfg["published"]`` the
        published counts of those three; ``cfg["share"]`` names
        ``chips_per_layer`` and ``experts_first``. The block sizes may
        be stated too (a toy size states smaller ones)."""
        sizes = {k: cfg[k] for k in SIZE_KEYS}
        sizes.update(cfg.get("published", {}))
        share = cfg.get("share", {})
        blocks = {k: int(cfg[k]) for k in ("select_block", "window_block",
                                           "key_chunk") if k in cfg}
        return cls(sizes=sizes, layers_held=int(cfg["num_hidden_layers"]),
                   experts_held=int(cfg["n_routed_experts"]),
                   vocab_held=int(cfg["vocab_size"]),
                   experts_first=int(share.get("experts_first", 0)),
                   chips_per_layer=int(share.get("chips_per_layer", 1)),
                   policy=policy, **blocks)

    # ── what the share holds ────────────────────────────────────────

    @property
    def share(self) -> ExpertShare:
        return ExpertShare(int(self.sizes["n_routed_experts"]),
                           self.experts_first, self.experts_held)

    def share_header(self) -> Dict:
        return {"layers_held": self.layers_held,
                "experts_held": self.experts_held,
                "experts_first": self.experts_first,
                "vocab_held": self.vocab_held,
                "chips_per_layer": self.chips_per_layer}

    def holds(self, params: Params) -> bool:
        """Whether the arrays are this share: as many layers, the held
        experts in each expert layer, the held rows of the vocabulary."""
        held = [p["ffn"]["w_gate"].shape[0] for p in params["layers"]
                if "router" in p["ffn"]]
        return (len(params["layers"]) == self.layers_held
                and params["embed"].shape[0] == self.vocab_held
                and all(n == self.experts_held for n in held))

    def layer_kinds(self) -> List[Tuple[str, str]]:
        dense = int(self.sizes["first_k_dense_replace"])
        return [(self.sizes["layer_types"][l],
                 "dense" if l < dense else "moe")
                for l in range(self.layers_held)]

    def latent_scales(self, kind: str) -> Tuple[float, float]:
        """(s_q, s_kv): the scale correction of the normed latents,
        sqrt(hidden / rank) where the config asks for it."""
        s, pre = self.sizes, "swa_" if kind == SLIDING else ""
        if not s["apply_mla_qkv_lora_rescale"]:
            return 1.0, 1.0
        return (math.sqrt(s["hidden_size"] / s[pre + "q_lora_rank"]),
                math.sqrt(s["hidden_size"] / s[pre + "kv_lora_rank"]))

    def attention_sizes(self, kind: str) -> AttentionSizes:
        s = self.sizes
        pre = "swa_" if kind == SLIDING else ""
        r_q, r_kv = s[pre + "q_lora_rank"], s[pre + "kv_lora_rank"]
        s_q, s_kv = self.latent_scales(kind)
        common = dict(
            heads=s[pre + "num_attention_heads"],
            d_nope=s[pre + "qk_nope_head_dim"],
            d_rope=s[pre + "qk_rope_head_dim"], d_v=s[pre + "v_head_dim"],
            r_q=r_q, r_kv=r_kv, theta=float(s[pre + "rope_theta"]),
            s_q=s_q, s_kv=s_kv)
        if kind == SLIDING:
            return AttentionSizes(window=s["sliding_window_size"], **common)
        return AttentionSizes(top_k=s["index_topk"],
                              index_heads=s["index_n_heads"],
                              index_dim=s["index_head_dim"], **common)

    def selected_steps(self, length: int) -> Tuple[str, int]:
        """For a route padded to ``length``, in one full layer: which
        online-softmax step runs (``"fused"`` or ``"xla"``: what
        ``select.attention_path`` says of this model's shapes here) and
        how many steps over chunks of keys it takes."""
        a = self.attention_sizes(FULL)
        block, chunk = block_and_chunk(length, self.select_block,
                                       self.key_chunk)
        path = attention_path(a.heads, block, chunk, a.d_nope, a.d_rope,
                              a.d_v, self.policy.compute_dtype)
        return path, chunk_steps(length, self.select_block, self.key_chunk)

    def topk_blocks(self, length: int) -> Tuple[str, int]:
        """For a route padded to ``length``, in one full layer: which
        form of the radix top-k runs (``"fused"`` or ``"xla"``) and how
        many blocks of queries run it (``select.topk_blocks``)."""
        return topk_blocks(length, self.select_block,
                           self.attention_sizes(FULL).top_k)

    def window_steps(self, length: int) -> Tuple[str, int]:
        """For a route padded to ``length``, in one sliding layer: which
        window step runs (``"fused"`` or ``"xla"``: what
        ``select.window_path`` says of this model's shapes here) and how
        many blocks of queries it takes."""
        a = self.attention_sizes(SLIDING)
        block = min(self.window_block, length)
        path = window_path(a.heads, block,
                           window_span(length, block, a.window), a.d_nope,
                           a.d_rope, a.d_v, self.policy.compute_dtype)
        return path, length // block

    def expert_blocks(self) -> Tuple[str, int]:
        """(the form of the held experts' grouped product at this
        model's widths, the expert layers held)."""
        n_moe = sum(1 for _, f in self.layer_kinds() if f == "moe")
        return expert_path(int(self.sizes["hidden_size"]),
                           int(self.sizes["moe_intermediate_size"]),
                           self.policy.compute_dtype), n_moe

    # ── what the scorer asks of a model (serve/seq_score.py) ────────

    @property
    def length_quantum(self) -> int:
        return int(math.lcm(self.select_block, self.window_block))

    def tap_tables(self, n_rows: int, width: int, n_named: int) -> Dict:
        """name → (shape, dtype, axis of the length, tokens an entry of
        that axis)."""
        kinds = self.layer_kinds()
        n_moe = sum(1 for _, f in kinds if f == "moe")
        n_full = sum(1 for a, _ in kinds if a == FULL)
        out = {"n_keys": ((len(kinds), n_rows, width), jnp.int32, 2, 1),
               "first_key": ((len(kinds), n_rows, width), jnp.int32, 2, 1)}
        if n_moe:
            out["chosen"] = ((n_moe, n_rows, width,
                              int(self.sizes["num_experts_per_tok"])),
                             jnp.int32, 2, 1)
        if n_full:
            out["selected"] = ((n_full, n_rows, n_named, width), jnp.bool_,
                               3, 1)
        return out

    def step_attrs(self, length: int) -> Dict[str, str]:
        path, window = self.selected_steps(length)[0], self.window_steps(
            length)[0]
        attrs = {"attention": path, "window": window,
                 "mixers": f"full={path},sliding={window}"}
        experts, n_moe = self.expert_blocks()
        if n_moe:
            attrs["experts"] = experts
        return attrs

    def step_stats(self, out: Dict, lengths) -> Dict:
        """Device values of one step for the pass's counters."""
        stats = {}
        if "counts" in out:
            stats["counts"] = out["counts"]
        if "selected" in out:        # the model has selecting layers
            full = [l for l, (a, _) in enumerate(self.layer_kinds())
                    if a == FULL]
            real = (jnp.arange(out["n_keys"].shape[2])[None, :]
                    < lengths[:, None])
            stats["selected_keys"] = jnp.sum(
                jnp.where(real[None], out["n_keys"][jnp.asarray(full)], 0))
        return stats

    def pass_counts(self, steps, stats, real: int) -> List[Tuple]:
        """(family, labels, value) of one pass for the scorer's
        counters and gauges: from the plan, and ``stats`` fetched once
        after the pass's sync."""
        import numpy as np

        n_full = sum(1 for a, _ in self.layer_kinds() if a == FULL)
        n_sliding = len(self.layer_kinds()) - n_full
        experts, n_moe = self.expert_blocks()
        out = []
        for step in steps:
            path, chunks = self.selected_steps(step.length)
            out.append(("chunks", {"path": path},
                        chunks * len(step.routes) * n_full))
            path, blocks = self.window_steps(step.length)
            out.append(("window_blocks", {"path": path},
                        blocks * len(step.routes) * n_sliding))
            if n_moe:
                out.append(("expert_blocks", {"path": experts}, n_moe))
            path, blocks = self.topk_blocks(step.length)
            if blocks:
                out.append(("topk_blocks", {"path": path},
                            blocks * len(step.routes) * n_full))
        counts = [s["counts"] for s in stats if "counts" in s]
        if counts:
            k = int(self.sizes["num_experts_per_tok"])
            out += expert_pass_counts(counts,
                                      k * real * np.shape(counts[0])[0],
                                      row_tile_of(experts))
        picked = [float(s["selected_keys"]) for s in stats
                  if "selected_keys" in s]
        if picked:
            out.append(("selected", {}, sum(picked) / max(1, real * n_full)))
        return out

    # ── parameters ──────────────────────────────────────────────────

    def init(self, key: jax.Array) -> Params:
        """Seeded random parameters in ``policy.param_dtype``: matrices
        normal with standard deviation 1/sqrt(fan-in), but the latents'
        up-projections sqrt(2)/(s sqrt(rank)) with ``s`` the latent's
        scale correction, so that queries, keys and values have variance
        2 and an attention logit standard deviation 2 at init (a trained
        model's attention is neither uniform nor one-hot; without this
        the correction alone makes every softmax one-hot and a bfloat16
        rounding flips its winner); norm weights 1 + 0.1 normal; the
        router's correction bias 0.01 normal (not zero, so that a layer
        that forgot it shows; as small as a balanced router's)."""
        dt = self.policy.param_dtype
        s, d = self.sizes, self.sizes["hidden_size"]
        keys = iter(jax.random.split(key, 64 * (self.layers_held + 1)))

        def mat(*shape, gain=1.0):
            return (jax.random.normal(next(keys), shape, dt)
                    * jnp.asarray(gain / math.sqrt(shape[-2]), dt))

        def near_one(n):
            return (1.0 + 0.1 * jax.random.normal(next(keys), (n,),
                                                  jnp.float32)).astype(dt)

        def mlp(width, lead=()):
            return {"w_gate": mat(*lead, d, width),
                    "w_up": mat(*lead, d, width),
                    "w_down": mat(*lead, width, d)}

        layers = []
        for attn_kind, ffn_kind in self.layer_kinds():
            a = self.attention_sizes(attn_kind)
            s_q, s_kv = self.latent_scales(attn_kind)
            attn = {"w_dq": mat(d, a.r_q), "q_norm": near_one(a.r_q),
                    "w_uq": mat(a.r_q, a.heads * (a.d_nope + a.d_rope),
                                gain=math.sqrt(2.0) / s_q),
                    "w_dkv": mat(d, a.r_kv + a.d_rope),
                    "kv_norm": near_one(a.r_kv),
                    "w_ukv": mat(a.r_kv, a.heads * (a.d_nope + a.d_v),
                                 gain=math.sqrt(2.0) / s_kv),
                    "w_gate": mat(d, a.heads),
                    "w_o": mat(a.heads * a.d_v, d)}
            if attn_kind == FULL:
                attn["idx"] = {
                    "w_q": mat(a.r_q, a.index_heads * a.index_dim),
                    "w_k": mat(d, a.index_dim),
                    "k_norm_w": near_one(a.index_dim),
                    "k_norm_b": (near_one(a.index_dim).astype(jnp.float32)
                                 - 1.0).astype(dt),
                    "w_w": mat(d, a.index_heads)}
            if ffn_kind == "dense":
                ffn = mlp(s["intermediate_size"])
            else:
                m = s["moe_intermediate_size"]
                ffn = mlp(m, lead=(self.experts_held,))
                ffn["router"] = mat(d, s["n_routed_experts"])
                ffn["bias"] = 0.01 * jax.random.normal(
                    next(keys), (s["n_routed_experts"],), jnp.float32)
                ffn["shared"] = mlp(m * s["n_shared_experts"])
            layers.append({"attn_norm": near_one(d), "ffn_norm": near_one(d),
                           "attn": attn, "ffn": ffn})
        return {"embed": jax.random.normal(next(keys), (self.vocab_held, d),
                                           dt),
                "head": mat(d, self.vocab_held), "final_norm": near_one(d),
                "layers": layers}

    # ── blocks ──────────────────────────────────────────────────────

    def attention(self, layer: int, kind: str, p: Params, x, rows_at):
        """One attention block: x (B, L, d) the block's normed input →
        (y (B, L, d), taps). ``taps``: ``n_keys`` and ``first_key`` (B,
        L) of every query and, for a full layer, ``selected`` (B, P, L):
        the key sets of the queries named in ``rows_at`` (B, P)."""
        a = self.attention_sizes(kind)
        eps, dt = self.sizes["rms_norm_eps"], x.dtype
        b_sz, length, _ = x.shape
        pos = jnp.arange(length, dtype=jnp.int32)
        scope = f"lm.L{layer}"
        with jax.named_scope(scope + (".swa" if kind == SLIDING else ".mla")):
            c_q = rms_norm(_dot(x, p["w_dq"]), p["q_norm"], eps)
            c_q = (c_q * a.s_q).astype(dt)
            kv = _dot(x, p["w_dkv"])
            c_kv = (rms_norm(kv[..., :a.r_kv], p["kv_norm"], eps)
                    * a.s_kv).astype(dt)
            k_shared = rope(kv[..., a.r_kv:], pos[None], a.theta).astype(dt)
            w_ukv = p["w_ukv"].reshape(a.r_kv, a.heads, a.d_nope + a.d_v)
            k = jnp.einsum("blr,rhd->blhd", c_kv, w_ukv[..., :a.d_nope],
                           preferred_element_type=jnp.float32).astype(dt)
            v = jnp.einsum("blr,rhd->blhd", c_kv, w_ukv[..., a.d_nope:],
                           preferred_element_type=jnp.float32).astype(dt)
            gate = jax.nn.sigmoid(_dot(x, p["w_gate"]))
            w_uq = p["w_uq"].reshape(a.r_q, a.heads, a.d_nope + a.d_rope)

            block = min(self.window_block if kind == SLIDING
                        else self.select_block, length)

            def q_fn(b, t0):
                cq = jax.lax.dynamic_slice_in_dim(c_q[b], t0, block, 0)
                q = jnp.einsum("qr,rhd->qhd", cq, w_uq,
                               preferred_element_type=jnp.float32)
                t = t0 + jnp.arange(block, dtype=jnp.int32)
                return (q[..., :a.d_nope].astype(dt),
                        rope(q[..., a.d_nope:], t, a.theta).astype(dt))

            if kind == SLIDING:
                out, n_keys, first = windowed_attention(
                    q_fn, k, k_shared, v, window=a.window, scale=a.scale,
                    block=block)
                taps = {}
            else:
                out, n_keys, first, taps = self._selected(
                    scope, p["idx"], a, x, c_q, k, k_shared, v, q_fn, block,
                    rows_at)
            out = (out.astype(jnp.float32) * gate[..., None]).astype(dt)
            y = _dot(out.reshape(b_sz, length, -1), p["w_o"]).astype(dt)
        return y, dict(taps, n_keys=n_keys, first_key=first)

    def _selected(self, scope, p, a, x, c_q, k, k_shared, v, q_fn, block,
                  rows_at):
        dt = x.dtype
        b_sz, length, _ = x.shape
        pos = jnp.arange(length, dtype=jnp.int32)
        dr = a.d_rope

        def half_rope(y, t):
            return jnp.concatenate(
                [rope(y[..., :dr], t, a.theta),
                 y[..., dr:].astype(jnp.float32)], -1).astype(dt)

        def queries(cq, xq, t):
            """Selector queries of some rows: (q_idx, w_idx)."""
            q = _dot(cq, p["w_q"]).reshape(cq.shape[:-1]
                                           + (a.index_heads, a.index_dim))
            w = _dot(xq, p["w_w"]) * (a.index_heads ** -0.5
                                      * a.index_dim ** -0.5)
            return half_rope(q, t), w

        with jax.named_scope(scope + ".selector"):
            k_idx = half_rope(layer_norm(_dot(x, p["w_k"]), p["k_norm_w"],
                                         p["k_norm_b"]), pos[None])

        def idx_fn(b, t0):
            return queries(
                jax.lax.dynamic_slice_in_dim(c_q[b], t0, block, 0),
                jax.lax.dynamic_slice_in_dim(x[b], t0, block, 0),
                t0 + jnp.arange(block, dtype=jnp.int32))

        out, n_keys, first = selected_attention(
            q_fn, k, k_shared, v, idx_fn, k_idx,
            top_k=a.top_k, scale=a.scale, block=block, chunk=self.key_chunk,
            scope=scope)

        def named(cq, xq, kk, t):
            return selected_rows(*queries(cq[t], xq[t], t), kk, t, a.top_k)

        with jax.named_scope(scope + ".selector"):
            chosen = jax.vmap(named)(c_q, x, k_idx, rows_at)
        return out, n_keys, first, {"selected": chosen}

    def ffn(self, layer: int, kind: str, p: Params, x, valid):
        """x (T, d) the block's normed input, ``valid`` (T,) → (y (T, d)
        float32, taps): ``chosen`` (T, k) and ``counts`` (experts_held,)
        for an expert layer."""
        if kind == "dense":
            with jax.named_scope(f"lm.L{layer}.dense"):
                return gated_mlp(x, p["w_gate"], p["w_up"], p["w_down"]), {}
        return moe_share(p, x, int(self.sizes["num_experts_per_tok"]),
                         self.share,
                         float(self.sizes["routed_scaling_factor"]),
                         valid=valid, scope=f"lm.L{layer}.moe")

    def head(self, params: Params, h, ids, lengths, rows_at):
        """→ next_logit (B, L), lse (B, L), rows (B, P, V)."""
        return next_arc_head(params, h, ids, lengths, rows_at,
                             self.sizes["rms_norm_eps"])

    # ── the model ───────────────────────────────────────────────────

    def apply(self, params: Params, ids, lengths, rows_at) -> Dict:
        """ids (B, L) int32 within the held slice, padded past
        ``lengths`` (B,); ``rows_at`` (B, P) positions whose whole logit
        row is wanted. → per position ``next_logit`` (the logit of
        ids[t + 1]; 0 where there is none) and ``lse`` (B, L) float32,
        per route ``loglik`` (B,), ``rows`` (B, P, vocab_held), and the
        taps: ``chosen`` (n_moe, B, L, k), ``counts`` (n_moe,
        experts_held), ``n_keys`` / ``first_key`` (n_layers, B, L),
        ``selected`` (n_full, B, P, L)."""
        b_sz, length = ids.shape
        eps = self.sizes["rms_norm_eps"]
        dt = self.policy.compute_dtype
        valid = (jnp.arange(length)[None, :] < lengths[:, None]).reshape(-1)
        h = params["embed"][ids].astype(dt)
        taps = {"chosen": [], "counts": [], "n_keys": [], "first_key": [],
                "selected": []}
        for l, (attn_kind, ffn_kind) in enumerate(self.layer_kinds()):
            p = params["layers"][l]
            y, t = self.attention(l, attn_kind, p["attn"],
                                  rms_norm(h, p["attn_norm"], eps), rows_at)
            h = h + y
            for name in ("n_keys", "first_key", "selected"):
                if name in t:
                    taps[name].append(t[name])
            x = rms_norm(h, p["ffn_norm"], eps).reshape(b_sz * length, -1)
            y, t = self.ffn(l, ffn_kind, p["ffn"], x, valid)
            h = h + y.astype(dt).reshape(h.shape)
            if t:
                taps["chosen"].append(t["chosen"].reshape(b_sz, length, -1))
                taps["counts"].append(t["counts"])
        next_logit, lse, rows = self.head(params, h, ids, lengths, rows_at)
        has_next = (jnp.arange(length)[None, :] + 1) < lengths[:, None]
        loglik = jnp.sum(jnp.where(has_next, next_logit - lse, 0.0), -1)
        out = {"next_logit": next_logit, "lse": lse, "loglik": loglik,
               "rows": rows}
        out.update({k: jnp.stack(v) for k, v in taps.items() if v})
        return out
