"""A third route-sequence language model behind the same scorer: the
architecture published as ``K-EXAONE-236B-A23B`` (its ``config.json``
keys are this model's ``sizes``), next-arc likelihood over whole route
histories as ``route_lm.RouteLM`` gives it, and beside it the
likelihood of the arc AFTER next from the architecture's prediction
module. Everything in this file speaks of that architecture;
``route_lm.py`` speaks of ``dots3-note-prev``, ``route_lm_sala.py`` of
``MiniCPM-SALA``, and what the three share lives in ``lm_common.py``.

- residual blocks with the norm on each sub-block's OUTPUT, none on its
  input: ``h += RMSNorm(Attn(h))``, ``h += RMSNorm(FFN(h))``;
- grouped-query attention (``num_attention_heads`` over
  ``num_key_value_heads``, query head h reads key-value head ``h //
  (H / G)``), RMSNorm with a learned weight on every query and key head,
  no gate, no bias, in two kinds (``layer_types``): **sliding** — RoPE
  (rotate-half, position within the route) and the ``sliding_window``
  keys that end at the query — and **full** — no RoPE, every causal key
  (``parallel/gqa.py``);
- a dense gated MLP where ``mlp_layer_types`` says ``dense``, else
  ``num_experts`` routed experts, top ``num_experts_per_tok`` by sigmoid
  score plus a correction bias, weights renormalised and scaled by
  ``routed_scaling_factor``, and a shared expert
  (``parallel/expert.py``);
- ``num_nextn_predict_layers`` prediction module (one): ``u_t = W_p
  [RMSNorm(h_t) ; RMSNorm(E[id_{t+1}])]`` from the trunk's last hidden
  state, one full-attention expert block over u, the trunk's embedding
  and head with a norm of the module's own: the distribution of
  ``id_{t+2}``. Here it is a scorer's second column, not a drafter:
  there is no decode path (ROADMAP M4).

**One chip's share of a layer**, as ``RouteLM``: ``layers_held`` leading
layers, the routed experts ``experts_first .. experts_first +
experts_held - 1`` of every expert layer (the module's too),
``vocab_held`` rows of the vocabulary, the attention weights whole, and
whether the module is held (``mtp_held``). What the absent experts
would add is left out and that partial result goes on.

The equations are written out in ``benchmark/reference/kexaone_ref.py``,
the plain float32 reference this model is tested against. Here the
parameters and activations are ``policy.compute_dtype`` (bfloat16),
products accumulate in float32, and the norms' statistics, the router's
scores and every softmax are float32. One path a mixer, XLA, whatever
the shapes and the backend. The stream is written out at each update
and the dense MLP runs ``MLP_ROWS`` rows at a time (``lm_common``), so
that a step of 32,768 tokens fits beside the weights.

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
from routest_tpu.models.lm_common import (dot32, expert_pass_counts,
                                          map_rows, next_arc_head,
                                          prediction_column, rms_norm, rope,
                                          settled)
from routest_tpu.parallel import gqa
from routest_tpu.parallel.expert import (ExpertShare, expert_path, gated_mlp,
                                         moe_share, row_tile_of)

Params = Dict

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"
# the published keys the model reads; an artifact's header carries them
SIZE_KEYS = (
    "head_dim", "hidden_size", "intermediate_size", "layer_types",
    "mlp_layer_types", "moe_intermediate_size", "mtp_layer_types", "n_group",
    "norm_topk_prob", "num_attention_heads", "num_experts",
    "num_experts_per_tok", "num_hidden_layers", "num_key_value_heads",
    "num_nextn_predict_layers", "num_shared_experts", "rms_norm_eps",
    "rope_parameters", "routed_scaling_factor", "scoring_func",
    "sliding_window", "topk_group", "vocab_size")
MLP_ROWS = 2048         # tokens of one product of the dense MLP


def by_group(q, groups: int):
    """q (B, L, H, d) → (B, L, G, H / G, d): query head h in the group
    of key-value head ``h // (H / G)``."""
    b_sz, length, heads, d = q.shape
    return q.reshape(b_sz, length, groups, heads // groups, d)


@dataclasses.dataclass(frozen=True)
class RouteLMKExaone:
    sizes: Mapping              # the published keys, published values
    layers_held: int
    experts_held: int
    vocab_held: int
    experts_first: int = 0
    chips_per_layer: int = 1
    mtp_held: bool = True
    policy: Policy = BF16_POLICY
    # queries of a block of the full / sliding layers (lengths are
    # padded to multiples of both), keys of a chunk of the full layers,
    # queries of one step of the sliding layers
    full_block: int = 256
    window_block: int = 128
    key_chunk: int = 1024
    window_rows: int = 2048

    @classmethod
    def from_config(cls, cfg: Mapping, policy: Policy = BF16_POLICY):
        """From a configuration that states the share: the published
        keys, where ``num_hidden_layers``, ``num_experts`` and
        ``vocab_size`` give what is HELD and ``cfg["published"]`` the
        published counts of those three; ``cfg["share"]`` names
        ``chips_per_layer``, ``experts_first`` and, where the module is
        left to another chip, ``mtp_held``. The block sizes may be
        stated too (a toy size states smaller ones)."""
        sizes = {k: cfg[k] for k in SIZE_KEYS}
        sizes.update(cfg.get("published", {}))
        share = cfg.get("share", {})
        blocks = {k: int(cfg[k]) for k in ("full_block", "window_block",
                                           "key_chunk", "window_rows")
                  if k in cfg}
        return cls(sizes=sizes, layers_held=int(cfg["num_hidden_layers"]),
                   experts_held=int(cfg["num_experts"]),
                   vocab_held=int(cfg["vocab_size"]),
                   experts_first=int(share.get("experts_first", 0)),
                   chips_per_layer=int(share.get("chips_per_layer", 1)),
                   mtp_held=bool(share.get(
                       "mtp_held", cfg["num_nextn_predict_layers"] > 0)),
                   policy=policy, **blocks)

    def __post_init__(self) -> None:
        s = self.sizes
        if s["num_attention_heads"] % s["num_key_value_heads"]:
            raise ValueError("query heads are not whole groups")
        built = {"scoring_func": "sigmoid", "norm_topk_prob": True,
                 "n_group": 1, "topk_group": 1}
        other = {k: s[k] for k, v in built.items() if s[k] != v}
        if other:
            raise ValueError(f"built for {built}; the sizes say {other}")
        if self.layers_held > len(s["layer_types"]):
            raise ValueError("more layers held than published")
        if self.mtp_held and (s["num_nextn_predict_layers"] != 1 or list(
                s["mtp_layer_types"]) != [FULL]):
            raise ValueError("built for one full-attention prediction "
                             "module")

    # ── what the share holds ────────────────────────────────────────

    @property
    def share(self) -> ExpertShare:
        return ExpertShare(int(self.sizes["num_experts"]),
                           self.experts_first, self.experts_held)

    def share_header(self) -> Dict:
        return {"layers_held": self.layers_held,
                "experts_held": self.experts_held,
                "experts_first": self.experts_first,
                "vocab_held": self.vocab_held,
                "chips_per_layer": self.chips_per_layer,
                "mtp_held": self.mtp_held}

    def holds(self, params: Params) -> bool:
        """Whether the arrays are this share: as many layers, the held
        experts in each expert layer, the held rows of the vocabulary,
        the module or none."""
        layers = list(params["layers"])
        if "mtp" in params:
            layers.append(params["mtp"]["layer"])
        held = [p["ffn"]["w_gate"].shape[0] for p in layers
                if "router" in p["ffn"]]
        return (len(params["layers"]) == self.layers_held
                and params["embed"].shape[0] == self.vocab_held
                and ("mtp" in params) == self.mtp_held
                and all(n == self.experts_held for n in held))

    def layer_kinds(self) -> List[Tuple[str, str]]:
        """(attention kind, ffn kind) of each held layer of the trunk."""
        s = self.sizes
        return [(s["layer_types"][l], s["mlp_layer_types"][l])
                for l in range(self.layers_held)]

    def block_kinds(self) -> List[Tuple[str, str]]:
        """The trunk's layers and then the module's block: the rows of
        the ``n_keys`` / ``first_key`` taps."""
        return self.layer_kinds() + [(FULL, SPARSE)] * self.mtp_held

    def expert_blocks(self) -> Tuple[str, int]:
        """(the form of the held experts' grouped product at this
        model's widths, the expert blocks held, the module's among
        them)."""
        n_moe = sum(1 for _, f in self.block_kinds() if f == SPARSE)
        return expert_path(int(self.sizes["hidden_size"]),
                           int(self.sizes["moe_intermediate_size"]),
                           self.policy.compute_dtype), n_moe

    def uses_rope(self, kind: str) -> bool:
        return kind == SLIDING

    def mtp_input_ids(self, ids):
        """The token whose embedding joins ``h_t``: ``id_{t+1}``."""
        return jnp.concatenate([ids[:, 1:], ids[:, :1]], 1)

    # ── what the scorer asks of a model (serve/seq_score.py) ────────

    @property
    def length_quantum(self) -> int:
        return int(math.lcm(self.full_block, self.window_block))

    def tap_tables(self, n_rows: int, width: int, n_named: int) -> Dict:
        """name → (shape, dtype, axis of the length, tokens an entry of
        that axis). The module's column comes as taps: a leading axis
        of one entry a module."""
        kinds = self.block_kinds()
        n_moe = sum(1 for _, f in kinds if f == SPARSE)
        over = (n_rows, width)
        out = {"n_keys": ((len(kinds),) + over, jnp.int32, 2, 1),
               "first_key": ((len(kinds),) + over, jnp.int32, 2, 1)}
        if n_moe:
            out["chosen"] = ((n_moe,) + over + (
                int(self.sizes["num_experts_per_tok"]),), jnp.int32, 2, 1)
        if self.mtp_held:
            out["mtp_next_logit"] = ((1,) + over, jnp.float32, 2, 1)
            out["mtp_lse"] = ((1,) + over, jnp.float32, 2, 1)
            out["mtp_loglik"] = ((1, n_rows), jnp.float32, None, 1)
        return out

    def step_attrs(self, length: int) -> Dict[str, str]:
        attrs = {"mixers": "full=xla,window=xla",
                 "mtp": str(int(self.mtp_held))}
        experts, n_moe = self.expert_blocks()
        if n_moe:
            attrs["experts"] = experts
        return attrs

    def step_stats(self, out: Dict, lengths) -> Dict:
        """Device values of one step for the pass's counters: the keys
        each block's real queries saw (one sum a row of ``n_keys``), the
        tokens every held expert got, the module's positions."""
        n_blocks, _, length = out["n_keys"].shape
        at = jnp.arange(length)[None, :]
        # the module's block, the last, has a route's n - 1 positions
        short = (jnp.arange(n_blocks) >= self.layers_held)[:, None, None]
        real = at[None] < lengths[None, :, None] - short
        stats = {"keys_seen": jnp.sum(jnp.where(real, out["n_keys"], 0),
                                      (1, 2)),
                 "mtp_tokens": jnp.sum(at + 1 < lengths[:, None]),
                 "mtp_positions": jnp.sum(at + 2 < lengths[:, None])}
        if "counts" in out:
            stats["counts"] = out["counts"]
        return stats

    def pass_counts(self, steps, stats, real: int) -> List[Tuple]:
        """(family, labels, value) of one pass for the scorer's
        counters and gauges: the visited keys from the plan, the rest
        from ``stats``, fetched once after the pass's sync."""
        import numpy as np

        kinds = self.block_kinds()
        is_window = np.asarray([a == SLIDING for a, _ in kinds])
        seen = sum(np.asarray(s["keys_seen"], np.float64) for s in stats)
        visited = {"window": 0.0, "full": 0.0}
        experts, n_moe = self.expert_blocks()
        for step in steps:
            n = len(step.routes)
            visited["window"] += n * is_window.sum() * gqa.window_visited(
                step.length, self.window_block)
            visited["full"] += n * (~is_window).sum() * gqa.causal_visited(
                step.length, self.full_block, self.key_chunk)
        out = []
        for layer, rows in (("window", is_window), ("full", ~is_window)):
            out += [("gqa_keys", {"layer": layer, "kind": "needed"},
                     float(seen[rows].sum())),
                    ("gqa_keys", {"layer": layer, "kind": "visited"},
                     visited[layer])]
        if n_moe:
            out.append(("expert_blocks", {"path": experts},
                        float(n_moe * len(steps))))
        mtp_tokens = sum(int(s["mtp_tokens"]) for s in stats)
        if self.mtp_held:
            out.append(("mtp_positions", {}, float(
                sum(int(s["mtp_positions"]) for s in stats))))
        counts = [s["counts"] for s in stats if "counts" in s]
        if counts:
            n_trunk = sum(1 for _, f in self.layer_kinds() if f == SPARSE)
            out += expert_pass_counts(
                counts, int(self.sizes["num_experts_per_tok"])
                * (real * n_trunk + mtp_tokens * self.mtp_held),
                row_tile_of(experts))
        return out

    # ── parameters ──────────────────────────────────────────────────

    def init(self, key: jax.Array) -> Params:
        """Seeded random parameters in ``policy.param_dtype``: matrices
        normal with standard deviation 1/sqrt(fan-in), the embedding
        normal 1; norm weights 1 + 0.1 normal, but two kinds apart.
        ``q_norm`` twice that: with queries and keys normed to unit RMS
        an attention logit has standard deviation 2 at init (as
        ``RouteLM.init`` and ``RouteLMSala.init`` arrange: a trained
        model's attention is neither uniform nor one-hot). The norms on
        the sub-blocks' OUTPUTS ``1 / sqrt(2 L)`` times that, L the
        published depth (0.102): depth-scaled residual branches, as
        ``RouteLMSala``'s ``scale_depth / sqrt(L)``. At weight 1 every
        sub-block's output, whatever its content, joins the stream at
        the embedding's own power; an attention that averages keys
        hands on what a route's tokens share (a route lives in a
        neighbourhood of the grid), the norm blows it up to unit RMS,
        and by the third layer the router sends a third of a route's
        tokens to one expert (PERF.md §6, PR 35): the work of the held
        experts then swings with the seed's draw of the router. The
        router's correction bias 0.002 normal: not zero, so that a
        layer that forgot it shows; a fifth of ``RouteLM``'s, because
        sixteen held experts' biases at 0.01 move the held share of the
        assignments by 3% from seed to seed and a pass by 0.5%, where
        a trained router's bias is what evens the load out."""
        dt = self.policy.param_dtype
        s, d = self.sizes, self.sizes["hidden_size"]
        keys = iter(jax.random.split(key, 32 * (self.layers_held + 2)))

        def mat(*shape):
            return (jax.random.normal(next(keys), shape, dt)
                    * jnp.asarray(1.0 / math.sqrt(shape[-2]), dt))

        def near_one(n, times=1.0):
            return (times * (1.0 + 0.1 * jax.random.normal(
                next(keys), (n,), jnp.float32))).astype(dt)

        def mlp(width, lead=()):
            return {"w_gate": mat(*lead, d, width),
                    "w_up": mat(*lead, d, width),
                    "w_down": mat(*lead, width, d)}

        branch = 1.0 / math.sqrt(2 * s["num_hidden_layers"])

        def block(ffn_kind):
            dh = s["head_dim"]
            wide = s["num_attention_heads"] * dh
            narrow = s["num_key_value_heads"] * dh
            attn = {"w_q": mat(d, wide), "w_k": mat(d, narrow),
                    "w_v": mat(d, narrow), "q_norm": near_one(dh, 2.0),
                    "k_norm": near_one(dh), "w_o": mat(wide, d)}
            if ffn_kind == DENSE:
                ffn = mlp(s["intermediate_size"])
            else:
                m = s["moe_intermediate_size"]
                ffn = mlp(m, lead=(self.experts_held,))
                ffn["router"] = mat(d, s["num_experts"])
                ffn["bias"] = 0.002 * jax.random.normal(
                    next(keys), (s["num_experts"],), jnp.float32)
                ffn["shared"] = mlp(m * s["num_shared_experts"])
            return {"attn": attn, "post_attn_norm": near_one(d, branch),
                    "post_ffn_norm": near_one(d, branch), "ffn": ffn}

        params = {"layers": [block(f) for _, f in self.layer_kinds()],
                  "embed": jax.random.normal(next(keys),
                                             (self.vocab_held, d), dt),
                  "head": mat(d, self.vocab_held),
                  "final_norm": near_one(d)}
        if self.mtp_held:
            params["mtp"] = {"h_norm": near_one(d), "e_norm": near_one(d),
                             "w_proj": mat(2 * d, d), "layer": block(SPARSE),
                             "final_norm": near_one(d)}
        return params

    # ── blocks ──────────────────────────────────────────────────────

    def attention(self, scope: str, kind: str, p: Params, x):
        """x (B, L, d) the stream → (y (B, L, d) float32, n_keys (B, L),
        first_key (B, L))."""
        s, dt = self.sizes, x.dtype
        b_sz, length, _ = x.shape
        heads, groups, dh = (s["num_attention_heads"],
                             s["num_key_value_heads"], s["head_dim"])
        eps = s["rms_norm_eps"]
        with jax.named_scope(scope):
            q = dot32(x, p["w_q"]).astype(dt).reshape(b_sz, length, heads,
                                                      dh)
            k = dot32(x, p["w_k"]).astype(dt).reshape(b_sz, length, groups,
                                                      dh)
            v = dot32(x, p["w_v"]).astype(dt).reshape(b_sz, length, groups,
                                                      dh)
            q, k = rms_norm(q, p["q_norm"], eps), rms_norm(k, p["k_norm"],
                                                           eps)
            if self.uses_rope(kind):
                pos = jnp.arange(length, dtype=jnp.int32)[None]
                theta = float(s["rope_parameters"]["rope_theta"])
                q, k = rope(q, pos, theta).astype(dt), rope(
                    k, pos, theta).astype(dt)
            q = by_group(q, groups)
        if kind == SLIDING:
            o, n_keys, first = gqa.window_attention(
                q, k, v, window=int(s["sliding_window"]), scale=dh ** -0.5,
                block=self.window_block, rows=self.window_rows,
                scope=scope + ".window")
        else:
            o, n_keys, first = gqa.causal_attention(
                q, k, v, scale=dh ** -0.5, block=self.full_block,
                chunk=self.key_chunk, scope=scope + ".full")
        with jax.named_scope(scope):
            return dot32(o.reshape(b_sz, length, heads * dh),
                         p["w_o"]), n_keys, first

    def ffn(self, scope: str, kind: str, p: Params, x, valid):
        """x (T, d) the stream, ``valid`` (T,) → (y (T, d) float32,
        taps): ``chosen`` (T, k) and ``counts`` (experts_held,) for an
        expert layer."""
        if kind == DENSE:
            with jax.named_scope(scope + ".dense"):
                return map_rows(lambda rows: gated_mlp(
                    rows, p["w_gate"], p["w_up"], p["w_down"]), x,
                    MLP_ROWS), {}
        return moe_share(p, x, int(self.sizes["num_experts_per_tok"]),
                         self.share,
                         float(self.sizes["routed_scaling_factor"]),
                         valid=valid, scope=scope + ".moe")

    def block(self, scope: str, kinds: Tuple[str, str], p: Params, h,
              valid, taps: Dict):
        """One residual block over the stream h (B, L, d): the norm on
        each sub-block's output; the block's taps appended to ``taps``."""
        eps, dt = self.sizes["rms_norm_eps"], h.dtype
        y, n_keys, first = self.attention(scope + ".attn", kinds[0],
                                          p["attn"], h)
        h = settled(h + rms_norm(y, p["post_attn_norm"], eps).astype(dt))
        y, t = self.ffn(scope, kinds[1], p["ffn"],
                        h.reshape(-1, h.shape[-1]), valid.reshape(-1))
        h = settled(h + rms_norm(y, p["post_ffn_norm"], eps).astype(dt)
                    .reshape(h.shape))
        taps["n_keys"].append(n_keys)
        taps["first_key"].append(first)
        if t:
            taps["chosen"].append(t["chosen"].reshape(h.shape[:2] + (-1,)))
            taps["counts"].append(t["counts"])
        return h

    # ── the model ───────────────────────────────────────────────────

    def apply(self, params: Params, ids, lengths, rows_at) -> Dict:
        """ids (B, L) int32 within the held slice, padded past
        ``lengths`` (B,); ``rows_at`` (B, P) positions whose whole logit
        row is wanted. → per position ``next_logit`` (the logit of
        ids[t + 1]; 0 where there is none) and ``lse`` (B, L) float32,
        per route ``loglik`` (B,), ``rows`` (B, P, vocab_held); the
        module's column ``mtp_next_logit`` (the logit of ids[t + 2]),
        ``mtp_lse`` (1, B, L) and ``mtp_loglik`` (1, B); and the taps,
        the module's block last: ``n_keys`` / ``first_key`` (blocks, B,
        L), ``chosen`` (expert blocks, B, L, k), ``counts`` (expert
        blocks, experts_held)."""
        b_sz, length = ids.shape
        s, dt = self.sizes, self.policy.compute_dtype
        eps = s["rms_norm_eps"]
        at = jnp.arange(length)[None, :]
        h = params["embed"][ids].astype(dt)
        taps = {"n_keys": [], "first_key": [], "chosen": [], "counts": []}
        for l, kinds in enumerate(self.layer_kinds()):
            h = self.block(f"lm.L{l}", kinds, params["layers"][l], h,
                           at < lengths[:, None], taps)
        next_logit, lse, rows = next_arc_head(params, h, ids, lengths,
                                              rows_at, eps)
        loglik = jnp.sum(jnp.where(at + 1 < lengths[:, None],
                                   next_logit - lse, 0.0), -1)
        out = {"next_logit": next_logit, "lse": lse, "loglik": loglik,
               "rows": rows}
        if self.mtp_held:
            out.update(prediction_column(
                params, h, ids, self.mtp_input_ids(ids), lengths, rows_at,
                eps, lambda p, u, valid: self.block(
                    "lm.mtp", (FULL, SPARSE), p, u, valid, taps)))
        out.update({k: jnp.stack(v) for k, v in taps.items() if v})
        return out
