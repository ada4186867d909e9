"""A fourth route-sequence language model behind the same scorer: the
architecture published as ``GigaChat3.1-702B-A36B`` (``model_type``
``deepseek_v3``; its ``config.json`` keys are this model's ``sizes``),
next-arc likelihood over whole route histories as ``route_lm.RouteLM``
gives it, and beside it the likelihood of the arc AFTER next from the
architecture's prediction module, as ``RouteLMKExaone``. Everything in
this file speaks of that architecture; what the four models share lives
in ``lm_common.py``.

- pre-norm residual blocks, ``h += Attn(RMSNorm(h))``, ``h +=
  FFN(RMSNorm(h))``;
- **dense causal latent attention**: queries and keys in two parts
  (``qk_nope_head_dim`` a head + ``qk_rope_head_dim`` rotary, ONE
  rotary key shared by the heads), values ``v_head_dim`` wide (192:
  wider than the keys' first part), both up-projected from normed
  latents (``q_lora_rank``, ``kv_lora_rank``); every query sees every
  key ``s <= t`` of its route (``parallel/latent.py``); no gate, no
  selector, no window. The expanded form is built, not the absorbed
  one: a scorer keeps no latent cache to save;
- **YaRN** on the rotary parts (``rope_scaling``): a per-frequency
  blend of the plain and the ``factor``-times-stretched rotation
  (``lm_common.yarn_inv_freq``), cos and sin times ``m(mscale) /
  m(mscale_all_dim)``, and the softmax scale ``(qk_nope_head_dim +
  qk_rope_head_dim) ** -0.5 * m(mscale_all_dim) ** 2``;
- a dense gated MLP in the first ``first_k_dense_replace`` layers, then
  ``n_routed_experts`` routed experts by **group-limited** routing
  (``topk_method`` ``noaux_tc``): sigmoid scores plus a correction
  bias, the ``topk_group`` best of ``n_group`` groups of consecutive
  experts kept (a group scored by its two best), the top
  ``num_experts_per_tok`` of those, weights renormalised and scaled by
  ``routed_scaling_factor``, and a shared expert
  (``parallel/expert.py``);
- ``num_nextn_predict_layers`` prediction module (one), in the form
  ``lm_common.prediction_column`` states, its block of the trunk's own
  kind (latent attention, expert FFN). A scorer's second column, not a
  drafter: there is no decode path (ROADMAP M4).

**One chip's share of a layer**, as ``RouteLM``: the published layers
``0 .. first_k_dense_replace - 1`` are one dense layer three times, so
ONE of them is held, then ``layers_held - 1`` expert layers; the routed
experts ``experts_first .. experts_first + experts_held - 1`` of every
expert layer (the module's too), ``vocab_held`` rows of the vocabulary,
the attention weights whole, and whether the module is held
(``mtp_held``). What the absent experts would add is left out and that
partial result goes on.

The equations are written out in ``benchmark/reference/gigachat_ref.py``,
the plain float32 reference this model is tested against. Here the
parameters and activations are ``policy.compute_dtype`` (bfloat16),
products accumulate in float32, and the norms' statistics, the router's
scores, the rotations and every softmax are float32. The dense softmax
runs as a kernel on a TPU at bfloat16 shapes that tile and as XLA
elsewhere (``latent.latent_path``); no option selects either.

``apply`` takes a batch of routes padded to one length; a route's
outputs depend on nothing but its own tokens.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Tuple

import jax
import jax.numpy as jnp

from routest_tpu.core.dtypes import BF16_POLICY, Policy
from routest_tpu.models.lm_common import (dot32, expert_pass_counts,
                                          map_rows, next_arc_head,
                                          prediction_column, rms_norm, rope,
                                          settled, yarn_inv_freq,
                                          yarn_mscale)
from routest_tpu.parallel import latent
from routest_tpu.parallel.expert import (ExpertShare, expert_path, gated_mlp,
                                         moe_share, row_tile_of)

Params = Dict

DENSE, SPARSE = "dense", "sparse"
# the published keys the model reads; an artifact's header carries them
SIZE_KEYS = (
    "first_k_dense_replace", "hidden_size", "intermediate_size",
    "kv_lora_rank", "moe_intermediate_size", "n_group", "n_routed_experts",
    "n_shared_experts", "norm_topk_prob", "num_attention_heads",
    "num_experts_per_tok", "num_hidden_layers", "num_nextn_predict_layers",
    "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "rms_norm_eps",
    "rope_scaling", "rope_theta", "routed_scaling_factor", "scoring_func",
    "topk_group", "topk_method", "v_head_dim", "vocab_size")
MLP_ROWS = 2048         # tokens of one product of the dense MLP


@dataclasses.dataclass(frozen=True)
class RouteLMGigaChat:
    sizes: Mapping              # the published keys, published values
    layers_held: int
    experts_held: int
    vocab_held: int
    experts_first: int = 0
    chips_per_layer: int = 1
    mtp_held: bool = True
    policy: Policy = BF16_POLICY
    # queries of a block (lengths are padded to its multiples) and keys
    # of a chunk of the dense causal softmax
    full_block: int = 256
    key_chunk: int = 1024

    @classmethod
    def from_config(cls, cfg: Mapping, policy: Policy = BF16_POLICY):
        """From a configuration that states the share: the published
        keys, where ``num_hidden_layers``, ``n_routed_experts`` and
        ``vocab_size`` give what is HELD and ``cfg["published"]`` the
        published counts of those three; ``cfg["share"]`` names
        ``chips_per_layer``, ``experts_first`` and, where the module is
        left to another chip, ``mtp_held``. The block sizes may be
        stated too (a toy size states smaller ones)."""
        sizes = {k: cfg[k] for k in SIZE_KEYS}
        sizes.update(cfg.get("published", {}))
        share = cfg.get("share", {})
        blocks = {k: int(cfg[k]) for k in ("full_block", "key_chunk")
                  if k in cfg}
        return cls(sizes=sizes, layers_held=int(cfg["num_hidden_layers"]),
                   experts_held=int(cfg["n_routed_experts"]),
                   vocab_held=int(cfg["vocab_size"]),
                   experts_first=int(share.get("experts_first", 0)),
                   chips_per_layer=int(share.get("chips_per_layer", 1)),
                   mtp_held=bool(share.get(
                       "mtp_held", cfg["num_nextn_predict_layers"] > 0)),
                   policy=policy, **blocks)

    def __post_init__(self) -> None:
        s = self.sizes
        built = {"scoring_func": "sigmoid", "norm_topk_prob": True,
                 "topk_method": "noaux_tc"}
        other = {k: s[k] for k, v in built.items() if s[k] != v}
        if other or s["rope_scaling"].get("rope_type") != "yarn":
            raise ValueError(f"built for {built} under YaRN; the sizes say "
                             f"{other or s['rope_scaling']}")
        if s["n_routed_experts"] % s["n_group"] or not (
                0 < s["topk_group"] <= s["n_group"]):
            raise ValueError("the experts are not whole routing groups")
        if self.mtp_held and s["num_nextn_predict_layers"] != 1:
            raise ValueError("built for one prediction module")

    # ── what the share holds ────────────────────────────────────────

    @property
    def share(self) -> ExpertShare:
        return ExpertShare(int(self.sizes["n_routed_experts"]),
                           self.experts_first, self.experts_held)

    @property
    def groups(self) -> Tuple[int, int]:
        return int(self.sizes["n_group"]), int(self.sizes["topk_group"])

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

    def layer_kinds(self) -> List[str]:
        """The ffn kind of each held layer of the trunk: the leading
        dense layers are held once (where the model has any), expert
        layers follow."""
        dense = min(1, int(self.sizes["first_k_dense_replace"]),
                    self.layers_held)
        return [DENSE] * dense + [SPARSE] * (self.layers_held - dense)

    def block_kinds(self) -> List[str]:
        """The trunk's layers and then the module's block: the rows of
        the ``n_keys`` / ``first_key`` taps."""
        return self.layer_kinds() + [SPARSE] * self.mtp_held

    def expert_blocks(self) -> Tuple[str, int]:
        """(the form of the held experts' grouped product at this
        model's widths, the expert blocks held, the module's among
        them)."""
        n_moe = sum(f == SPARSE for f in self.block_kinds())
        return expert_path(int(self.sizes["hidden_size"]),
                           int(self.sizes["moe_intermediate_size"]),
                           self.policy.compute_dtype), n_moe

    def rotary(self) -> Tuple:
        """(YaRN's frequencies of the rotary pairs, what cos and sin are
        multiplied by, the softmax scale)."""
        s, scaling = self.sizes, self.sizes["rope_scaling"]
        all_dim = yarn_mscale(scaling["factor"], scaling["mscale_all_dim"])
        return (yarn_inv_freq(int(s["qk_rope_head_dim"]),
                              float(s["rope_theta"]), scaling),
                yarn_mscale(scaling["factor"], scaling["mscale"]) / all_dim,
                (s["qk_nope_head_dim"] + s["qk_rope_head_dim"]) ** -0.5
                * all_dim ** 2)

    def mtp_input_ids(self, ids):
        """The token whose embedding joins ``h_t``: ``id_{t+1}``."""
        return jnp.concatenate([ids[:, 1:], ids[:, :1]], 1)

    # ── what the scorer asks of a model (serve/seq_score.py) ────────

    @property
    def length_quantum(self) -> int:
        return self.full_block

    def tap_tables(self, n_rows: int, width: int, n_named: int) -> Dict:
        """name → (shape, dtype, axis of the length, tokens an entry of
        that axis). The module's column comes as taps: a leading axis
        of one entry a module."""
        n_blocks = len(self.block_kinds())
        n_moe = self.expert_blocks()[1]
        over = (n_rows, width)
        out = {"n_keys": ((n_blocks,) + over, jnp.int32, 2, 1),
               "first_key": ((n_blocks,) + over, jnp.int32, 2, 1)}
        if n_moe:
            out["chosen"] = ((n_moe,) + over + (
                int(self.sizes["num_experts_per_tok"]),), jnp.int32, 2, 1)
        if self.mtp_held:
            out["mtp_next_logit"] = ((1,) + over, jnp.float32, 2, 1)
            out["mtp_lse"] = ((1,) + over, jnp.float32, 2, 1)
            out["mtp_loglik"] = ((1, n_rows), jnp.float32, None, 1)
        return out

    def latent_steps(self, length: int) -> str:
        """Which dense-softmax step a block runs for routes padded to
        ``length`` (``"fused"`` or ``"xla"``: what
        ``latent.latent_path`` says of this model's shapes here)."""
        s = self.sizes
        return latent.latent_path(
            int(s["num_attention_heads"]), length, self.full_block,
            self.key_chunk, int(s["qk_nope_head_dim"]),
            int(s["qk_rope_head_dim"]), int(s["v_head_dim"]),
            self.policy.compute_dtype)

    def step_attrs(self, length: int) -> Dict[str, str]:
        attrs = {"mixers": "latent=" + self.latent_steps(length),
                 "mtp": str(int(self.mtp_held))}
        experts, n_moe = self.expert_blocks()
        if n_moe:
            attrs["experts"] = experts
            attrs["groups"] = "%d/%d" % self.groups
        return attrs

    def step_stats(self, out: Dict, lengths) -> Dict:
        """Device values of one step for the pass's counters: the keys
        each block's real queries saw (one sum a row of ``n_keys``), the
        module's positions, the tokens every held expert got and the
        (token, expert block) pairs one of whose chosen experts lies in
        the held experts' routing group."""
        n_blocks, _, length = out["n_keys"].shape
        at = jnp.arange(length)[None, :]

        def real(n_rows, n_trunk):
            """(rows, B, L): the module's rows, from ``n_trunk`` on,
            have a route's n - 1 positions."""
            short = (jnp.arange(n_rows) >= n_trunk)[:, None, None]
            return at[None] < lengths[None, :, None] - short

        stats = {"keys_seen": jnp.sum(jnp.where(
                     real(n_blocks, self.layers_held), out["n_keys"], 0),
                     (1, 2)),
                 "mtp_tokens": jnp.sum(at + 1 < lengths[:, None]),
                 "mtp_positions": jnp.sum(at + 2 < lengths[:, None])}
        if "counts" in out:
            per_group = self.sizes["n_routed_experts"] // self.groups[0]
            mine = self.experts_first // per_group
            n_moe = out["chosen"].shape[0]
            hit = jnp.any(out["chosen"] // per_group == mine, -1)
            stats["counts"] = out["counts"]
            stats["group_hits"] = jnp.sum(hit & real(
                n_moe, n_moe - self.mtp_held))
        return stats

    def pass_counts(self, steps, stats, real: int) -> List[Tuple]:
        """(family, labels, value) of one pass for the scorer's
        counters and gauges: the visited keys and the dense softmax's
        grid steps from the plan (``latent.causal_grid``'s tables), the
        rest from ``stats``, fetched once after the pass's sync."""
        import numpy as np

        experts, n_moe = self.expert_blocks()
        n_blocks = len(self.block_kinds())
        out = [("latent_keys", {"kind": "needed"}, float(sum(
                   np.asarray(s["keys_seen"], np.float64).sum()
                   for s in stats))),
               ("latent_keys", {"kind": "visited"}, float(sum(
                   len(step.routes) * n_blocks * latent.visited(
                       step.length, self.full_block, self.key_chunk)
                   for step in steps)))]
        heads = int(self.sizes["num_attention_heads"])
        for kind in ("interior", "diagonal"):
            out.append(("latent_tiles", {"kind": kind}, float(sum(
                n_blocks * latent.grid_steps(
                    len(step.routes), step.length, self.full_block,
                    self.key_chunk, heads)[kind] for step in steps))))
        mtp_tokens = sum(int(s["mtp_tokens"]) for s in stats)
        if self.mtp_held:
            out.append(("mtp_positions", {}, float(
                sum(int(s["mtp_positions"]) for s in stats))))
        counts = [s["counts"] for s in stats if "counts" in s]
        if counts:
            tokens = (real * (n_moe - self.mtp_held)
                      + mtp_tokens * self.mtp_held)
            out += [("expert_blocks", {"path": experts},
                     float(n_moe * len(steps))),
                    ("expert_group_tokens", {"kind": "held_group"}, float(
                        sum(int(s["group_hits"]) for s in stats))),
                    ("expert_group_tokens", {"kind": "all"}, float(tokens))]
            out += expert_pass_counts(
                counts, int(self.sizes["num_experts_per_tok"]) * tokens,
                row_tile_of(experts))
        return out

    # ── parameters ──────────────────────────────────────────────────

    def init(self, key: jax.Array) -> Params:
        """Seeded random parameters in ``policy.param_dtype``: matrices
        normal with standard deviation 1/sqrt(fan-in), the embedding
        normal 1, norm weights 1 + 0.1 normal. Nothing is tuned for the
        attention: the normed query latent and the normed key latent
        give queries and keys of variance 1, and ``m(mscale_all_dim) **
        2`` = 2.005 on the softmax scale makes an attention logit's
        standard deviation 2.0 at init (neither uniform nor one-hot:
        what ``RouteLM.init`` arranges with a gain). The router's
        correction bias 0.002 normal: not zero, so that a layer that
        forgot it shows; as small as ``RouteLMKExaone``'s, because the
        draw of the sixteen held experts' biases moves the held share
        of the assignments from seed to seed, where a trained router's
        bias is what evens the load out."""
        dt = self.policy.param_dtype
        s, d = self.sizes, self.sizes["hidden_size"]
        keys = iter(jax.random.split(key, 32 * (self.layers_held + 2)))

        def mat(*shape):
            return (jax.random.normal(next(keys), shape, dt)
                    * jnp.asarray(shape[-2] ** -0.5, dt))

        def near_one(n):
            return (1.0 + 0.1 * jax.random.normal(
                next(keys), (n,), jnp.float32)).astype(dt)

        def mlp(width, lead=()):
            return {"w_gate": mat(*lead, d, width),
                    "w_up": mat(*lead, d, width),
                    "w_down": mat(*lead, width, d)}

        def block(ffn_kind):
            heads, dn = s["num_attention_heads"], s["qk_nope_head_dim"]
            r_q, r_kv = s["q_lora_rank"], s["kv_lora_rank"]
            dr, dv = s["qk_rope_head_dim"], s["v_head_dim"]
            attn = {"w_dq": mat(d, r_q), "q_norm": near_one(r_q),
                    "w_uq": mat(r_q, heads * (dn + dr)),
                    "w_dkv": mat(d, r_kv + dr), "kv_norm": near_one(r_kv),
                    "w_ukv": mat(r_kv, heads * (dn + dv)),
                    "w_o": mat(heads * dv, d)}
            if ffn_kind == DENSE:
                ffn = mlp(s["intermediate_size"])
            else:
                m = s["moe_intermediate_size"]
                ffn = mlp(m, lead=(self.experts_held,))
                ffn["router"] = mat(d, s["n_routed_experts"])
                ffn["bias"] = 0.002 * jax.random.normal(
                    next(keys), (s["n_routed_experts"],), jnp.float32)
                ffn["shared"] = mlp(m * s["n_shared_experts"])
            return {"attn_norm": near_one(d), "ffn_norm": near_one(d),
                    "attn": attn, "ffn": ffn}

        params = {"layers": [block(f) for f in self.layer_kinds()],
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

    def attention(self, scope: str, p: Params, x):
        """x (B, L, d) the block's normed input → (y (B, L, d) float32,
        n_keys (B, L), first_key (B, L))."""
        s, dt = self.sizes, x.dtype
        b_sz, length, _ = x.shape
        heads, eps = s["num_attention_heads"], s["rms_norm_eps"]
        dn, dr, dv = (s["qk_nope_head_dim"], s["qk_rope_head_dim"],
                      s["v_head_dim"])
        r_q, r_kv, theta = (s["q_lora_rank"], s["kv_lora_rank"],
                            float(s["rope_theta"]))
        inv_freq, amplitude, scale = self.rotary()
        block = min(self.full_block, length)

        def turned(y, pos):
            y = rope(y, pos, theta, inv_freq)
            return (y if amplitude == 1.0 else y * amplitude).astype(dt)

        with jax.named_scope(scope + ".mla"):
            c_q = rms_norm(dot32(x, p["w_dq"]), p["q_norm"], eps).astype(dt)
            kv = dot32(x, p["w_dkv"])
            c_kv = rms_norm(kv[..., :r_kv], p["kv_norm"], eps).astype(dt)
            k_shared = turned(kv[..., r_kv:],
                              jnp.arange(length, dtype=jnp.int32)[None])
            # whole chunks of keys: the narrow latents are padded, not
            # the arrays expanded from them
            widen = ((0, 0), (0, latent.padded_keys(
                length, block, self.key_chunk) - length), (0, 0))
            c_kv, k_shared = jnp.pad(c_kv, widen), jnp.pad(k_shared, widen)
            # by head, as the kernel tiles them: the keys' length before
            # their width, the values' after it, the queries whole
            w_ukv = p["w_ukv"].reshape(r_kv, heads, dn + dv)
            k = jnp.einsum("blr,rhd->bhld", c_kv, w_ukv[..., :dn],
                           preferred_element_type=jnp.float32).astype(dt)
            v = jnp.einsum("blr,rhd->bhdl", c_kv, w_ukv[..., dn:],
                           preferred_element_type=jnp.float32).astype(dt)
            w_uq = p["w_uq"].reshape(r_q, heads, dn + dr)
            q = jnp.einsum("blr,rhd->bhld", c_q, w_uq[..., :dn],
                           preferred_element_type=jnp.float32).astype(dt)
            q_shared = turned(jnp.einsum(
                "blr,rhd->bhld", c_q, w_uq[..., dn:],
                preferred_element_type=jnp.float32),
                jnp.arange(length, dtype=jnp.int32)[None, None])

        out, n_keys, first = latent.causal_attention(
            q, q_shared, k, k_shared, v, length=length, scale=scale,
            block=block, chunk=self.key_chunk, scope=scope + ".mla.full")
        with jax.named_scope(scope + ".mla"):
            return dot32(out.reshape(b_sz, length, heads * dv),
                         p["w_o"]), n_keys, first

    def ffn(self, scope: str, kind: str, p: Params, x, valid):
        """x (T, d) the block's normed input, ``valid`` (T,) → (y (T, d)
        float32, taps): ``chosen`` (T, k) and ``counts`` (experts_held,)
        for an expert layer."""
        if kind == DENSE:
            with jax.named_scope(scope + ".dense"):
                return map_rows(lambda rows: gated_mlp(
                    rows, p["w_gate"], p["w_up"], p["w_down"]), x,
                    MLP_ROWS), {}
        return moe_share(p, x, int(self.sizes["num_experts_per_tok"]),
                         self.share,
                         float(self.sizes["routed_scaling_factor"]),
                         valid=valid, scope=scope + ".moe",
                         groups=self.groups)

    def block(self, scope: str, kind: str, p: Params, h, valid, taps: Dict):
        """One pre-norm residual block over the stream h (B, L, d); the
        block's taps appended to ``taps``."""
        eps, dt = self.sizes["rms_norm_eps"], h.dtype
        y, n_keys, first = self.attention(
            scope, p["attn"], rms_norm(h, p["attn_norm"], eps))
        h = settled(h + y.astype(dt))
        y, t = self.ffn(scope, kind, p["ffn"],
                        rms_norm(h, p["ffn_norm"], eps).reshape(
                            -1, h.shape[-1]), valid.reshape(-1))
        h = settled(h + y.astype(dt).reshape(h.shape))
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
        length = ids.shape[1]
        eps = self.sizes["rms_norm_eps"]
        at = jnp.arange(length)[None, :]
        h = params["embed"][ids].astype(self.policy.compute_dtype)
        taps = {"n_keys": [], "first_key": [], "chosen": [], "counts": []}
        for l, kind in enumerate(self.layer_kinds()):
            h = self.block(f"lm.L{l}", kind, params["layers"][l], h,
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
                    "lm.mtp", SPARSE, p, u, valid, taps)))
        out.update({k: jnp.stack(v) for k, v in taps.items() if v})
        return out
