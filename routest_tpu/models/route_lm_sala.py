"""A second route-sequence language model behind the same scorer: the
architecture published as ``MiniCPM-SALA`` (its ``config.json`` keys are
this model's ``sizes``), next-arc likelihood over whole route histories
as ``route_lm.RouteLM`` gives it. Everything in this file speaks of
that architecture; ``route_lm.py`` speaks of ``dots3-note-prev``.

- pre-norm residual blocks, RMSNorm, ``hidden_size`` wide, with the
  muP scalings of the family: the embedding times ``scale_emb``, every
  mixer and MLP output times ``scale_depth / sqrt(L)`` (L the published
  depth) before it joins the stream, the logits divided by
  ``hidden_size / dim_model_base``; the head is not tied;
- two mixers in one model (``mixer_types``, by published layer index):
  ``lightning-attn`` — decayed linear attention, a float32 d x d state
  a head, per-head and per-layer decay, RMSNorm on q and k, RoPE, an
  RMSNorm over the concatenated heads and an elementwise sigmoid gate
  (``parallel/linear_attn.py``) — and ``minicpm4`` — grouped-query heads
  (``num_attention_heads`` over ``num_key_value_heads``) that see a
  learned choice of ``sparse.topk`` blocks of ``sparse.block_size``
  keys, no RoPE, an elementwise gate (``parallel/select.py``,
  :func:`~routest_tpu.parallel.select.block_sparse_attention`);
- a dense gated MLP in every layer; no experts, no latents.

**A run of layers.** The model is told what it holds: the
``layers_held`` published layers from ``layers_first`` on (a pipeline
stage), each whole, and the whole vocabulary (the embedding and the
head that the first and last stage hold in the deployment). A layer's
decay and its scope names go by its PUBLISHED index.

The equations are written out in ``benchmark/reference/sala_ref.py``,
the plain float32 reference this model is tested against. Here the
parameters and activations are ``policy.compute_dtype`` (bfloat16),
products accumulate in float32, and the norms' statistics, both
softmaxes, the block scores, the decay powers and the linear state are
float32. One path a mixer, XLA, whatever the shapes and the backend
(a kernel for the sparse mixer's second stage was tried on the chip and
gained 0.25% of a pass: PERF.md §6, PR 32).

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
from routest_tpu.models.lm_common import (dot32, map_rows, next_arc_head,
                                          rms_norm, rope)
from routest_tpu.models.lm_common import settled as _settled
from routest_tpu.parallel import linear_attn
from routest_tpu.parallel.expert import gated_mlp
from routest_tpu.parallel.select import (block_and_chunk,
                                         block_sparse_attention, chunk_steps)

Params = Dict

SPARSE, LINEAR = "minicpm4", "lightning-attn"
# the published keys the model reads; an artifact's header carries them
SIZE_KEYS = (
    "attn_use_output_gate", "attn_use_rope", "dim_model_base", "head_dim",
    "hidden_size", "intermediate_size", "lightning_head_dim", "lightning_nh",
    "lightning_nkv", "lightning_use_rope", "mixer_types",
    "num_attention_heads", "num_hidden_layers", "num_key_value_heads",
    "qk_norm", "rms_norm_eps", "rope_theta", "scale_depth", "scale_emb",
    "sparse", "use_output_gate", "use_output_norm", "vocab_size")
MLP_ROWS = 2048         # tokens of one MLP product


@dataclasses.dataclass(frozen=True)
class RouteLMSala:
    sizes: Mapping              # the published keys, published values
    layers_held: int
    layers_first: int
    vocab_held: int
    chips_per_layer: int = 1
    policy: Policy = BF16_POLICY
    # queries of a block of the sparse mixer, keys of a chunk of its
    # second stage, tokens of a chunk of the linear scan: lengths are
    # padded to multiples of ``length_quantum``
    q_block: int = 128
    key_chunk: int = 2048
    scan_chunk: int = 256

    @classmethod
    def from_config(cls, cfg: Mapping, policy: Policy = BF16_POLICY):
        """From a configuration that states the share: the published
        keys, where ``num_hidden_layers`` gives what is HELD and
        ``cfg["published"]`` the published depth; ``cfg["share"]`` names
        ``layers_first`` and ``chips_per_layer``. The block sizes may be
        stated too (a toy size states smaller ones)."""
        sizes = {k: cfg[k] for k in SIZE_KEYS}
        sizes.update(cfg.get("published", {}))
        share = cfg.get("share", {})
        blocks = {k: int(cfg[k]) for k in ("q_block", "key_chunk",
                                           "scan_chunk") if k in cfg}
        return cls(sizes=sizes, layers_held=int(cfg["num_hidden_layers"]),
                   layers_first=int(share.get("layers_first", 0)),
                   vocab_held=int(cfg["vocab_size"]),
                   chips_per_layer=int(share.get("chips_per_layer", 1)),
                   policy=policy, **blocks)

    def __post_init__(self) -> None:
        s = self.sizes
        if s["lightning_nkv"] != s["lightning_nh"]:
            raise ValueError("the linear mixer has one key-value head a "
                             "query head")
        if s["num_attention_heads"] % s["num_key_value_heads"]:
            raise ValueError("query heads are not whole groups")
        built = {"qk_norm": True, "lightning_use_rope": True,
                 "attn_use_rope": False, "use_output_norm": True,
                 "use_output_gate": True, "attn_use_output_gate": True}
        other = {k: s[k] for k, v in built.items() if bool(s[k]) != v}
        if other:
            raise ValueError(f"built for {built}; the sizes say {other}")
        if self.layers_first + self.layers_held > len(s["mixer_types"]):
            raise ValueError("the run of layers passes the published depth")
        sp = s["sparse"]
        if (sp["kernel_size"] % sp["kernel_stride"]
                or sp["block_size"] % sp["kernel_stride"]):
            raise ValueError("the compression's stride divides neither its "
                             "window nor the block")

    # ── what the share holds ────────────────────────────────────────

    def share_header(self) -> Dict:
        return {"layers_held": self.layers_held,
                "layers_first": self.layers_first,
                "vocab_held": self.vocab_held,
                "chips_per_layer": self.chips_per_layer}

    def holds(self, params: Params) -> bool:
        """Whether the arrays are this share: as many layers, each of
        its published kind, and the vocabulary's rows."""
        kinds = [LINEAR if "o_norm" in p["attn"] else SPARSE
                 for p in params["layers"]]
        return (kinds == [k for k, _ in self.layer_kinds()]
                and params["embed"].shape[0] == self.vocab_held)

    def layer_kinds(self) -> List[Tuple[str, int]]:
        """(mixer kind, published index) of each held layer."""
        return [(self.sizes["mixer_types"][l], l)
                for l in range(self.layers_first,
                               self.layers_first + self.layers_held)]

    def count(self, kind: str) -> int:
        return sum(1 for k, _ in self.layer_kinds() if k == kind)

    @property
    def residual_scale(self) -> float:
        return self.sizes["scale_depth"] / math.sqrt(
            self.sizes["num_hidden_layers"])

    @property
    def logit_scale(self) -> float:
        return self.sizes["dim_model_base"] / self.sizes["hidden_size"]

    # ── what the scorer asks of a model (serve/seq_score.py) ────────

    @property
    def length_quantum(self) -> int:
        sp = self.sizes["sparse"]
        return int(math.lcm(self.q_block, self.scan_chunk, sp["block_size"]))

    def tap_tables(self, n_rows: int, width: int, n_named: int) -> Dict:
        """name → (shape, dtype, axis of the length, tokens an entry of
        that axis)."""
        s, sp = self.sizes, self.sizes["sparse"]
        n_sp, n_lin = self.count(SPARSE), self.count(LINEAR)
        groups = s["num_key_value_heads"]
        out = {}
        if n_sp:
            out["n_keys"] = ((n_sp, n_rows, width, groups), jnp.int32, 2, 1)
            out["n_visible"] = ((n_sp, n_rows, width), jnp.int32, 2, 1)
            out["blocks"] = ((n_sp, n_rows, n_named, groups,
                              -(-width // sp["block_size"])), jnp.bool_, 4,
                             sp["block_size"])
        if n_lin:
            dl = s["lightning_head_dim"]
            out["state"] = ((n_lin, n_rows, s["lightning_nh"], dl, dl),
                            jnp.float32, None, 1)
        return out

    def step_attrs(self, length: int) -> Dict[str, str]:
        return {"mixers": "sparse=xla,linear=xla"}

    def step_stats(self, out: Dict, lengths) -> Dict:
        """Device scalars of one step for the pass's counters."""
        if "n_keys" not in out:
            return {}
        real = (jnp.arange(out["n_keys"].shape[2])[None, :]
                < lengths[:, None])[None, :, :, None]
        return {"chosen_keys": jnp.sum(jnp.where(real, out["n_keys"], 0)
                                       .astype(jnp.float32))}

    def pass_counts(self, steps, stats, real: int) -> List[Tuple]:
        """(family, labels, value) of one pass for the scorer's
        counters and gauges: from the plan, and ``stats`` fetched once
        after the pass's sync."""
        s = self.sizes
        n_sp, n_lin = self.count(SPARSE), self.count(LINEAR)
        groups = s["num_key_value_heads"]
        visited = chunks = 0
        for step in steps:
            block, chunk = block_and_chunk(step.length, self.q_block,
                                           self.key_chunk)
            visited += (len(step.routes) * n_sp * groups * block * chunk
                        * chunk_steps(step.length, self.q_block,
                                      self.key_chunk))
            chunks += (len(step.routes) * n_lin
                       * linear_attn.chunk_count(step.length,
                                                 self.scan_chunk))
        chosen = sum(float(st["chosen_keys"]) for st in stats
                     if "chosen_keys" in st)
        out = [("linear_chunks", {}, float(chunks))]
        if n_sp:
            out += [("sparse_keys", {"kind": "chosen"}, chosen),
                    ("sparse_keys", {"kind": "visited"}, float(visited)),
                    ("sparse_blocks", {}, chosen / max(
                        1, real * n_sp * groups * s["sparse"]["block_size"]))]
        return out

    # ── parameters ──────────────────────────────────────────────────

    def init(self, key: jax.Array) -> Params:
        """Seeded random parameters in ``policy.param_dtype``: matrices
        normal with standard deviation 1/sqrt(fan-in), but the embedding
        1/``scale_emb`` (the stream enters the first block at unit
        scale, as a trained model's does, and not twelve times the
        blocks' outputs) and the head ``hidden_size / dim_model_base``
        over sqrt(fan-in) (unit logits after the muP division: the
        next-arc distribution is neither uniform nor one-hot); norm
        weights 1 + 0.1 normal, but the sparse mixer's ``q_norm`` twice
        that: an attention logit has standard deviation 2 at init (as
        ``RouteLM.init`` arranges), a first-stage logit over a mean of
        32 keys 0.35."""
        dt = self.policy.param_dtype
        s, d = self.sizes, self.sizes["hidden_size"]
        keys = iter(jax.random.split(key, 16 * (self.layers_held + 1)))

        def mat(*shape, gain=1.0):
            return (jax.random.normal(next(keys), shape, dt)
                    * jnp.asarray(gain / math.sqrt(shape[-2]), dt))

        def near_one(n, times=1.0):
            return (times * (1.0 + 0.1 * jax.random.normal(
                next(keys), (n,), jnp.float32))).astype(dt)

        layers = []
        for kind, _ in self.layer_kinds():
            if kind == SPARSE:
                dh = s["head_dim"]
                wide = s["num_attention_heads"] * dh
                narrow = s["num_key_value_heads"] * dh
                attn = {"w_q": mat(d, wide), "w_k": mat(d, narrow),
                        "w_v": mat(d, narrow), "q_norm": near_one(dh, 2.0),
                        "k_norm": near_one(dh), "w_gate": mat(d, wide),
                        "w_o": mat(wide, d)}
            else:
                dl = s["lightning_head_dim"]
                wide = s["lightning_nh"] * dl
                attn = {"w_q": mat(d, wide), "w_k": mat(d, wide),
                        "w_v": mat(d, wide), "q_norm": near_one(dl),
                        "k_norm": near_one(dl), "o_norm": near_one(wide),
                        "w_gate": mat(d, wide), "w_o": mat(wide, d)}
            f = s["intermediate_size"]
            layers.append({"attn_norm": near_one(d), "ffn_norm": near_one(d),
                           "attn": attn,
                           "ffn": {"w_gate": mat(d, f), "w_up": mat(d, f),
                                   "w_down": mat(f, d)}})
        embed = (jax.random.normal(next(keys), (self.vocab_held, d), dt)
                 * jnp.asarray(1.0 / s["scale_emb"], dt))
        return {"embed": embed,
                "head": mat(d, self.vocab_held, gain=1.0 / self.logit_scale),
                "final_norm": near_one(d), "layers": layers}

    # ── mixers ──────────────────────────────────────────────────────

    def linear(self, layer: int, p: Params, x, lengths):
        """x (B, L, d) the block's normed input → (y (B, L, d) float32,
        the state (B, H, dl, dl) float32 at each route's last token)."""
        s, dt = self.sizes, x.dtype
        b_sz, length, _ = x.shape
        heads, dl, eps = s["lightning_nh"], s["lightning_head_dim"], \
            s["rms_norm_eps"]
        scope = f"lm.L{layer}.linear"
        with jax.named_scope(scope):
            q, k, v = (dot32(x, p[w]).astype(dt).reshape(
                b_sz, length, heads, dl) for w in ("w_q", "w_k", "w_v"))
            pos = jnp.arange(length, dtype=jnp.int32)[None]
            theta = float(s["rope_theta"])
            q = rope(rms_norm(q, p["q_norm"], eps), pos, theta).astype(dt)
            k = rope(rms_norm(k, p["k_norm"], eps), pos, theta).astype(dt)
        o, state = linear_attn.chunked(
            q, k, v, linear_attn.log_decay(heads, layer,
                                           s["num_hidden_layers"]),
            lengths, dl ** -0.5, chunk=self.scan_chunk, scope=scope)
        with jax.named_scope(scope):
            o = rms_norm(o.reshape(b_sz, length, heads * dl), p["o_norm"],
                         eps)
            o = (o.astype(jnp.float32)
                 * jax.nn.sigmoid(dot32(x, p["w_gate"]))).astype(dt)
            return dot32(o, p["w_o"]), state

    def sparse(self, layer: int, p: Params, x, lengths, rows_at):
        """→ (y (B, L, d) float32, taps ``n_keys`` (B, L, G),
        ``n_visible`` (B, L), ``blocks`` (B, P, G, M))."""
        s, sp, dt = self.sizes, self.sizes["sparse"], x.dtype
        b_sz, length, _ = x.shape
        heads, groups, dh = (s["num_attention_heads"],
                             s["num_key_value_heads"], s["head_dim"])
        eps, scope = s["rms_norm_eps"], f"lm.L{layer}.sparse"
        with jax.named_scope(scope):
            q = dot32(x, p["w_q"]).astype(dt).reshape(
                b_sz, length, groups, heads // groups, dh)
            k = dot32(x, p["w_k"]).astype(dt).reshape(b_sz, length, groups,
                                                      dh)
            v = dot32(x, p["w_v"]).astype(dt).reshape(b_sz, length, groups,
                                                      dh)
            q, k = rms_norm(q, p["q_norm"], eps), rms_norm(k, p["k_norm"],
                                                           eps)
        o, n_keys, n_visible, blocks = block_sparse_attention(
            q, k, v, lengths, rows_at, scale=dh ** -0.5,
            dense_len=sp["dense_len"], top=sp["topk"],
            block=sp["block_size"], window=sp["kernel_size"],
            stride=sp["kernel_stride"], init=sp["init_blocks"],
            local=sp["window_size"], q_block=self.q_block,
            chunk=self.key_chunk, scope=scope)
        with jax.named_scope(scope):
            o = (o.reshape(b_sz, length, heads * dh).astype(jnp.float32)
                 * jax.nn.sigmoid(dot32(x, p["w_gate"]))).astype(dt)
            return dot32(o, p["w_o"]), {"n_keys": n_keys,
                                        "n_visible": n_visible,
                                        "blocks": blocks}

    # ── the model ───────────────────────────────────────────────────

    def apply(self, params: Params, ids, lengths, rows_at) -> Dict:
        """ids (B, L) int32, padded past ``lengths`` (B,); ``rows_at``
        (B, P) positions whose whole logit row is wanted. → per position
        ``next_logit`` (the logit of ids[t + 1]; 0 where there is none)
        and ``lse`` (B, L) float32, per route ``loglik`` (B,), ``rows``
        (B, P, vocab), and the taps, stacked over the layers of a kind:
        ``n_keys`` (n_sparse, B, L, G), ``n_visible`` (n_sparse, B, L),
        ``blocks`` (n_sparse, B, P, G, M), ``state`` (n_linear, B, H,
        dl, dl)."""
        b_sz, length = ids.shape
        s, dt = self.sizes, self.policy.compute_dtype
        eps, r = s["rms_norm_eps"], self.residual_scale
        h = (params["embed"][ids].astype(jnp.float32)
             * s["scale_emb"]).astype(dt)
        taps = {"n_keys": [], "n_visible": [], "blocks": [], "state": []}
        for i, (kind, l) in enumerate(self.layer_kinds()):
            p = params["layers"][i]
            x = rms_norm(h, p["attn_norm"], eps)
            if kind == LINEAR:
                y, state = self.linear(l, p["attn"], x, lengths)
                taps["state"].append(state)
            else:
                y, t = self.sparse(l, p["attn"], x, lengths, rows_at)
                for name, value in t.items():
                    taps[name].append(value)
            h = _settled(h + (r * y).astype(dt))
            x = rms_norm(h, p["ffn_norm"], eps).reshape(b_sz * length, -1)
            with jax.named_scope(f"lm.L{l}.mlp"):
                y = map_rows(lambda rows: gated_mlp(
                    rows, p["ffn"]["w_gate"], p["ffn"]["w_up"],
                    p["ffn"]["w_down"]), x, MLP_ROWS)
            h = _settled(h + (r * y).astype(dt).reshape(h.shape))
        next_logit, lse, rows = next_arc_head(
            params, h, ids, lengths, rows_at, eps, self.logit_scale)
        has_next = (jnp.arange(length)[None, :] + 1) < lengths[:, None]
        loglik = jnp.sum(jnp.where(has_next, next_logit - lse, 0.0), -1)
        out = {"next_logit": next_logit, "lse": lse, "loglik": loglik,
               "rows": rows}
        out.update({k: jnp.stack(v) for k, v in taps.items() if v})
        return out
