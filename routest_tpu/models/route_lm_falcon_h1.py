"""A fifth route-sequence language model behind the same scorer: the
architecture published as ``Falcon-H1-34B-Instruct`` (``model_type``
``falcon_h1``; its ``config.json`` keys are this model's ``sizes``),
next-arc likelihood over whole route histories as ``route_lm.RouteLM``
gives it. Everything in this file speaks of that architecture; what the
five models share lives in ``lm_common.py``.

Every block is the same hybrid: TWO mixers read one normed input and add
into one residual, then a dense gated MLP, with the muP multipliers of
the family where the published forward applies them:

- ``h = RMSNorm(x)``; ``x += ssm_out_multiplier * Mamba2(h) +
  attention_out_multiplier * Attn(attention_in_multiplier * h)``;
  ``x += W_down(silu(gate_mult * h' W_gate) * h' W_up) * down_mult``,
  ``h' = RMSNorm(x)``, ``(gate_mult, down_mult) = mlp_multipliers``;
- **Mamba2**: ``in_proj`` of ``ssm_in_multiplier * h`` in three parts,
  ``z`` (``mamba_d_ssm``), ``xBC`` (``mamba_d_ssm + 2 G N``) and ``dt``
  (``mamba_n_heads``), the five parts z, x, B, C, dt times
  ``ssm_multipliers``; ``xBC`` through the causal depthwise convolution
  of ``mamba_d_conv`` taps with a bias and ``silu``; ``dt = softplus(dt +
  dt_bias)``, 0 at a padded position; ``A = -exp(A_log)``; the scan of
  ``parallel/ssd.py`` (``mamba_n_heads`` heads of ``mamba_d_head``, a
  state of ``mamba_d_state`` per group of ``mamba_n_groups``, a ``D``
  skip, chunks of ``mamba_chunk_size``); the gated RMSNorm over each
  group's ``mamba_d_ssm / G`` lanes of ``y * silu(z)`` (the gate before
  the norm: ``mamba_norm_before_gate`` false); ``out_proj``;
- **attention**: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key-value heads of ``head_dim``, the keys
  times ``key_multiplier``, RoPE (rotate-half, ``rope_theta``, the whole
  head) on queries and keys, every causal key (``parallel/gqa.py``),
  the softmax scaled by ``head_dim ** -0.5``;
- the embedding times ``embedding_multiplier``, the logits times
  ``lm_head_multiplier``; the head is not tied.

**A pipeline stage**, with a slice of the vocabulary: the model holds
the ``layers_held`` published blocks from ``layers_first`` on (each
whole) and ``vocab_held`` rows of the embedding and the head (the
vocabulary divided by rows over ``vocab_chips`` chips). A block's scope
names go by its PUBLISHED index.

The equations are written out in ``benchmark/reference/falcon_h1_ref.py``,
the plain float32 reference this model is tested against. Here the
parameters and activations are ``policy.compute_dtype`` (bfloat16),
products accumulate in float32, and the norms' statistics, the softmax,
``dt``, the decays and the scan's state are float32; ``A_log``,
``dt_bias`` and ``D`` are kept in float32. The scan runs as a kernel on
a TPU at bfloat16 shapes that tile and as XLA elsewhere
(``ssd.ssd_path``); the attention is XLA. No option selects either.

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
                                          rms_norm, rope, settled)
from routest_tpu.parallel import gqa, ssd
from routest_tpu.parallel.expert import gated_mlp

Params = Dict

# the published keys the model reads; an artifact's header carries them
SIZE_KEYS = (
    "attention_bias", "attention_in_multiplier", "attention_out_multiplier",
    "embedding_multiplier", "head_dim", "hidden_size", "intermediate_size",
    "key_multiplier", "lm_head_multiplier", "mamba_chunk_size",
    "mamba_conv_bias", "mamba_d_conv", "mamba_d_head", "mamba_d_ssm",
    "mamba_d_state", "mamba_n_groups", "mamba_n_heads",
    "mamba_norm_before_gate", "mamba_proj_bias", "mamba_rms_norm",
    "mlp_bias", "mlp_multipliers", "num_attention_heads",
    "num_hidden_layers", "num_key_value_heads", "projectors_bias",
    "rms_norm_eps", "rope_scaling", "rope_theta", "ssm_in_multiplier",
    "ssm_multipliers", "ssm_out_multiplier", "tie_word_embeddings",
    "vocab_size")
MLP_ROWS = 2048         # tokens of one product of the MLP


def scaled(x, m: float):
    """``x`` times a published multiplier (left alone at 1)."""
    return x if m == 1.0 else x * m


def gated_rms_norm(y, z, w, groups: int, eps: float):
    """``RMSNorm(y * silu(z))`` over each of ``groups`` equal parts of
    the last axis, with the weight ``w``: the gate BEFORE the norm
    (``mamba_norm_before_gate`` false). Statistics in float32."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    parts = g.reshape(g.shape[:-1] + (groups, -1))
    parts = parts * jax.lax.rsqrt(
        jnp.mean(parts * parts, -1, keepdims=True) + eps)
    return (parts.reshape(g.shape) * w.astype(jnp.float32)).astype(y.dtype)


@dataclasses.dataclass(frozen=True)
class RouteLMFalconH1:
    sizes: Mapping              # the published keys, published values
    layers_held: int
    vocab_held: int
    layers_first: int = 0
    vocab_chips: int = 1
    policy: Policy = BF16_POLICY
    # queries of a block and keys of a chunk of the causal softmax
    # (lengths are padded to multiples of the block and of the scan's
    # chunk)
    full_block: int = 256
    key_chunk: int = 1024

    @classmethod
    def from_config(cls, cfg: Mapping, policy: Policy = BF16_POLICY):
        """From a configuration that states the share: the published
        keys, where ``num_hidden_layers`` and ``vocab_size`` give what
        is HELD and ``cfg["published"]`` the published counts of those
        two; ``cfg["share"]`` names ``layers_first`` and
        ``vocab_chips``. The block sizes may be stated too (a toy size
        states smaller ones)."""
        sizes = {k: cfg[k] for k in SIZE_KEYS}
        sizes.update(cfg.get("published", {}))
        share = cfg.get("share", {})
        blocks = {k: int(cfg[k]) for k in ("full_block", "key_chunk")
                  if k in cfg}
        return cls(sizes=sizes, layers_held=int(cfg["num_hidden_layers"]),
                   vocab_held=int(cfg["vocab_size"]),
                   layers_first=int(share.get("layers_first", 0)),
                   vocab_chips=int(share.get("vocab_chips", 1)),
                   policy=policy, **blocks)

    def __post_init__(self) -> None:
        s = self.sizes
        built = {"mamba_norm_before_gate": False, "mamba_rms_norm": True,
                 "mamba_conv_bias": True, "mamba_proj_bias": False,
                 "attention_bias": False, "mlp_bias": False,
                 "projectors_bias": False, "tie_word_embeddings": False,
                 "rope_scaling": None}
        other = {k: s[k] for k, v in built.items() if s[k] != v}
        if other:
            raise ValueError(f"built for {built}; the sizes say {other}")
        if s["num_attention_heads"] % s["num_key_value_heads"]:
            raise ValueError("query heads are not whole groups")
        if (s["mamba_d_ssm"] != s["mamba_n_heads"] * s["mamba_d_head"]
                or s["mamba_n_heads"] % s["mamba_n_groups"]):
            raise ValueError("the state-space heads are not the mixer's "
                             "width in whole groups")
        if self.layers_first + self.layers_held > s["num_hidden_layers"]:
            raise ValueError("the run of layers passes the published depth")

    # ── what the share holds ────────────────────────────────────────

    def share_header(self) -> Dict:
        return {"layers_held": self.layers_held,
                "layers_first": self.layers_first,
                "vocab_held": self.vocab_held,
                "vocab_chips": self.vocab_chips}

    def holds(self, params: Params) -> bool:
        """Whether the arrays are this share: as many blocks, each
        hybrid, and the held rows of the vocabulary."""
        return (len(params["layers"]) == self.layers_held
                and all("ssm" in p and "attn" in p for p in params["layers"])
                and params["embed"].shape[0] == self.vocab_held)

    def layer_indices(self) -> List[int]:
        """The PUBLISHED index of each held block."""
        return list(range(self.layers_first,
                          self.layers_first + self.layers_held))

    def ssm_multipliers(self) -> Tuple[float, ...]:
        """(z, x, B, C, dt): what the in-projection's five parts are
        multiplied by."""
        return tuple(float(m) for m in self.sizes["ssm_multipliers"])

    def ssm_shape(self) -> Tuple[int, int, int, int]:
        """(heads, head width P, groups G, state N) of the scan."""
        s = self.sizes
        return (int(s["mamba_n_heads"]), int(s["mamba_d_head"]),
                int(s["mamba_n_groups"]), int(s["mamba_d_state"]))

    def ssm_steps(self) -> str:
        """Which form of the scan a block runs (``"fused"`` or
        ``"xla"``: what ``ssd.ssd_path`` says of this model's shapes
        here)."""
        heads, p, groups, n = self.ssm_shape()
        return ssd.ssd_path(heads, p, n, self.policy.compute_dtype,
                            groups=groups,
                            chunk=int(self.sizes["mamba_chunk_size"]))

    # ── what the scorer asks of a model (serve/seq_score.py) ────────

    @property
    def length_quantum(self) -> int:
        return int(math.lcm(self.full_block,
                            int(self.sizes["mamba_chunk_size"])))

    def tap_tables(self, n_rows: int, width: int, n_named: int) -> Dict:
        """name → (shape, dtype, axis of the length, tokens an entry of
        that axis)."""
        heads, p, _, n = self.ssm_shape()
        over = (self.layers_held, n_rows, width)
        return {"n_keys": (over, jnp.int32, 2, 1),
                "first_key": (over, jnp.int32, 2, 1),
                "state": ((self.layers_held, n_rows, heads, p, n),
                          jnp.float32, None, 1)}

    def step_attrs(self, length: int) -> Dict[str, str]:
        return {"mixers": f"ssm={self.ssm_steps()},attn=xla"}

    def step_stats(self, out: Dict, lengths) -> Dict:
        """Device values of one step for the pass's counters: the keys
        each block's real queries saw (one sum a block)."""
        real = (jnp.arange(out["n_keys"].shape[2])[None, :]
                < lengths[:, None])[None]
        return {"keys_seen": jnp.sum(jnp.where(real, out["n_keys"], 0),
                                     (1, 2))}

    def pass_counts(self, steps, stats, real: int) -> List[Tuple]:
        """(family, labels, value) of one pass for the scorer's
        counters: the keys the attention's real queries saw (from
        ``stats``) and multiplied, and the scan's chunk steps (routes x
        chunks x blocks, by the form that ran them), from the plan."""
        import numpy as np

        chunk = int(self.sizes["mamba_chunk_size"])
        visited = chunks = 0
        for step in steps:
            n = len(step.routes) * self.layers_held
            visited += n * gqa.causal_visited(step.length, self.full_block,
                                              self.key_chunk)
            chunks += n * ssd.chunk_count(step.length, chunk)
        seen = sum(float(np.asarray(s["keys_seen"], np.float64).sum())
                   for s in stats)
        return [("gqa_keys", {"layer": "full", "kind": "needed"}, seen),
                ("gqa_keys", {"layer": "full", "kind": "visited"},
                 float(visited)),
                ("ssm_chunks", {"path": self.ssm_steps()}, float(chunks))]

    # ── parameters ──────────────────────────────────────────────────

    def init(self, key: jax.Array) -> Params:
        """Seeded random parameters: matrices normal with standard
        deviation 1/sqrt(fan-in) in ``policy.param_dtype``, norm weights
        1 + 0.1 normal, and the two places tuned as the other models'
        are: the keys' matrix ``2 / key_multiplier`` times that, so that
        an attention logit has standard deviation 2 at init (neither
        uniform nor one-hot), and the head ``1 / lm_head_multiplier``
        times it, so that the logits are of unit scale after the
        multiplier; the embedding ``1 / embedding_multiplier``, so the
        stream enters the first block at unit scale. The state-space
        mixer as Mamba-2 initialises it: ``A_log = log U[1, 16]``,
        ``dt_bias`` the inverse softplus of a ``dt`` log-uniform in
        [0.001, 0.1], ``D = 1`` (those three float32), and the
        convolution as a depthwise ``Conv1d`` is: weight and bias
        uniform in ``±1 / sqrt(mamba_d_conv)``."""
        dt = self.policy.param_dtype
        s, d = self.sizes, self.sizes["hidden_size"]
        heads, p, groups, n = self.ssm_shape()
        keys = iter(jax.random.split(key, 24 * (self.layers_held + 1)))
        f32 = jnp.float32

        def mat(*shape, gain=1.0):
            return (jax.random.normal(next(keys), shape, dt)
                    * jnp.asarray(gain / math.sqrt(shape[-2]), dt))

        def near_one(width):
            return (1.0 + 0.1 * jax.random.normal(
                next(keys), (width,), f32)).astype(dt)

        def uniform(shape, lo, hi):
            return jax.random.uniform(next(keys), shape, f32, lo, hi)

        dh = s["head_dim"]
        wide = s["num_attention_heads"] * dh
        narrow = s["num_key_value_heads"] * dh
        conv_width = s["mamba_d_ssm"] + 2 * groups * n
        taps = int(s["mamba_d_conv"])
        bound = 1.0 / math.sqrt(taps)
        f = s["intermediate_size"]

        def block():
            step0 = jnp.exp(uniform((heads,), math.log(1e-3),
                                    math.log(0.1)))
            ssm = {"w_z": mat(d, s["mamba_d_ssm"]),
                   "w_xbc": mat(d, conv_width), "w_dt": mat(d, heads),
                   "conv_w": uniform((taps, conv_width), -bound,
                                     bound).astype(dt),
                   "conv_b": uniform((conv_width,), -bound,
                                     bound).astype(dt),
                   "dt_bias": step0 + jnp.log(-jnp.expm1(-step0)),
                   "a_log": jnp.log(uniform((heads,), 1.0, 16.0)),
                   "d": jnp.ones((heads,), f32),
                   "norm": near_one(s["mamba_d_ssm"]),
                   "w_out": mat(s["mamba_d_ssm"], d)}
            attn = {"w_q": mat(d, wide),
                    "w_k": mat(d, narrow, gain=2.0 / s["key_multiplier"]),
                    "w_v": mat(d, narrow), "w_o": mat(wide, d)}
            return {"input_norm": near_one(d), "ffn_norm": near_one(d),
                    "ssm": ssm, "attn": attn,
                    "ffn": {"w_gate": mat(d, f), "w_up": mat(d, f),
                            "w_down": mat(f, d)}}

        layers = [block() for _ in range(self.layers_held)]
        embed = (jax.random.normal(next(keys), (self.vocab_held, d), dt)
                 * jnp.asarray(1.0 / s["embedding_multiplier"], dt))
        return {"layers": layers, "embed": embed,
                "head": mat(d, self.vocab_held,
                            gain=1.0 / s["lm_head_multiplier"]),
                "final_norm": near_one(d)}

    # ── mixers ──────────────────────────────────────────────────────

    def step_size(self, dt, bias, live):
        """``softplus(dt + dt_bias)`` (B, L, H) float32, 0 where the
        position is no real token."""
        return ssd.live_step(jax.nn.softplus(
            dt.astype(jnp.float32) + bias.astype(jnp.float32)), live)

    def ssm(self, layer: int, p: Params, x, live):
        """x (B, L, d) the block's normed input, ``live`` (B, L) → (y
        (B, L, d) float32 before ``ssm_out_multiplier``, the state (B,
        H, P, N) float32 at each route's last real token)."""
        s, dt = self.sizes, x.dtype
        heads, p_dim, groups, n = self.ssm_shape()
        m_z, m_x, m_b, m_c, m_dt = self.ssm_multipliers()
        scope = f"lm.L{layer}.ssm"
        with jax.named_scope(scope):
            u = scaled(x, s["ssm_in_multiplier"])
            z = scaled(dot32(u, p["w_z"]), m_z).astype(dt)
            width = heads * p_dim
            mup = jnp.concatenate([jnp.full((width,), m_x, jnp.float32),
                                   jnp.full((groups * n,), m_b, jnp.float32),
                                   jnp.full((groups * n,), m_c, jnp.float32)])
            xbc = (dot32(u, p["w_xbc"]) * mup).astype(dt)
            xbc = jax.nn.silu(ssd.causal_conv(xbc, p["conv_w"],
                                              p["conv_b"])).astype(dt)
            step = self.step_size(scaled(dot32(u, p["w_dt"]), m_dt),
                                  p["dt_bias"], live)
            a = -jnp.exp(p["a_log"].astype(jnp.float32))
        y, state = ssd.scan(xbc, step, a, p["d"], heads=heads, groups=groups,
                            state=n, chunk=int(s["mamba_chunk_size"]),
                            scope=scope + ".scan")
        with jax.named_scope(scope):
            y = gated_rms_norm(y, z, p["norm"], groups, s["rms_norm_eps"])
            return dot32(y, p["w_out"]), state

    def attention(self, layer: int, p: Params, x):
        """x (B, L, d) the block's normed input → (y (B, L, d) float32
        before ``attention_out_multiplier``, n_keys (B, L), first_key
        (B, L))."""
        s, dt = self.sizes, x.dtype
        b_sz, length, _ = x.shape
        heads, groups, dh = (s["num_attention_heads"],
                             s["num_key_value_heads"], s["head_dim"])
        scope = f"lm.L{layer}.attn"
        with jax.named_scope(scope):
            u = scaled(x, s["attention_in_multiplier"])
            pos = jnp.arange(length, dtype=jnp.int32)[None]
            theta = float(s["rope_theta"])
            q = dot32(u, p["w_q"]).reshape(b_sz, length, heads, dh)
            k = (dot32(u, p["w_k"]) * s["key_multiplier"]).reshape(
                b_sz, length, groups, dh)
            v = dot32(u, p["w_v"]).astype(dt).reshape(b_sz, length, groups,
                                                      dh)
            q = rope(q.astype(dt), pos, theta).astype(dt).reshape(
                b_sz, length, groups, heads // groups, dh)
            k = rope(k.astype(dt), pos, theta).astype(dt)
        o, n_keys, first = gqa.causal_attention(
            q, k, v, scale=dh ** -0.5, block=self.full_block,
            chunk=self.key_chunk, scope=scope + ".full")
        with jax.named_scope(scope):
            return dot32(o.reshape(b_sz, length, heads * dh),
                         p["w_o"]), n_keys, first

    def block(self, layer: int, p: Params, h, live, taps: Dict):
        """One hybrid block over the stream h (B, L, d): both mixers on
        one normed input into one residual, then the MLP; the block's
        taps appended to ``taps``."""
        s, dt = self.sizes, h.dtype
        eps = s["rms_norm_eps"]
        x = rms_norm(h, p["input_norm"], eps)
        y_ssm, state = self.ssm(layer, p["ssm"], x, live)
        y_attn, n_keys, first = self.attention(layer, p["attn"], x)
        h = settled(h + (y_ssm * s["ssm_out_multiplier"]
                         + y_attn * s["attention_out_multiplier"]).astype(dt))
        x = rms_norm(h, p["ffn_norm"], eps).reshape(-1, h.shape[-1])
        gate_mult, down_mult = (float(m) for m in s["mlp_multipliers"])
        f = p["ffn"]
        with jax.named_scope(f"lm.L{layer}.mlp"):
            y = map_rows(lambda rows: gated_mlp(
                rows, f["w_gate"], f["w_up"], f["w_down"],
                gate_mult=gate_mult, down_mult=down_mult), x, MLP_ROWS)
        h = settled(h + y.astype(dt).reshape(h.shape))
        taps["n_keys"].append(n_keys)
        taps["first_key"].append(first)
        taps["state"].append(state)
        return h

    # ── the model ───────────────────────────────────────────────────

    def apply(self, params: Params, ids, lengths, rows_at) -> Dict:
        """ids (B, L) int32 within the held slice, padded past
        ``lengths`` (B,); ``rows_at`` (B, P) positions whose whole logit
        row is wanted. → per position ``next_logit`` (the logit of
        ids[t + 1]; 0 where there is none) and ``lse`` (B, L) float32,
        per route ``loglik`` (B,), ``rows`` (B, P, vocab_held); and the
        taps over the held blocks: ``n_keys`` / ``first_key`` (blocks,
        B, L), ``state`` (blocks, B, H, P, N) float32 at each route's
        last real token."""
        length = ids.shape[1]
        s, dt = self.sizes, self.policy.compute_dtype
        eps = s["rms_norm_eps"]
        at = jnp.arange(length)[None, :]
        live = at < lengths[:, None]
        h = (params["embed"][ids].astype(jnp.float32)
             * s["embedding_multiplier"]).astype(dt)
        taps = {"n_keys": [], "first_key": [], "state": []}
        for i, layer in enumerate(self.layer_indices()):
            h = self.block(layer, params["layers"][i], h, live, taps)
        next_logit, lse, rows = next_arc_head(
            params, h, ids, lengths, rows_at, eps,
            float(s["lm_head_multiplier"]))
        loglik = jnp.sum(jnp.where(at + 1 < lengths[:, None],
                                   next_logit - lse, 0.0), -1)
        out = {"next_logit": next_logit, "lse": lse, "loglik": loglik,
               "rows": rows}
        out.update({k: jnp.stack(v) for k, v in taps.items()})
        return out
