"""The route-sequence backbones at toy sizes for the CPU tests, one entry
a model, keyed by its class's name as an artifact's header names it
(``train/checkpoint._route_lm_types``). Each toy has every mechanism of
its published architecture at widths of tens; its entry carries the
plain reference and that reference's call, the outputs the model
answers, the limits the shared contract (``test_route_lm_contract.py``)
holds it to and the shares an artifact of it must refuse. A toy's
parameters and programs are made once a process."""

import dataclasses
import functools
import json
import os
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import (counts_falcon, counts_gigachat, counts_kexaone,
                       counts_sala, counts_seq)
from benchmark.reference import (dots3_ref, falcon_h1_ref, gigachat_ref,
                                 kexaone_ref, sala_ref)
from routest_tpu.core.dtypes import Policy
from routest_tpu.train.checkpoint import _route_lm_types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32 = Policy(param_dtype=jnp.float32, compute_dtype=jnp.float32)
OUTPUTS = ("next_logit", "lse", "rows", "loglik")
MTP = ("mtp_next_logit", "mtp_lse", "mtp_loglik")


def highest(fn):
    def run(*a, **k):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **k)
    return run


def pick(out, what, b, n):
    """Route b's part of an output table (its first n positions; the
    prediction module's first n - 1; a state's every layer)."""
    if what.startswith("mtp_"):
        col = out[what][0, b]
        return col if what == "mtp_loglik" else col[:n - 1]
    if what == "state":
        return out[what][:, b]
    return out[what][b] if what in ("rows", "loglik") else out[what][b, :n]


def by_layer(what, got, want):
    return zip(got, want) if what == "state" else [(got, want)]


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def close(got, want, tol, what=""):
    """``tol``: ``(rtol, atol)`` elementwise, or a float, a bound on the
    relative norm of the gap."""
    if isinstance(tol, float):
        assert rel(got, want) < tol, what
    else:
        np.testing.assert_allclose(got, want, rtol=tol[0], atol=tol[1],
                                   err_msg=what)


def taps_equal(out, w, b, taps):
    """Route b's taps are the reference's, cut to the reference's
    shapes; the chosen experts as sets."""
    for key in taps:
        for i, want in enumerate(w.get(key, ())):
            got = np.asarray(out[key][i, b])[
                tuple(slice(0, s) for s in np.shape(want))]
            if key == "chosen":
                got, want = np.sort(got, -1), np.sort(want, -1)
            np.testing.assert_array_equal(got, want, err_msg=f"{key} {i}")


class Toy(NamedTuple):
    """A tolerance is ``close``'s; ``"*"`` is every other output's."""
    name: str
    config: Dict
    reference: object             # the module whose ``forward`` it is
    share: Optional[Tuple[int, int]]   # the experts the reference holds
    outputs: Tuple[str, ...]
    seed: int                     # the float32 toy's parameters
    table: Tuple[int, Tuple[int, ...]]   # its routes: seed, lengths
    blocks: Optional[object]      # the reference's, at the table's width
    bf16: Optional[Dict]          # seed, routes' seed, tables, limits
    own: Dict                     # a route where its bits may move
    refuse: Dict                  # share key → a value refused
    liar: Callable                # (model, params) → not the header's share
    config_file: str              # the cell's, under benchmark/configs
    cut: Dict                     # its reduced keys, at the cell's values
    cell_share: Dict
    count: Dict                   # at the published widths, exactly
    counted: Callable             # the counting module's, from the config
    attrs: Dict                   # the model's at the published widths
    laws: Callable = lambda w, n: None   # (reference's answers, n): taps
    floors: Callable = lambda cfg: True
    f32: Dict = {"*": (2e-6, 2e-5)}
    own_seeds: Dict = {}          # a case's routes' seed, if not the table's
    own_exact: Tuple[str, ...] = ("in_a_table", "alone")

    def model(self, policy=F32, **changes):
        return _route_lm_types()[self.name].from_config(
            dict(self.config, **changes), policy=policy)

    def routes(self, seed: int, lengths, named: int = 3):
        """ids (R, max length), lengths, rows_at (R, named), as numpy."""
        rng = np.random.default_rng(seed)
        lengths = np.asarray(lengths, np.int32)
        ids = rng.integers(0, self.config["vocab_size"],
                           (len(lengths), int(lengths.max()))).astype(np.int32)
        ids = np.where(np.arange(ids.shape[1])[None] < lengths[:, None], ids, 0)
        rows_at = np.stack([np.sort(rng.choice(int(n) - 1, named,
                                               replace=False))
                            for n in lengths]).astype(np.int32)
        return ids, lengths, rows_at

    def cell(self):
        """The cell's configuration at the published widths."""
        with open(os.path.join(REPO, "benchmark", "configs",
                               self.config_file + ".json")) as f:
            return json.load(f)

    def forward(self, params, ids, rows_at, config=None, **kw):
        """The reference on one route."""
        share = () if self.share is None else (self.share,)
        return self.reference.forward(params, config or self.config, ids,
                                      *share, list(rows_at), **kw)

    def matches(self, out, want, b, n, what):
        """Route b's ``what`` is the reference's within the float32
        tolerance: a state layer by layer, in units of its largest."""
        tol = self.f32.get(what, self.f32["*"])
        for g, w in by_layer(what, pick(out, what, b, n), want[what]):
            scale = max(float(np.abs(w).max()), 1e-12) if what == "state" \
                else 1.0
            close(np.asarray(g) / scale, np.asarray(w) / scale, tol, what)


@functools.lru_cache(maxsize=None)
def model(name: str, policy: Policy):
    return TOYS[name].model(policy)


@functools.lru_cache(maxsize=None)
def _init(name: str, policy: Policy):
    return jax.jit(model(name, policy).init)


@functools.lru_cache(maxsize=None)
def params(name: str, policy: Policy, seed: int):
    return _init(name, policy)(jax.random.PRNGKey(seed))


@functools.lru_cache(maxsize=None)
def program(name: str, policy: Policy):
    """The toy's ``apply``, jitted once: each shape compiles once."""
    return jax.jit(model(name, policy).apply)


def expert_layer(seed=0, n_exp=16, bias=0.3, d=64, width=32):
    """A seeded expert layer: n_exp gated experts of width 32 over 64,
    a router and its bias, a shared expert."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)

    def mlp(k, lead=()):
        return {"w_gate": jax.random.normal(k[0], lead + (d, width)) / 8,
                "w_up": jax.random.normal(k[1], lead + (d, width)) / 8,
                "w_down": jax.random.normal(k[2], lead + (width, d)) / 6}

    return dict(mlp(ks[:3], (n_exp,)), shared=mlp(ks[5:8]),
                router=jax.random.normal(ks[3], (d, n_exp)) / 8,
                bias=bias * jax.random.normal(ks[4], (n_exp,)))


def _dots3_laws(w, n):
    # a query of a selecting layer sees min(t + 1, index_topk) keys, a
    # windowed one min(t + 1, window)
    t = np.arange(n) + 1
    np.testing.assert_array_equal(w["n_keys"][1], np.minimum(t, 16))
    np.testing.assert_array_equal(w["n_keys"][2], np.minimum(t, 9))


def _sala_laws(w, n):
    for keys in w["n_keys"]:            # the two sparse layers
        if n < 48:                      # dense: every causal key
            assert (keys[-1] == n).all()
        else:                           # six blocks of eight, less the future
            assert (keys[-1] < n).all() and (keys[-1] > 40).all()


def _causal_laws(window, sliding):
    """Five trunk blocks and the module's (n - 1 positions): a sliding
    one sees min(t + 1, window) keys from t + 1 - window on, a full one
    every causal key."""
    def laws(w, n):
        assert len(w["n_keys"]) == 6 and len(w["chosen"]) == 5
        for i in range(6):
            t = np.arange(n if i < 5 else n - 1)
            cap = window if i in sliding else len(t)
            np.testing.assert_array_equal(w["n_keys"][i],
                                          np.minimum(t + 1, cap))
            np.testing.assert_array_equal(w["first_key"][i],
                                          np.maximum(t + 1 - cap, 0))
    return laws


def _gigachat_laws(w, n):
    _causal_laws(None, ())(w, n)
    for chosen in w["chosen"]:          # at most four of eight groups
        groups = [len(set(np.asarray(row) // 4)) for row in chosen]
        assert min(groups) >= 1 and max(groups) <= 4


def _drop_mtp(m, p):
    return m, {k: v for k, v in p.items() if k != "mtp"}


# two full layers (4 heads, a selector choosing 16 keys) and three
# sliding ones (2 heads, a window of 9), a dense first layer then 16
# experts of which a token takes 4 and a share holds 8, a vocabulary
# slice of 128
DOTS3 = Toy(
    name="RouteLM", config=dict(
        apply_mla_qkv_lora_rescale=True, first_k_dense_replace=1,
        hidden_size=64, index_head_dim=16, index_n_heads=4, index_topk=16,
        intermediate_size=96, kv_lora_rank=16,
        layer_types=["full_attention", "full_attention", "sliding_attention",
                     "sliding_attention", "sliding_attention"] * 2,
        moe_intermediate_size=32, n_routed_experts=8, n_shared_experts=1,
        norm_topk_prob=True, num_attention_heads=4, num_experts_per_tok=4,
        num_hidden_layers=5, q_lora_rank=24, qk_nope_head_dim=16,
        qk_rope_head_dim=8, rms_norm_eps=1e-5, rope_theta=8e7,
        routed_scaling_factor=1, sliding_window_size=9, swa_kv_lora_rank=24,
        swa_num_attention_heads=2, swa_q_lora_rank=24,
        swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8, swa_rope_theta=5e4,
        swa_v_head_dim=16, v_head_dim=16, vocab_size=128,
        published={"n_routed_experts": 16, "vocab_size": 1024,
                   "num_hidden_layers": 10},
        share={"chips_per_layer": 2, "experts_first": 0},
        select_block=8, window_block=8, key_chunk=16),
    reference=dots3_ref, share=(0, 8), outputs=OUTPUTS, seed=0,
    table=(0, (96, 41, 17)),
    # one padded length, so that the reference's eager operations
    # compile once; its blocks are exercised on the way
    blocks=dots3_ref.Blocks(q_block=32, sel_block=16, head_group=2,
                            row_block=48, pad_to=96),
    laws=_dots3_laws, bf16=None,
    own={"*": (1e-7, 2e-5), "loglik": (1e-5, 0)},
    refuse={"experts_held": 16, "experts_first": 8, "vocab_held": 1024,
            "layers_held": 10, "chips_per_layer": 4},
    liar=lambda m, p: (dataclasses.replace(m, experts_held=4), p),
    config_file="dots3-note-prev-ep8",
    cut={"num_hidden_layers": 5, "n_routed_experts": 32,
         "vocab_size": 19008},
    cell_share={"chips_per_layer": 8, "experts_first": 0},
    # counted by hand from the published widths: 4,087 M
    count={"parameters": 4_087_154_176, "matrices": 4_087_087_104,
           "bytes": 8_174_310_400},
    counted=lambda cfg: {"matrices": counts_seq.weight_bytes(cfg) / 2},
    attrs={"share": (256, 0, 32), "length_quantum": 512,
           "vocab_held": 19008, "layer_kinds": [
               ("full_attention", "dense"), ("full_attention", "moe")]
           + [("sliding_attention", "moe")] * 3})

# a run of four layers out of ten (sparse, linear, linear, sparse: the
# published layers 3-6), 4 query heads over 2 key-value heads,
# compressed keys of 4 at a stride of 2, blocks of 8 keys of which a
# query takes 6 (the first and a local window of 16 forced), dense
# below 48 tokens, a linear state of 16 x 16 a head over chunks of 8
SALA = Toy(
    name="RouteLMSala", config=dict(
        attn_use_output_gate=True, attn_use_rope=False, dim_model_base=16,
        head_dim=16, hidden_size=64, intermediate_size=96,
        lightning_head_dim=16, lightning_nh=4, lightning_nkv=4,
        lightning_use_rope=True,
        mixer_types=["minicpm4", "lightning-attn", "lightning-attn",
                     "minicpm4", "lightning-attn", "lightning-attn",
                     "minicpm4", "minicpm4", "lightning-attn", "minicpm4"],
        num_attention_heads=4, num_hidden_layers=4, num_key_value_heads=2,
        qk_norm=True, rms_norm_eps=1e-6, rope_theta=10000, scale_depth=1.4,
        scale_emb=12, use_output_gate=True, use_output_norm=True,
        vocab_size=128,
        sparse={"kernel_size": 4, "kernel_stride": 2, "block_size": 8,
                "topk": 6, "init_blocks": 1, "window_size": 16,
                "dense_len": 48},
        published={"num_hidden_layers": 10},
        share={"layers_first": 3, "chips_per_layer": 1},
        q_block=8, key_chunk=16, scan_chunk=8),
    reference=sala_ref, share=None, outputs=OUTPUTS + ("state",), seed=1,
    # two routes that select (dense_len is 48) and one that does not
    table=(0, (96, 33, 70)),
    blocks=sala_ref.Blocks(q_block=32, row_block=48, pad_to=96),
    laws=_sala_laws, f32={"*": (2e-6, 2e-5), "state": 2e-6},
    # the gaps the cell compares, at a toy width (several times noisier
    # than 4,096)
    bf16={"seed": 2, "routes": 3, "tables": ((96,), (40,)),
          "limits": {"next_logit": 0.05, "lse": 2e-3, "rows": 0.05,
                     "state": 0.03, "blocks": 0.1}},
    own={"*": (1e-5, 1e-5)},
    refuse={"layers_first": 0, "layers_held": 8, "vocab_held": 1024,
            "chips_per_layer": 4},
    # the same number of layers from another place of the pattern
    liar=lambda m, p: (dataclasses.replace(m, layers_first=4), p),
    config_file="minicpm-sala-l9-16", cut={"num_hidden_layers": 8},
    cell_share={"layers_first": 9, "chips_per_layer": 1},
    # reckoned by hand: 2,820.5 M from the matrices alone (2,820.47 M);
    # the norms' vectors add 0.1 M
    count={"parameters": 2_820_569_088,
           "matrices": 2 * 253_755_392 + 6 * 285_212_672 + 601_686_016,
           "bytes": 2 * 2_820_569_088},
    counted=lambda cfg: {"parameters": counts_sala.parameter_count(cfg)},
    attrs={"length_quantum": 256, "vocab_held": 73448, "layer_kinds": [
        ("minicpm4", 9)] + [("lightning-attn", i) for i in range(10, 16)]
        + [("minicpm4", 16)]},
    floors=lambda cfg: [i for i, k in enumerate(cfg["mixer_types"])
                        if k == "minicpm4"] == [0, 9, 16, 17, 22, 29, 30, 31]
    and len(cfg["mixer_types"]) == 32)

# five layers of eight (sliding, sliding, sliding, full, sliding), 8
# query heads over 2 key-value heads of 16, a window of 8 keys, a dense
# first layer then 16 experts of which a token takes 4 at a routed
# scaling of 2.5 and a share holds 8, a shared expert, a vocabulary
# slice of 128 and the prediction module
_K_ATTENTION = 2 * 6144 * 8192 + 2 * 6144 * 1024                # 113.25 M
_K_SPARSE = _K_ATTENTION + 6144 * 128 + 17 * 3 * 6144 * 2048    # 755.76 M
KEXAONE = Toy(
    name="RouteLMKExaone", config=dict(
        first_k_dense_replace=1, head_dim=16, hidden_size=64,
        intermediate_size=96,
        layer_types=["sliding_attention", "sliding_attention",
                     "sliding_attention", "full_attention"] * 2,
        mlp_layer_types=["dense"] + ["sparse"] * 7, moe_intermediate_size=32,
        mtp_layer_types=["full_attention"], n_group=1, norm_topk_prob=True,
        num_attention_heads=8, num_experts=8, num_experts_per_tok=4,
        num_hidden_layers=5, num_key_value_heads=2,
        num_nextn_predict_layers=1, num_shared_experts=1, rms_norm_eps=1e-5,
        rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
        routed_scaling_factor=2.5, scoring_func="sigmoid", sliding_window=8,
        topk_group=1, vocab_size=128,
        published={"num_hidden_layers": 8, "num_experts": 16,
                   "vocab_size": 1024},
        share={"chips_per_layer": 2, "experts_first": 0},
        full_block=8, window_block=8, key_chunk=16, window_rows=16),
    reference=kexaone_ref, share=(0, 8), outputs=OUTPUTS + MTP, seed=1,
    table=(0, (96, 33, 70)),
    blocks=dots3_ref.Blocks(q_block=32, row_block=48, expert_cap=1,
                            pad_to=96),
    laws=_causal_laws(8, (0, 1, 2, 4)),
    # at a toy width (several times noisier than 6,144) it reads
    # 0.022-0.029 on the logits of both columns, 2.7e-4-5.1e-4 on the
    # log-sum-exps, 0.007-0.008 on the rows, 98% of the choices
    bf16={"seed": 2, "routes": 3, "tables": ((96,), (40,)),
          "limits": {"next_logit": 0.06, "lse": 1.5e-3, "rows": 0.03,
                     "mtp_next_logit": 0.06, "mtp_lse": 1.5e-3,
                     "chosen": 0.95}},
    own={"*": (1e-5, 1e-5)},
    refuse={"experts_first": 8, "experts_held": 16, "layers_held": 4,
            "vocab_held": 1024, "chips_per_layer": 8, "mtp_held": False},
    liar=_drop_mtp,
    config_file="k-exaone-236b-ep8",
    cut={"num_hidden_layers": 5, "num_experts": 16, "vocab_size": 19200},
    cell_share={"chips_per_layer": 8, "experts_first": 0},
    # reckoned by hand: 4,543.2 M from the matrices alone (4,543.19 M,
    # the routers among them); the norms' vectors and the routers'
    # biases add 0.10 M
    count={"parameters": 4_543_318_144, "vectors": 100_480,
           "matrices": (_K_ATTENTION + 3 * 6144 * 18432             # 452.98 M
                        + 4 * _K_SPARSE + 2 * 6144 * 19200          # 235.93 M
                        + 2 * 6144 * 6144 + _K_SPARSE),             # 831.26 M
           "bytes": 9_086_637_568},
    counted=lambda cfg: {"parameters": counts_kexaone.parameter_count(cfg)},
    attrs={"length_quantum": 256, "vocab_held": 19200,
           "share": (128, 0, 16), "mtp_held": True},
    # a whole period, four layers after the dense one, at least 8
    # experts, an eighth of the vocabulary
    floors=lambda cfg: cfg["layer_types"][1:5] == [
        "sliding_attention"] * 2 + ["full_attention", "sliding_attention"]
    and cfg["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    and cfg["num_experts"] >= 8 and cfg["vocab_size"] * 8 >= 153600)

# five held layers of eight (one of the leading dense layers, then four
# expert layers), 4 heads with 16 + 8 wide two-part keys and 24-wide
# values from latents of 24 and 16, YaRN stretching 8 original
# positions by 8 with a blend over all four rotary pairs (most of a
# route lies past the original positions), 32 experts in 8 routing
# groups of which a token keeps 4 and takes 4 experts at a routed
# scaling of 2.5, a share that holds experts 0-1 (half of group 0), a
# shared expert, a vocabulary slice of 128 and the prediction module
_G_ATTENTION = (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576
                + 512 * 64 * 320 + 12288 * 7168)                # 132.58 M
_G_SPARSE = _G_ATTENTION + 7168 * 256 + 17 * 3 * 7168 * 2048    # 883.10 M
GIGACHAT = Toy(
    name="RouteLMGigaChat", config=dict(
        first_k_dense_replace=3, hidden_size=64, intermediate_size=96,
        kv_lora_rank=16, moe_intermediate_size=32, n_group=8,
        n_routed_experts=2, n_shared_experts=1, norm_topk_prob=True,
        num_attention_heads=4, num_experts_per_tok=4, num_hidden_layers=5,
        num_nextn_predict_layers=1, q_lora_rank=24, qk_nope_head_dim=16,
        qk_rope_head_dim=8, rms_norm_eps=1e-6, rope_theta=100000,
        rope_scaling={"beta_fast": 32, "beta_slow": 0.0001, "factor": 8,
                      "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 8,
                      "rope_type": "yarn"},
        routed_scaling_factor=2.5, scoring_func="sigmoid", topk_group=4,
        topk_method="noaux_tc", v_head_dim=24, vocab_size=128,
        published={"num_hidden_layers": 8, "n_routed_experts": 32,
                   "vocab_size": 1024},
        share={"chips_per_layer": 16, "experts_first": 0},
        full_block=8, key_chunk=16),
    reference=gigachat_ref, share=(0, 2), outputs=OUTPUTS + MTP, seed=1,
    table=(0, (96, 33, 70)),
    blocks=dots3_ref.Blocks(q_block=32, head_group=2, row_block=48,
                            expert_cap=1, pad_to=96),
    laws=_gigachat_laws,
    # at a toy width (several times noisier than 7,168, and with two
    # held experts of 32 a flipped choice is a whole term) it reads
    # 0.012-0.019 on the logits of both columns, 3e-4-5e-4 on the
    # log-sum-exps, 0.008-0.012 on the rows, 99% of the choices
    bf16={"seed": 2, "routes": 3, "tables": ((96,), (40,)),
          "limits": {"next_logit": 0.06, "lse": 1.5e-3, "rows": 0.04,
                     "mtp_next_logit": 0.06, "mtp_lse": 1.5e-3,
                     "chosen": 0.95}},
    own={"*": (1e-5, 1e-5)},
    refuse={"experts_first": 2, "experts_held": 4, "layers_held": 4,
            "vocab_held": 1024, "chips_per_layer": 8, "mtp_held": False},
    liar=_drop_mtp,
    config_file="gigachat3.1-702b-ep16",
    cut={"num_hidden_layers": 5, "n_routed_experts": 16,
         "vocab_size": 16032},
    cell_share={"chips_per_layer": 16, "experts_first": 0},
    # reckoned by hand: 5,277.0 M from the matrices alone (5,277.03 M,
    # the routers among them); the norms' vectors and the routers'
    # biases add 0.12 M
    count={"parameters": 5_277_152_512,
           "matrices": (_G_ATTENTION + 3 * 7168 * 18432             # 528.94 M
                        + 4 * _G_SPARSE + 2 * 7168 * 16032          # 229.83 M
                        + 2 * 7168 * 7168 + _G_SPARSE),             # 985.86 M
           "vectors": 6 * (1536 + 512 + 2 * 7168) + 5 * 256 + 4 * 7168,
           "bytes": 10_554_307_584},
    counted=lambda cfg: {"parameters": counts_gigachat.parameter_count(cfg)},
    attrs={"length_quantum": 256, "vocab_held": 16032,
           "share": (256, 0, 16), "groups": (8, 4), "mtp_held": True},
    # four layers after the leading dense ones, at least 8 experts, an
    # eighth of the vocabulary; a share is half a group
    floors=lambda cfg: cfg["num_hidden_layers"] - 1 >= 4
    and cfg["n_routed_experts"] >= 8 and cfg["vocab_size"] * 8 >= 128256
    and cfg["n_routed_experts"] * 2 == 256 // cfg["n_group"])

# a run of three hybrid blocks out of six (the published blocks 2-4), a
# state-space mixer of 16 heads of 8 in two groups of a state of 16 (8
# heads a group: one tile of the kernel), a convolution of 4 taps,
# chunks of 8; 10 query heads over 2 key-value heads (5 a group); every
# multiplier at the published value
FALCON_H1 = Toy(
    name="RouteLMFalconH1", config=dict(
        attention_bias=False, attention_in_multiplier=1,
        attention_out_multiplier=0.0375,
        embedding_multiplier=5.656854249492381, head_dim=8, hidden_size=64,
        intermediate_size=96, key_multiplier=0.011048543456039804,
        lm_head_multiplier=0.0078125, mamba_chunk_size=8,
        mamba_conv_bias=True, mamba_d_conv=4, mamba_d_head=8,
        mamba_d_ssm=128, mamba_d_state=16, mamba_n_groups=2,
        mamba_n_heads=16, mamba_norm_before_gate=False,
        mamba_proj_bias=False, mamba_rms_norm=True, mlp_bias=False,
        mlp_multipliers=[0.1767766952966369, 0.011160714285714284],
        num_attention_heads=10, num_hidden_layers=3, num_key_value_heads=2,
        projectors_bias=False, rms_norm_eps=1e-05, rope_scaling=None,
        rope_theta=100000000000, ssm_in_multiplier=0.25,
        ssm_multipliers=[0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                         0.3535533905932738],
        ssm_out_multiplier=0.08838834764831845, tie_word_embeddings=False,
        vocab_size=112,
        published={"num_hidden_layers": 6, "vocab_size": 896},
        share={"layers_first": 2, "vocab_chips": 8},
        full_block=8, key_chunk=16),
    reference=falcon_h1_ref, share=None, outputs=OUTPUTS + ("state",),
    seed=0, table=(1, (13, 24, 40)), blocks=None,
    f32={"*": (0, 2e-5), "loglik": (2e-5, 0)},
    bf16={"seed": 4, "routes": 4, "tables": ((13, 24, 40),),
          "limits": {"next_logit": 0.05}},
    own={"*": (1e-5, 1e-5), "state": (1e-5, 1e-7)},
    own_seeds={"alone": 2, "in_a_table": 3}, own_exact=("in_a_table",),
    refuse={"layers_first": 0, "layers_held": 6, "vocab_held": 896,
            "vocab_chips": 1},
    liar=lambda m, p: (dataclasses.replace(m, layers_held=2), p),
    config_file="falcon-h1-34b-l0-7",
    cut={"num_hidden_layers": 8, "vocab_size": 32640},
    cell_share={"layers_first": 0, "vocab_chips": 8},
    # a block and its mixer, as counted by hand
    count={"parameters": 3_775_198_976, "matrices": 3_775_037_440,
           "bytes": 7_550_399_488,                           # 7.03 GiB
           ("layers", 0): 430_120_032, ("layers", 0, "ssm"): 68_351_072},
    counted=lambda cfg: {"parameters": counts_falcon.parameter_count(cfg)},
    attrs={"length_quantum": 256, "vocab_held": 32640,
           "layer_indices": list(range(8))})

TOYS = {t.name: t for t in (DOTS3, SALA, KEXAONE, GIGACHAT, FALCON_H1)}
