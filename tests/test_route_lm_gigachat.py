"""The fourth route-sequence language model against its plain reference
(``benchmark/reference/gigachat_ref.py``) at a toy size, both likelihood
columns: tightly in float32, within stated limits in bfloat16; a route's
outputs are its own, bit for bit; group-limited routing against a numpy
oracle, ties and all; YaRN's frequencies and softmax scale against the
formulas' numbers; the 16 shares of an expert layer under 8 routing
groups add up to the uncut layer; the parameter count at the published
widths; the artifact round trip and its share gate."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _route_lm_gigachat_toy import CONFIG, SHARE, model, routes
from _route_lm_toy import highest
from benchmark.reference import gigachat_ref as ref
from benchmark.reference.dots3_ref import Blocks, gated_mlp
from routest_tpu.core.dtypes import BF16_POLICY
from routest_tpu.models import lm_common
from routest_tpu.parallel import expert

LENGTHS = [96, 33, 70]
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_FILE = os.path.join(REPO, "benchmark", "configs",
                           "gigachat3.1-702b-ep16.json")
PUBLISHED_YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                  "mscale_all_dim": 1,
                  "original_max_position_embeddings": 4096,
                  "rope_type": "yarn"}


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.fixture(scope="module")
def toy():
    m = model()
    params = jax.jit(m.init)(jax.random.PRNGKey(1))
    ids, lengths, rows_at = routes(0, LENGTHS)
    out = highest(jax.jit(m.apply))(params, ids, lengths, rows_at)
    blocks = Blocks(q_block=32, head_group=2, row_block=48, expert_cap=1,
                    pad_to=96)
    want = [ref.forward(params, CONFIG, ids[b, :n], SHARE, list(rows_at[b]),
                        blocks=blocks) for b, n in enumerate(lengths)]
    return m, params, (ids, lengths, rows_at), out, want


@pytest.mark.parametrize("b", range(len(LENGTHS)))
@pytest.mark.parametrize("what", ["next_logit", "lse", "rows", "loglik",
                                  "mtp_next_logit", "mtp_lse", "mtp_loglik"])
def test_whole_model_matches_the_reference_in_float32(toy, b, what):
    _, _, (_, lengths, _), out, want = toy
    n = lengths[b]
    got = {"rows": lambda: out["rows"][b], "loglik": lambda: out["loglik"][b],
           "mtp_loglik": lambda: out["mtp_loglik"][0, b],
           "mtp_next_logit": lambda: out[what][0, b, :n - 1],
           "mtp_lse": lambda: out[what][0, b, :n - 1]}.get(
               what, lambda: out[what][b, :n])()
    np.testing.assert_allclose(got, want[b][what], rtol=2e-6, atol=2e-5)


@pytest.mark.parametrize("b", range(len(LENGTHS)))
def test_taps_match_the_reference_block_by_block(toy, b):
    """Five trunk blocks and the module's (n - 1 positions), each seeing
    every causal key; the chosen experts are the reference's, and every
    token's lie in at most four of the eight routing groups."""
    _, _, (_, lengths, _), out, want = toy
    n, w = lengths[b], want[b]
    assert len(w["n_keys"]) == 6 and len(w["chosen"]) == 5
    for i in range(6):
        live = n if i < 5 else n - 1
        np.testing.assert_array_equal(out["n_keys"][i, b, :live],
                                      w["n_keys"][i])
        np.testing.assert_array_equal(out["first_key"][i, b, :live],
                                      w["first_key"][i])
        np.testing.assert_array_equal(w["n_keys"][i], np.arange(live) + 1)
        assert not w["first_key"][i].any()
    for i in range(5):
        live = n if i < 4 else n - 1
        got = np.asarray(out["chosen"][i, b, :live])
        np.testing.assert_array_equal(np.sort(got, -1),
                                      np.sort(w["chosen"][i], -1))
        groups = [len(set(row // 4)) for row in got]
        assert max(groups) <= 4 and min(groups) >= 1


@pytest.mark.parametrize("length", [96, 40])
def test_bfloat16_stays_within_stated_limits(length):
    """bfloat16 parameters and activations against the float32
    reference on the same (bfloat16-valued) weights: the gaps the cell
    compares, at a toy width (several times noisier than 7,168, and
    with two held experts of 32 a flipped choice is a whole term): it
    reads 0.012-0.019 on the logits of both columns, 3e-4-5e-4 on the
    log-sum-exps, 0.008-0.012 on the rows, 99% of the choices."""
    m = model(policy=BF16_POLICY)
    params = jax.jit(m.init)(jax.random.PRNGKey(2))
    ids, lengths, rows_at = routes(3, [length])
    out = jax.jit(m.apply)(params, ids, lengths, rows_at)
    want = ref.forward(params, CONFIG, ids[0], SHARE, list(rows_at[0]))
    assert out["lse"].dtype == out["mtp_lse"].dtype == jnp.float32
    assert rel(out["next_logit"][0], want["next_logit"]) < 0.06
    assert rel(out["lse"][0], want["lse"]) < 1.5e-3
    assert rel(out["rows"][0], want["rows"]) < 0.04
    assert rel(out["mtp_next_logit"][0, 0, :-1], want["mtp_next_logit"]) < 0.06
    assert rel(out["mtp_lse"][0, 0, :-1], want["mtp_lse"]) < 1.5e-3
    np.testing.assert_array_equal(out["n_keys"][:5, 0],
                                  np.stack(want["n_keys"][:5]))
    agree = [(np.asarray(out["chosen"][i, 0, :len(w)])[:, :, None]
              == w[:, None, :]).any(-1).mean()
             for i, w in enumerate(want["chosen"])]
    assert min(agree) > 0.95


def test_a_route_alone_equals_the_route_in_a_table_bit_for_bit(toy):
    """The same padded length, another neighbour, another order,
    rubbish past the route's end: both columns, the taps, every bit.
    (Another padded length is another chunking of the same softmax:
    ``test_seq_score_protocol`` holds that to 1e-5.)"""
    m, params, (ids, lengths, rows_at), out, _ = toy
    table = np.zeros((3, 96), np.int32)
    table[0, :70], table[2] = ids[2, :70], ids[0]
    table[0, 70:] = 5
    table[1, :50] = 7
    again = highest(jax.jit(m.apply))(
        params, table, np.asarray([70, 50, 96], np.int32),
        rows_at[[2, 1, 0]])
    alone = highest(jax.jit(m.apply))(
        params, ids[2:3], lengths[2:3], rows_at[2:3])
    for b, src, got in ((0, 2, again), (2, 0, again), (0, 2, alone)):
        n = lengths[src]
        for what in ("next_logit", "lse"):
            np.testing.assert_array_equal(got[what][b, :n],
                                          out[what][src, :n])
            np.testing.assert_array_equal(got["mtp_" + what][0, b, :n - 1],
                                          out["mtp_" + what][0, src, :n - 1])
        np.testing.assert_array_equal(got["rows"][b], out["rows"][src])
        np.testing.assert_array_equal(got["mtp_loglik"][0, b],
                                      out["mtp_loglik"][0, src])
        np.testing.assert_array_equal(got["chosen"][:, b, :n - 1],
                                      out["chosen"][:, src, :n - 1])
        np.testing.assert_array_equal(got["n_keys"][:, b, :n - 1],
                                      out["n_keys"][:, src, :n - 1])


def test_the_modules_column_looks_no_further_than_the_next_token(toy):
    m, params, (ids, lengths, rows_at), out, _ = toy
    changed = np.array(ids)
    changed[0, 42:] = (changed[0, 42:] + 7) % CONFIG["vocab_size"]
    again = highest(jax.jit(m.apply))(params, changed, lengths, rows_at)
    np.testing.assert_array_equal(again["lse"][0, :42], out["lse"][0, :42])
    np.testing.assert_array_equal(again["mtp_lse"][0, 0, :41],
                                  out["mtp_lse"][0, 0, :41])
    assert again["mtp_lse"][0, 0, 41] != out["mtp_lse"][0, 0, 41]
    assert again["lse"][0, 42] != out["lse"][0, 42]


def test_the_held_layers_and_what_the_scorer_is_told():
    m = model()
    assert m.layer_kinds() == ["dense"] + ["sparse"] * 4
    assert m.block_kinds()[-1] == "sparse" and len(m.block_kinds()) == 6
    assert m.length_quantum == 8 and m.share == (32, 0, 2)
    assert m.groups == (8, 4)
    assert m.step_attrs(96) == {"mixers": "latent=xla", "mtp": "1",
                                "experts": "xla", "groups": "8/4"}
    bare = model(share={"chips_per_layer": 16, "experts_first": 0,
                        "mtp_held": False})
    assert len(bare.block_kinds()) == 5
    assert "mtp" not in jax.eval_shape(bare.init, jax.random.PRNGKey(0))
    assert set(bare.tap_tables(4, 96, 3)) == {"n_keys", "first_key",
                                              "chosen"}
    assert model(first_k_dense_replace=0).layer_kinds() == ["sparse"] * 5
    with pytest.raises(ValueError, match="scoring_func"):
        model(scoring_func="softmax")
    with pytest.raises(ValueError, match="YaRN"):
        model(rope_scaling={"rope_type": "default"})
    with pytest.raises(ValueError, match="whole routing groups"):
        model(n_group=5)
    with pytest.raises(ValueError, match="prediction module"):
        model(num_nextn_predict_layers=2)


# ── YaRN ─────────────────────────────────────────────────────────────


def test_yarn_frequencies_and_scale_are_the_formulas_numbers():
    """At the published sizes (64 rotary dimensions, base 100,000, 4,096
    original positions x 64): pairs 0-8 keep their frequency, pairs
    19-31 turn 64 times slower, pairs 9-18 blend linearly; the softmax
    scale is 192^-0.5 times 1.41589^2."""
    got = lm_common.yarn_inv_freq(64, 100000, PUBLISHED_YARN)

    def cd(turns):
        return 64 * math.log(4096 / (2 * math.pi * turns)) / (
            2 * math.log(100000))

    assert (math.floor(cd(32)), math.ceil(cd(1))) == (8, 19)
    for i in range(32):
        f = 100000 ** (-2 * i / 64)
        ramp = min(max((i - 8) / 11, 0.0), 1.0)
        assert got[i] == pytest.approx(f * (1 - ramp) + f / 64 * ramp,
                                       rel=1e-12)
    np.testing.assert_allclose(got[:9], 100000 ** (-np.arange(9) / 32))
    np.testing.assert_allclose(got[19:],
                               100000 ** (-np.arange(19, 32) / 32) / 64)
    assert got[13] == pytest.approx(
        100000 ** (-13 / 32) * (6 / 11 + 5 / 11 / 64))
    np.testing.assert_allclose(got, ref.yarn(dict(
        qk_rope_head_dim=64, qk_nope_head_dim=128, rope_theta=100000,
        rope_scaling=PUBLISHED_YARN))[0], rtol=1e-6)
    m = 0.1 * math.log(64) + 1
    assert lm_common.yarn_mscale(64, 1) == pytest.approx(m) \
        == pytest.approx(1.41589, abs=1e-5)
    assert lm_common.yarn_mscale(1, 1) == 1.0
    with open(CONFIG_FILE) as f:
        from routest_tpu.models.route_lm_gigachat import RouteLMGigaChat
        real = RouteLMGigaChat.from_config(json.load(f))
    inv_freq, amplitude, scale = real.rotary()
    np.testing.assert_array_equal(inv_freq, got)
    assert amplitude == 1.0
    assert scale == pytest.approx(192 ** -0.5 * m * m) \
        == pytest.approx(0.1446796, abs=1e-6)


def test_where_the_two_mscales_differ_cos_and_sin_carry_their_ratio():
    """``mscale`` 0.25 against ``mscale_all_dim`` 1: the rotary parts are
    multiplied by ``m(0.25) / m(1)`` in the program and in the reference
    alike, and the first column moves by it."""
    scaling = dict(CONFIG["rope_scaling"], mscale=0.25)
    m = model(rope_scaling=scaling)
    ratio = m.rotary()[1]
    assert ratio == pytest.approx((0.025 * math.log(8) + 1)
                                  / (0.1 * math.log(8) + 1)) and ratio < 0.9
    params = jax.jit(m.init)(jax.random.PRNGKey(1))
    ids, lengths, rows_at = routes(5, [40])
    out = highest(jax.jit(m.apply))(params, ids, lengths, rows_at)
    want = ref.forward(params, dict(CONFIG, rope_scaling=scaling), ids[0],
                       SHARE, list(rows_at[0]))
    np.testing.assert_allclose(out["lse"][0], want["lse"], rtol=2e-6,
                               atol=2e-5)
    plain = highest(jax.jit(model().apply))(params, ids, lengths, rows_at)
    assert float(jnp.abs(plain["lse"] - out["lse"]).max()) > 1e-3


def test_rope_with_a_table_and_without():
    """The present callers compute what they computed: no table is the
    plain law, the plain law's table gives the same bits, and a position
    past the original length turns a stretched pair by the table's
    angle."""
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 3, 8))
    pos = jnp.asarray([0, 1, 7, 900, 5000])
    plain = lm_common.rope(x, pos, 10000.0)
    table = np.float32(10000.0) ** (-np.arange(4, dtype=np.float32) / 4)
    np.testing.assert_array_equal(
        plain, lm_common.rope(x, pos, 10000.0, inv_freq=table))
    slow = lm_common.rope(x, pos, 10000.0, inv_freq=table / 64)
    np.testing.assert_array_equal(slow[0], x[0])
    ang = 5000 * table[1] / 64
    np.testing.assert_allclose(
        slow[4, :, 1], x[4, :, 1] * np.cos(ang) - x[4, :, 5] * np.sin(ang),
        rtol=1e-5, atol=1e-6)


# ── group-limited routing ────────────────────────────────────────────


def oracle(prob, bias, n_group, topk_group, top_k, scaling):
    """Group-limited top-k one token at a time in numpy: stable sorts
    on the negated scores give ties to the lower index."""
    chosen, weights = [], []
    for p in np.asarray(prob, np.float32):
        c = p + bias
        groups = c.reshape(n_group, -1)
        score = np.sort(groups, -1)[:, -2:].sum(-1, dtype=np.float32)
        kept = np.argsort(-score, kind="stable")[:topk_group]
        masked = np.zeros_like(groups)
        masked[kept] = groups[kept]
        pick = np.argsort(-masked.reshape(-1), kind="stable")[:top_k]
        chosen.append(pick)
        weights.append(p[pick] / (p[pick].sum() + 1e-20) * scaling)
    return np.asarray(chosen), np.asarray(weights)


def routed(logits, bias, n_group, topk_group, top_k, scaling=2.5):
    """``route_top_k`` on tokens whose router logits ARE the input: a
    router of the identity."""
    n = logits.shape[1]
    return expert.route_top_k(
        jnp.asarray(logits, jnp.float32), jnp.eye(n, dtype=jnp.float32),
        jnp.asarray(bias), top_k, scaling, n_group=n_group,
        topk_group=topk_group)


def test_group_limited_routing_against_the_oracle_with_ties():
    """Random tokens; tokens with equal scores inside a group, across
    groups and at the cut; and a token whose eight best experts by score
    alone lie in five groups, so that the group cut changes its choice."""
    rng = np.random.default_rng(0)
    n_group, per, top_k = 8, 4, 8
    logits = rng.normal(size=(200, n_group * per)).astype(np.float32)
    logits[0] = 0.0                              # every score equal
    logits[1, :] = np.repeat(rng.normal(size=n_group), per)  # ties inside
    logits[2] = np.tile(rng.normal(size=per), n_group)   # ties across
    # token 3: the eight largest scores spread 2-2-2-1-1 over groups
    # 0-4; group 4's best two sum to less than group 3's, so it goes
    logits[3] = -4.0
    for at, v in ((0, 3.0), (1, 2.9), (4, 2.8), (5, 2.7), (8, 2.6),
                  (9, 2.5), (12, 2.4), (16, 2.3), (13, -1.0), (17, -3.0)):
        logits[3, at] = v
    bias = (0.01 * rng.normal(size=n_group * per)).astype(np.float32)
    bias[[5, 9]] = bias[4]
    prob = np.asarray(jax.nn.sigmoid(jnp.asarray(logits)))
    for keep in (4, 2, 8):
        chosen, weights = routed(logits, bias, n_group, keep, top_k)
        want_c, want_w = oracle(prob, bias, n_group, keep, top_k, 2.5)
        np.testing.assert_array_equal(chosen, want_c)
        np.testing.assert_allclose(weights, want_w, rtol=1e-6)
        assert all(len(set(row // per)) <= keep for row in want_c)
    # every score equal and no bias: the lower groups, the lower experts
    level, _ = routed(logits[:1], np.zeros_like(bias), n_group, 4, top_k)
    assert [int(e) for e in level[0]] == list(range(8))
    chosen, _ = routed(logits, bias, n_group, 4, top_k)
    by_score = set(np.argsort(-(prob[3] + bias), kind="stable")[:8])
    assert len({e // per for e in by_score}) == 5
    assert set(np.asarray(chosen[3])) != by_score
    assert {int(e) // per for e in chosen[3]} == {0, 1, 2, 3}
    # the reference's own router, written another way, agrees
    p = {"router": np.eye(n_group * per, dtype=np.float32), "bias": bias}
    cfg = dict(n_group=n_group, topk_group=4, num_experts_per_tok=top_k,
               routed_scaling_factor=2.5)
    ref_c, ref_w = highest(ref.route)(p, jnp.asarray(logits), cfg)
    np.testing.assert_array_equal(ref_c, chosen)


@pytest.mark.parametrize("scaling", [1.0, 2.5])
def test_one_group_is_todays_router_bit_for_bit(scaling):
    """``n_group`` 1 / ``topk_group`` 1, stated or left out, against the
    router as it stood before groups: same choices, same weights, and
    the same program."""
    def before(x, router, bias, top_k, scaling=1.0):
        prob = jax.nn.sigmoid(jnp.matmul(
            x, router, preferred_element_type=jnp.float32))
        _, chosen = jax.lax.top_k(prob + bias.astype(jnp.float32), top_k)
        picked = jnp.take_along_axis(prob, chosen, axis=-1)
        return chosen.astype(jnp.int32), (
            picked / picked.sum(-1, keepdims=True) * scaling)

    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.normal(ks[0], (64, 32), jnp.bfloat16)
    router = jax.random.normal(ks[1], (32, 16), jnp.bfloat16) / 6
    bias = 0.01 * jax.random.normal(ks[2], (16,))
    want = jax.jit(before, static_argnums=(3, 4))(x, router, bias, 4, scaling)
    for kw in ({}, {"n_group": 1, "topk_group": 1}):
        got = jax.jit(lambda *a: expert.route_top_k(*a, 4, scaling, **kw))(
            x, router, bias)
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])
    text = [jax.jit(lambda *a, f=f: f(*a, 4, scaling)).lower(
        x, router, bias).as_text() for f in (before, expert.route_top_k)]
    strip = lambda t: [line.split(" loc(")[0] for line in t.split("\n")  # noqa: E731
                       if "func.func" not in line and "module @" not in line]
    assert strip(text[0]) == strip(text[1])


# ── the share ────────────────────────────────────────────────────────


def test_the_parts_of_the_sixteen_shares_add_up_to_the_uncut_layer():
    """32 experts in 8 routing groups of 4 over 16 shares of 2: a share
    is half a group. Every share routes over all 32 (4 groups kept, top
    4 of those, scaled 2.5) and adds its own two experts' terms; the
    shared expert, which every chip computes alike, is counted once."""
    d, width, n_exp, top = 64, 32, 32, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 8)

    def mlp(k, lead=()):
        return {"w_gate": jax.random.normal(k[0], lead + (d, width)) / 8,
                "w_up": jax.random.normal(k[1], lead + (d, width)) / 8,
                "w_down": jax.random.normal(k[2], lead + (width, d)) / 6}

    p = dict(mlp(ks[:3], (n_exp,)), shared=mlp(ks[5:8]),
             router=jax.random.normal(ks[3], (d, n_exp)) / 8,
             bias=0.05 * jax.random.normal(ks[4], (n_exp,)))
    cfg = dict(n_group=8, topk_group=4, num_experts_per_tok=top,
               routed_scaling_factor=2.5)
    x = jax.random.normal(jax.random.PRNGKey(9), (50, d))
    whole, chosen, _ = highest(ref.moe)(p, x, cfg, (0, n_exp))
    assert all(len(set(np.asarray(row) // 4)) <= 4 for row in chosen)
    shared = highest(gated_mlp)(x, p["shared"])
    total, rows = jnp.zeros_like(whole), []
    for s in range(16):
        mine = dict(p, **{k: p[k][2 * s:2 * s + 2]
                          for k in ("w_gate", "w_up", "w_down")})
        y, taps = highest(jax.jit(lambda q, x, s=s: expert.moe_share(
            q, x, top, expert.ExpertShare(n_exp, 2 * s, 2), 2.5,
            groups=(8, 4))))(mine, x)
        want, _, _ = highest(ref.moe)(mine, x, cfg, (2 * s, 2))
        np.testing.assert_allclose(y, want, atol=5e-5)
        np.testing.assert_array_equal(np.sort(taps["chosen"], -1),
                                      np.sort(chosen, -1))
        total = total + (y - shared)
        rows.append(int(taps["counts"].sum()))
    np.testing.assert_allclose(total + shared, whole, atol=1e-4)
    assert sum(rows) == 50 * top
    # without the group cut the layer is another layer
    loose, _ = highest(jax.jit(lambda q, x: expert.moe_share(
        q, x, top, expert.ExpertShare(n_exp, 0, n_exp), 2.5)))(p, x)
    assert float(jnp.abs(loose - whole).max()) > 1e-2


# ── the configuration ────────────────────────────────────────────────


def test_parameter_count_at_the_published_widths():
    """5,277.15 M: ISSUE 39 reckoned 5,277.0 M from the matrices alone
    (5,277.03 M, the routers among them); the norms' vectors and the
    routers' biases add 0.12 M."""
    from benchmark import counts_gigachat
    from routest_tpu.models.route_lm_gigachat import RouteLMGigaChat

    with open(CONFIG_FILE) as f:
        cfg = json.load(f)
    m = RouteLMGigaChat.from_config(cfg)
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(shapes)
    n = sum(int(np.prod(x.shape)) for x in leaves)
    vectors = sum(int(np.prod(x.shape)) for x in leaves if len(x.shape) == 1)
    assert n == 5_277_152_512 == counts_gigachat.parameter_count(cfg)
    attention = (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576
                 + 512 * 64 * 320 + 12288 * 7168)              # 132.58 M
    expert_m = 3 * 7168 * 2048                                  # 44.04 M
    sparse = attention + 7168 * 256 + 17 * expert_m             # 883.10 M
    assert attention == 132_579_328 and sparse == 883_097_600
    assert n - vectors == (attention + 3 * 7168 * 18432         # 528.94 M
                           + 4 * sparse + 2 * 7168 * 16032      # 229.83 M
                           + 2 * 7168 * 7168 + sparse)          # 985.86 M
    assert round((n - vectors) / 1e6, 1) == 5277.0
    assert vectors == 6 * (1536 + 512 + 2 * 7168) + 5 * 256 + 4 * 7168
    assert all(x.dtype in (jnp.bfloat16, jnp.float32) for x in leaves)
    assert m.length_quantum == 256 and m.vocab_held == 16032
    assert m.share == (256, 0, 16) and m.groups == (8, 4) and m.mtp_held


def test_the_configuration_keeps_every_published_key():
    with open(CONFIG_FILE) as f:
        cfg = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "GigaChat3.1-702B-A36B"]
    assert cfg["source"] == row["source_url"]
    changed = {"num_hidden_layers": 5, "n_routed_experts": 16,
               "vocab_size": 16032}
    for key, value in row["config"].items():
        assert cfg[key] == changed.get(key, value), key
    assert cfg["published"] == {k: row["config"][k] for k in changed}
    assert cfg["reduced"] == list(changed)
    assert cfg["share"] == {"chips_per_layer": 16, "experts_first": 0}
    assert cfg["assumed"] and cfg["deployment"] and cfg["not_built"]
    assert cfg["guarantees"]
    # the floors: four layers after the leading dense ones, at least 8
    # experts, an eighth of the vocabulary; a share is half a group
    assert cfg["num_hidden_layers"] - 1 >= 4
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= 128256
    assert cfg["n_routed_experts"] * 2 == 256 // cfg["n_group"]


# ── the artifact ─────────────────────────────────────────────────────


@pytest.fixture(scope="module")
def saved(tmp_path_factory):
    from routest_tpu.train.checkpoint import save_route_lm

    m = model(policy=BF16_POLICY)
    params = jax.jit(m.init)(jax.random.PRNGKey(3))
    path = str(tmp_path_factory.mktemp("gigachat") / "route_lm.msgpack")
    save_route_lm(path, m, params)
    return m, params, path


def test_artifact_round_trip_returns_the_model_the_header_names(saved):
    from routest_tpu.models.route_lm_gigachat import RouteLMGigaChat
    from routest_tpu.train.checkpoint import load_route_lm

    m, params, path = saved
    m2, p2 = load_route_lm(path, expect_share=m.share_header())
    assert isinstance(m2, RouteLMGigaChat) and m2.policy == m.policy
    assert m2.share_header() == m.share_header()
    assert m2.share_header()["mtp_held"] is True
    assert dict(m2.sizes) == dict(m.sizes)
    for x, y in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(p2)):
        assert np.asarray(x).dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), y)
    ids, lengths, rows_at = routes(1, [64, 24])
    m2 = dataclasses.replace(m2, full_block=8, key_chunk=16)
    one = jax.jit(m.apply)(params, ids, lengths, rows_at)
    two = jax.jit(m2.apply)(p2, ids, lengths, rows_at)
    np.testing.assert_array_equal(one["lse"], two["lse"])
    np.testing.assert_array_equal(one["mtp_lse"], two["mtp_lse"])


@pytest.mark.parametrize("key,value", [
    ("experts_first", 2), ("experts_held", 4), ("layers_held", 4),
    ("vocab_held", 1024), ("chips_per_layer", 8), ("mtp_held", False)])
def test_artifact_of_another_share_is_refused(saved, key, value):
    from routest_tpu.train.checkpoint import load_route_lm

    with pytest.raises(ValueError, match=key):
        load_route_lm(saved[2], expect_share={key: value})


def test_artifact_whose_arrays_are_not_the_headers_share_is_refused(
        saved, tmp_path):
    """A header that says the module is held over arrays without one."""
    from routest_tpu.train.checkpoint import load_route_lm, save_route_lm

    m, params, _ = saved
    path = str(tmp_path / "liar.msgpack")
    save_route_lm(path, m, {k: v for k, v in params.items() if k != "mtp"})
    with pytest.raises(ValueError, match="not the share"):
        load_route_lm(path)
