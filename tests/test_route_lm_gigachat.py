"""The fourth route-sequence language model's own: group-limited
routing against a numpy oracle, ties and all; YaRN's frequencies and
softmax scale against the formulas' numbers and its plain reference
(``benchmark/reference/gigachat_ref.py``); the 16 shares of an expert
layer under 8 routing groups add up to the uncut layer. What every
backbone owes, the prediction module's column among it, is in
``test_route_lm_contract.py``."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _route_lm_toys import (F32, TOYS, expert_layer, highest, model, params,
                            program)
from benchmark.reference import gigachat_ref as ref
from benchmark.reference.dots3_ref import gated_mlp
from routest_tpu.models import lm_common
from routest_tpu.parallel import expert

TOY = TOYS["RouteLMGigaChat"]
CONFIG = TOY.config
PUBLISHED_YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                  "mscale_all_dim": 1,
                  "original_max_position_embeddings": 4096,
                  "rope_type": "yarn"}


def test_the_held_layers_and_what_the_scorer_is_told():
    m = model("RouteLMGigaChat", F32)
    assert m.layer_kinds() == ["dense"] + ["sparse"] * 4
    assert m.block_kinds()[-1] == "sparse" and len(m.block_kinds()) == 6
    assert m.length_quantum == 8 and m.share == (32, 0, 2)
    assert m.groups == (8, 4)
    assert m.step_attrs(96) == {"mixers": "latent=xla", "mtp": "1",
                                "experts": "xla", "groups": "8/4"}
    assert TOY.model(first_k_dense_replace=0).layer_kinds() == ["sparse"] * 5
    with pytest.raises(ValueError, match="scoring_func"):
        TOY.model(scoring_func="softmax")
    with pytest.raises(ValueError, match="YaRN"):
        TOY.model(rope_scaling={"rope_type": "default"})
    with pytest.raises(ValueError, match="whole routing groups"):
        TOY.model(n_group=5)
    with pytest.raises(ValueError, match="prediction module"):
        TOY.model(num_nextn_predict_layers=2)


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
    real = type(model("RouteLMGigaChat", F32)).from_config(TOY.cell())
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
    m = TOY.model(rope_scaling=scaling)
    ratio = m.rotary()[1]
    assert ratio == pytest.approx((0.025 * math.log(8) + 1)
                                  / (0.1 * math.log(8) + 1)) and ratio < 0.9
    p = params("RouteLMGigaChat", F32, 1)  # its init is the toy's
    ids, lengths, rows_at = TOY.routes(5, [40])
    out = highest(jax.jit(m.apply))(p, ids, lengths, rows_at)
    want = TOY.forward(p, ids[0], rows_at[0],
                       config=dict(CONFIG, rope_scaling=scaling))
    np.testing.assert_allclose(out["lse"][0], want["lse"], rtol=2e-6,
                               atol=2e-5)
    plain = highest(program("RouteLMGigaChat", F32))(p, ids, lengths,
                                                     rows_at)
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
    d, n_exp, top = 64, 32, 4
    p = expert_layer(0, n_exp, bias=0.05)
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
