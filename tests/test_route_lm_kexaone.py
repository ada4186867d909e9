"""The third route-sequence language model against its plain reference
(``benchmark/reference/kexaone_ref.py``) at a toy size, both likelihood
columns: tightly in float32, within stated limits in bfloat16; a route's
outputs are its own and the module's column looks no further than t + 1;
the 8 shares of an expert layer at a routed scaling of 2.5 add up to the
uncut layer; the parameter count at the published widths; the artifact
round trip and its share gate."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _route_lm_kexaone_toy import CONFIG, SHARE, model, routes
from _route_lm_toy import highest
from benchmark.reference import kexaone_ref as ref
from benchmark.reference.dots3_ref import Blocks, gated_mlp, moe
from routest_tpu.core.dtypes import BF16_POLICY
from routest_tpu.parallel import expert

LENGTHS = [96, 33, 70]
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_FILE = os.path.join(REPO, "benchmark", "configs",
                           "k-exaone-236b-ep8.json")


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.fixture(scope="module")
def toy():
    m = model()
    params = jax.jit(m.init)(jax.random.PRNGKey(1))
    ids, lengths, rows_at = routes(0, LENGTHS)
    out = highest(jax.jit(m.apply))(params, ids, lengths, rows_at)
    blocks = Blocks(q_block=32, row_block=48, expert_cap=1, pad_to=96)
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
    """Five trunk blocks and the module's (n - 1 positions): three
    sliding layers see min(t + 1, 8) keys from t - 7 on, the full layer
    and the module every causal key; the chosen experts are the
    reference's."""
    _, _, (_, lengths, _), out, want = toy
    n, w = lengths[b], want[b]
    assert len(w["n_keys"]) == 6 and len(w["chosen"]) == 5
    for i in range(6):
        live = n if i < 5 else n - 1
        np.testing.assert_array_equal(out["n_keys"][i, b, :live],
                                      w["n_keys"][i])
        np.testing.assert_array_equal(out["first_key"][i, b, :live],
                                      w["first_key"][i])
        t = np.arange(live)
        sliding = i in (0, 1, 2, 4)
        np.testing.assert_array_equal(
            w["n_keys"][i], np.minimum(t + 1, 8) if sliding else t + 1)
        np.testing.assert_array_equal(
            w["first_key"][i], np.maximum(t - 7, 0) if sliding else 0 * t)
    for i in range(5):
        live = n if i < 4 else n - 1
        np.testing.assert_array_equal(
            np.sort(out["chosen"][i, b, :live], -1),
            np.sort(w["chosen"][i], -1))


@pytest.mark.parametrize("length", [96, 40])
def test_bfloat16_stays_within_stated_limits(length):
    """bfloat16 parameters and activations against the float32
    reference on the same (bfloat16-valued) weights: the gaps the cell
    compares, at a toy width (several times noisier than 6,144): it
    reads 0.022-0.029 on the logits of both columns, 2.7e-4-5.1e-4 on
    the log-sum-exps, 0.007-0.008 on the rows, 98% of the choices."""
    m = model(policy=BF16_POLICY)
    params = jax.jit(m.init)(jax.random.PRNGKey(2))
    ids, lengths, rows_at = routes(3, [length])
    out = jax.jit(m.apply)(params, ids, lengths, rows_at)
    want = ref.forward(params, CONFIG, ids[0], SHARE, list(rows_at[0]))
    assert out["lse"].dtype == out["mtp_lse"].dtype == jnp.float32
    assert rel(out["next_logit"][0], want["next_logit"]) < 0.06
    assert rel(out["lse"][0], want["lse"]) < 1.5e-3
    assert rel(out["rows"][0], want["rows"]) < 0.03
    assert rel(out["mtp_next_logit"][0, 0, :-1], want["mtp_next_logit"]) < 0.06
    assert rel(out["mtp_lse"][0, 0, :-1], want["mtp_lse"]) < 1.5e-3
    np.testing.assert_array_equal(out["n_keys"][:5, 0],
                                  np.stack(want["n_keys"][:5]))
    agree = [(np.asarray(out["chosen"][i, 0, :len(w)])[:, :, None]
              == w[:, None, :]).any(-1).mean()
             for i, w in enumerate(want["chosen"])]
    assert min(agree) > 0.95


def test_a_routes_outputs_are_its_own(toy):
    """Another padded length, another neighbour, another order: both
    columns, and the window layers' taps."""
    m, params, (ids, lengths, rows_at), out, _ = toy
    wide = np.zeros((2, 128), np.int32)
    wide[0, :70], wide[1, :96] = ids[2, :70], ids[0]
    wide[0, 70:] = 5                    # rubbish past the route's end
    again = highest(jax.jit(m.apply))(
        params, wide, np.asarray([70, 96], np.int32), rows_at[[2, 0]])
    for b, src in ((0, 2), (1, 0)):
        n = lengths[src]
        for what in ("next_logit", "lse"):
            np.testing.assert_allclose(again[what][b, :n], out[what][src, :n],
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(again["mtp_" + what][0, b, :n - 1],
                                       out["mtp_" + what][0, src, :n - 1],
                                       rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(again["mtp_loglik"][0, b],
                                   out["mtp_loglik"][0, src], rtol=1e-5)
        np.testing.assert_array_equal(again["n_keys"][:, b, :n - 1],
                                      out["n_keys"][:, src, :n - 1])
        np.testing.assert_array_equal(again["first_key"][:, b, :n - 1],
                                      out["first_key"][:, src, :n - 1])


def test_the_modules_column_looks_no_further_than_the_next_token(toy):
    """With every token past t + 1 = 41 changed, the first column's
    distribution stands up to t = 41 and the module's up to t = 40 (it
    has read ``id_41``); the module's at 41 has read ``id_42`` and
    moves."""
    m, params, (ids, lengths, rows_at), out, _ = toy
    changed = np.array(ids)
    changed[0, 42:] = (changed[0, 42:] + 7) % CONFIG["vocab_size"]
    again = highest(jax.jit(m.apply))(params, changed, lengths, rows_at)
    np.testing.assert_array_equal(again["lse"][0, :42], out["lse"][0, :42])
    np.testing.assert_array_equal(again["mtp_lse"][0, 0, :41],
                                  out["mtp_lse"][0, 0, :41])
    np.testing.assert_array_equal(again["mtp_next_logit"][0, 0, :40],
                                  out["mtp_next_logit"][0, 0, :40])
    assert again["mtp_lse"][0, 0, 41] != out["mtp_lse"][0, 0, 41]
    assert again["lse"][0, 42] != out["lse"][0, 42]


def test_the_held_layers_are_the_published_pattern():
    m = model()
    assert m.layer_kinds() == [
        ("sliding_attention", "dense"), ("sliding_attention", "sparse"),
        ("sliding_attention", "sparse"), ("full_attention", "sparse"),
        ("sliding_attention", "sparse")]
    assert m.block_kinds()[-1] == ("full_attention", "sparse")
    assert m.length_quantum == 8 and m.share == (16, 0, 8)
    assert m.step_attrs(96) == {"mixers": "full=xla,window=xla", "mtp": "1",
                                "experts": "xla"}
    bare = model(share={"chips_per_layer": 2, "experts_first": 0,
                        "mtp_held": False})
    assert len(bare.block_kinds()) == 5
    assert "mtp" not in jax.eval_shape(bare.init, jax.random.PRNGKey(0))
    assert set(bare.tap_tables(4, 96, 3)) == {"n_keys", "first_key",
                                              "chosen"}
    with pytest.raises(ValueError, match="whole groups"):
        model(num_key_value_heads=3)
    with pytest.raises(ValueError, match="scoring_func"):
        model(scoring_func="softmax")
    with pytest.raises(ValueError, match="prediction module"):
        model(num_nextn_predict_layers=2)


# ── the share ────────────────────────────────────────────────────────


def test_the_parts_of_the_eight_shares_add_up_to_the_uncut_layer():
    """Every share routes over all 16 experts at a routed scaling of 2.5
    and adds its own two experts' terms; the shared expert, which every
    chip computes alike, is counted once."""
    d, width, n_exp, top, scaling = 64, 32, 16, 4, 2.5
    ks = jax.random.split(jax.random.PRNGKey(0), 8)

    def mlp(k, lead=()):
        return {"w_gate": jax.random.normal(k[0], lead + (d, width)) / 8,
                "w_up": jax.random.normal(k[1], lead + (d, width)) / 8,
                "w_down": jax.random.normal(k[2], lead + (width, d)) / 6}

    p = dict(mlp(ks[:3], (n_exp,)), shared=mlp(ks[5:8]),
             router=jax.random.normal(ks[3], (d, n_exp)) / 8,
             bias=0.3 * jax.random.normal(ks[4], (n_exp,)))
    x = jax.random.normal(jax.random.PRNGKey(9), (50, d))
    whole, _, _ = moe(p, x, top, (0, n_exp), scaling)
    shared = gated_mlp(x, p["shared"])
    unscaled, _, _ = moe(p, x, top, (0, n_exp), 1.0)
    np.testing.assert_allclose(whole - shared, scaling * (unscaled - shared),
                               atol=5e-5)
    total = jnp.zeros_like(whole)
    for s in range(8):
        mine = dict(p, **{k: p[k][2 * s:2 * s + 2]
                          for k in ("w_gate", "w_up", "w_down")})
        y, taps = highest(jax.jit(lambda q, x, s=s: expert.moe_share(
            q, x, top, expert.ExpertShare(n_exp, 2 * s, 2), scaling)))(
                mine, x)
        want, _, _ = moe(mine, x, top, (2 * s, 2), scaling)
        np.testing.assert_allclose(y, want, atol=5e-5)
        total = total + (y - shared)
    np.testing.assert_allclose(total + shared, whole, atol=1e-4)


# ── the configuration ────────────────────────────────────────────────


def test_parameter_count_at_the_published_widths():
    """4,543.32 M: ISSUE 35 reckoned 4,543.2 M from the matrices alone
    (4,543.19 M, the routers among them); the norms' vectors and the
    routers' biases add 0.10 M."""
    from benchmark import counts_kexaone
    from routest_tpu.models.route_lm_kexaone import RouteLMKExaone

    with open(CONFIG_FILE) as f:
        cfg = json.load(f)
    m = RouteLMKExaone.from_config(cfg)
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(shapes)
    n = sum(int(np.prod(x.shape)) for x in leaves)
    vectors = sum(int(np.prod(x.shape)) for x in leaves if len(x.shape) == 1)
    assert n == 4_543_318_144 == counts_kexaone.parameter_count(cfg)
    attention = 2 * 6144 * 8192 + 2 * 6144 * 1024            # 113.25 M
    expert_m = 3 * 6144 * 2048                                # 37.75 M
    sparse = attention + 6144 * 128 + 17 * expert_m           # 755.76 M
    assert n - vectors == (attention + 3 * 6144 * 18432       # 452.98 M
                           + 4 * sparse + 2 * 6144 * 19200    # 235.93 M
                           + 2 * 6144 * 6144 + sparse)        # 831.26 M
    assert sparse == 755_761_152 and vectors == 100_480
    assert all(x.dtype in (jnp.bfloat16, jnp.float32) for x in leaves)
    assert m.length_quantum == 256 and m.vocab_held == 19200
    assert m.share == (128, 0, 16) and m.mtp_held


def test_the_configuration_keeps_every_published_key():
    with open(CONFIG_FILE) as f:
        cfg = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "K-EXAONE-236B-A23B"]
    assert cfg["source"] == row["source_url"]
    changed = {"num_hidden_layers": 5, "num_experts": 16,
               "vocab_size": 19200}
    for key, value in row["config"].items():
        assert cfg[key] == changed.get(key, value), key
    assert cfg["published"] == {k: row["config"][k] for k in changed}
    assert cfg["reduced"] == list(changed)
    assert cfg["share"] == {"chips_per_layer": 8, "experts_first": 0}
    assert cfg["assumed"] and cfg["deployment"] and cfg["not_built"]
    # the floors: a whole period, four layers after the dense one, at
    # least 8 experts, an eighth of the vocabulary
    assert cfg["layer_types"][1:5] == ["sliding_attention"] * 2 + [
        "full_attention", "sliding_attention"]
    assert cfg["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert cfg["num_experts"] >= 8 and cfg["vocab_size"] * 8 >= 153600


# ── the artifact ─────────────────────────────────────────────────────


@pytest.fixture(scope="module")
def saved(tmp_path_factory):
    from routest_tpu.train.checkpoint import save_route_lm

    m = model(policy=BF16_POLICY)
    params = jax.jit(m.init)(jax.random.PRNGKey(3))
    path = str(tmp_path_factory.mktemp("kexaone") / "route_lm.msgpack")
    save_route_lm(path, m, params)
    return m, params, path


def test_artifact_round_trip_returns_the_model_the_header_names(saved):
    from routest_tpu.models.route_lm_kexaone import RouteLMKExaone
    from routest_tpu.train.checkpoint import load_route_lm

    m, params, path = saved
    m2, p2 = load_route_lm(path, expect_share=m.share_header())
    assert isinstance(m2, RouteLMKExaone) and m2.policy == m.policy
    assert m2.share_header() == m.share_header()
    assert m2.share_header()["mtp_held"] is True
    assert dict(m2.sizes) == dict(m.sizes)
    for x, y in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(p2)):
        assert np.asarray(x).dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), y)
    ids, lengths, rows_at = routes(1, [64, 24])
    m2 = dataclasses.replace(m2, full_block=8, window_block=8, key_chunk=16,
                             window_rows=16)
    one = jax.jit(m.apply)(params, ids, lengths, rows_at)
    two = jax.jit(m2.apply)(p2, ids, lengths, rows_at)
    np.testing.assert_array_equal(one["lse"], two["lse"])
    np.testing.assert_array_equal(one["mtp_lse"], two["mtp_lse"])


@pytest.mark.parametrize("key,value", [
    ("experts_first", 8), ("experts_held", 16), ("layers_held", 4),
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
