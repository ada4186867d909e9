"""The second route-sequence language model against its plain reference
(``benchmark/reference/sala_ref.py``) at a toy size: tightly in float32,
within stated limits in bfloat16, below and above ``dense_len``; a
route's outputs are its own; the parameter count at the published
widths; the artifact round trip and its share gate."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _route_lm_sala_toy import CONFIG, F32, model, routes
from _route_lm_toy import highest
from benchmark.reference import sala_ref as ref
from routest_tpu.core.dtypes import BF16_POLICY

# two routes that select (dense_len is 48) and one that does not
LENGTHS = [96, 33, 70]
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.fixture(scope="module")
def toy():
    m = model()
    params = jax.jit(m.init)(jax.random.PRNGKey(1))
    ids, lengths, rows_at = routes(0, LENGTHS)
    out = highest(jax.jit(m.apply))(params, ids, lengths, rows_at)
    blocks = ref.Blocks(q_block=32, row_block=48, pad_to=96)
    want = [ref.forward(params, CONFIG, ids[b, :n], list(rows_at[b]),
                        blocks=blocks) for b, n in enumerate(lengths)]
    return m, params, (ids, lengths, rows_at), out, want


@pytest.mark.parametrize("b", range(len(LENGTHS)))
@pytest.mark.parametrize("what", ["next_logit", "lse", "rows", "loglik"])
def test_whole_model_matches_the_reference_in_float32(toy, b, what):
    _, _, (_, lengths, _), out, want = toy
    n = lengths[b]
    got = out[what][b] if what in ("rows", "loglik") else out[what][b, :n]
    np.testing.assert_allclose(got, want[b][what], rtol=2e-6, atol=2e-5)


@pytest.mark.parametrize("b", range(len(LENGTHS)))
def test_taps_match_the_reference_in_both_regimes(toy, b):
    _, _, (_, lengths, _), out, want = toy
    n, w = lengths[b], want[b]
    for i in range(2):                  # the two sparse layers
        np.testing.assert_array_equal(out["n_keys"][i, b, :n], w["n_keys"][i])
        np.testing.assert_array_equal(out["n_visible"][i, b, :n],
                                      w["n_visible"][i])
        blocks = np.asarray(out["blocks"][i, b])[..., :-(-n // 8)]
        np.testing.assert_array_equal(blocks, w["blocks"][i])
        last = w["n_keys"][i][-1]
        if n < 48:                      # dense: every causal key
            assert (last == n).all()
        else:                           # six blocks of eight, less the future
            assert (last < n).all() and (last > 40).all()
    for i in range(2):                  # the two linear layers
        assert rel(out["state"][i, b], w["state"][i]) < 2e-6


@pytest.mark.parametrize("length", [96, 40], ids=["selecting", "dense"])
def test_bfloat16_stays_within_stated_limits(length):
    """bfloat16 parameters and activations against the float32
    reference on the same (bfloat16-valued) weights: the gaps the cell
    compares, at a toy width (several times noisier than 4,096)."""
    m = model(policy=BF16_POLICY)
    params = jax.jit(m.init)(jax.random.PRNGKey(2))
    ids, lengths, rows_at = routes(3, [length])
    out = jax.jit(m.apply)(params, ids, lengths, rows_at)
    want = ref.forward(params, CONFIG, ids[0], list(rows_at[0]))
    assert out["lse"].dtype == jnp.float32
    assert rel(out["next_logit"][0], want["next_logit"]) < 0.05
    assert rel(out["lse"][0], want["lse"]) < 2e-3
    assert rel(out["rows"][0], want["rows"]) < 0.05
    for i in range(2):
        assert rel(out["state"][i, 0], want["state"][i]) < 0.03
        np.testing.assert_array_equal(out["n_keys"][i, 0], want["n_keys"][i])
        missed = (want["blocks"][i] & ~np.asarray(out["blocks"][i, 0])).sum()
        assert missed <= 0.1 * want["blocks"][i].sum()


def test_a_routes_outputs_are_its_own(toy):
    """Another padded length, another neighbour, another order."""
    m, params, (ids, lengths, rows_at), out, _ = toy
    wide = np.zeros((2, 128), np.int32)
    wide[0, :96], wide[1, :70] = ids[0], ids[2, :70]
    wide[1, 70:] = 5                    # rubbish past the route's end
    again = highest(jax.jit(m.apply))(
        params, wide, np.asarray([96, 70], np.int32), rows_at[[0, 2]])
    for b, src in ((0, 0), (1, 2)):
        n = lengths[src]
        for what in ("next_logit", "lse"):
            np.testing.assert_allclose(again[what][b, :n], out[what][src, :n],
                                       rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(again["n_keys"][:, b, :n],
                                      out["n_keys"][:, src, :n])
        np.testing.assert_allclose(again["state"][:, b],
                                   out["state"][:, src], rtol=1e-5,
                                   atol=1e-5)


def test_the_held_layers_are_the_published_run():
    m = model()
    assert m.layer_kinds() == [("minicpm4", 3), ("lightning-attn", 4),
                               ("lightning-attn", 5), ("minicpm4", 6)]
    assert m.length_quantum == 8
    with pytest.raises(ValueError, match="published depth"):
        model(share={"layers_first": 8, "chips_per_layer": 1})
    with pytest.raises(ValueError, match="key-value head"):
        model(lightning_nkv=2)
    with pytest.raises(ValueError, match="attn_use_rope"):
        model(attn_use_rope=True)     # a published flag this model lacks


def test_parameter_count_at_the_published_widths():
    """2,820.57 M: ISSUE 32 reckoned 2,820.5 M from the matrices alone
    (2,820.47 M); the norms' vectors add 0.1 M."""
    from benchmark import counts_sala
    from routest_tpu.models.route_lm_sala import RouteLMSala

    with open(os.path.join(REPO, "benchmark", "configs",
                           "minicpm-sala-l9-16.json")) as f:
        cfg = json.load(f)
    m = RouteLMSala.from_config(cfg)
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(shapes)
    n = sum(int(np.prod(x.shape)) for x in leaves)
    norms = sum(int(np.prod(x.shape)) for x in leaves if len(x.shape) == 1)
    assert n == 2_820_569_088 == counts_sala.parameter_count(cfg)
    assert n - norms == 2 * 253_755_392 + 6 * 285_212_672 + 601_686_016
    assert all(x.dtype == jnp.bfloat16 for x in leaves)
    assert [k for k, _ in m.layer_kinds()] == cfg["mixer_types"][9:17] == [
        "minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"]
    assert m.length_quantum == 256 and m.vocab_held == 73448


def test_the_configuration_keeps_every_published_key():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "minicpm-sala-l9-16.json")) as f:
        cfg = json.load(f)
    published = {
        "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 4096,
        "intermediate_size": 16384, "lightning_head_dim": 128,
        "lightning_nh": 32, "lightning_nkv": 32,
        "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
        "max_position_embeddings": 524288, "model_type": "minicpm_sala",
        "num_attention_heads": 32, "num_key_value_heads": 2, "qk_norm": True,
        "rand_init": False, "rms_norm_eps": 1e-06, "vocab_size": 73448,
        "rope_theta": 10000, "scale_emb": 12, "scale_depth": 1.4,
        "mup_denominator": 32, "dim_model_base": 256,
        "tie_word_embeddings": False, "use_output_gate": True,
        "use_output_norm": True, "attn_use_output_gate": True}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert len(cfg["mixer_types"]) == 32
    assert [i for i, k in enumerate(cfg["mixer_types"])
            if k == "minicpm4"] == [0, 9, 16, 17, 22, 29, 30, 31]
    assert cfg["num_hidden_layers"] == 8
    assert cfg["published"] == {"num_hidden_layers": 32}
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["share"] == {"layers_first": 9, "chips_per_layer": 1}
    assert cfg["assumed"] and cfg["deployment"]


# ── the artifact ─────────────────────────────────────────────────────


@pytest.fixture(scope="module")
def saved(tmp_path_factory):
    from routest_tpu.train.checkpoint import save_route_lm

    m = model(policy=BF16_POLICY)
    params = jax.jit(m.init)(jax.random.PRNGKey(3))
    path = str(tmp_path_factory.mktemp("sala") / "route_lm_sala.msgpack")
    save_route_lm(path, m, params)
    return m, params, path


def test_artifact_round_trip_returns_the_model_the_header_names(saved):
    from routest_tpu.models.route_lm_sala import RouteLMSala
    from routest_tpu.train.checkpoint import load_route_lm

    m, params, path = saved
    m2, p2 = load_route_lm(path, expect_share=m.share_header())
    assert isinstance(m2, RouteLMSala) and m2.policy == m.policy
    assert m2.share_header() == m.share_header()
    assert dict(m2.sizes) == dict(m.sizes)
    for x, y in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(p2)):
        assert np.asarray(x).dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), y)
    ids, lengths, rows_at = routes(1, [64, 24])
    m2 = dataclasses.replace(m2, q_block=8, key_chunk=16, scan_chunk=8)
    one = jax.jit(m.apply)(params, ids, lengths, rows_at)
    two = jax.jit(m2.apply)(p2, ids, lengths, rows_at)
    np.testing.assert_array_equal(one["lse"], two["lse"])


@pytest.mark.parametrize("key,value", [
    ("layers_first", 0), ("layers_held", 8), ("vocab_held", 1024),
    ("chips_per_layer", 4)])
def test_artifact_of_another_share_is_refused(saved, key, value):
    from routest_tpu.train.checkpoint import load_route_lm

    with pytest.raises(ValueError, match=key):
        load_route_lm(saved[2], expect_share={key: value})


def test_artifact_whose_layers_are_not_the_headers_run_is_refused(
        saved, tmp_path):
    """The same number of layers from another place of the pattern."""
    from routest_tpu.train.checkpoint import load_route_lm, save_route_lm

    m, params, _ = saved
    liar = dataclasses.replace(m, layers_first=4)     # linear, linear, ...
    path = str(tmp_path / "liar.msgpack")
    save_route_lm(path, liar, params)
    with pytest.raises(ValueError, match="not the share"):
        load_route_lm(path)


def test_an_artifact_of_the_first_model_still_loads_as_it(tmp_path):
    from _route_lm_toy import CONFIG as DOTS3
    from routest_tpu.models.route_lm import RouteLM
    from routest_tpu.train.checkpoint import load_route_lm, save_route_lm

    m = RouteLM.from_config(DOTS3)
    path = str(tmp_path / "route_lm.msgpack")
    save_route_lm(path, m, jax.jit(m.init)(jax.random.PRNGKey(0)))
    assert isinstance(load_route_lm(path)[0], RouteLM)
    with pytest.raises(ValueError, match="layers_first"):
        load_route_lm(path, expect_share={"layers_first": 9})
