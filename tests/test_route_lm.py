"""The route-sequence language model against its plain reference
(``benchmark/reference/dots3_ref.py``) at a toy size in float32: every
block, the whole model, the taps; a route's outputs are its own,
whatever it is padded to and whatever else is scored beside it; the
artifact round trip and its share gate."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _route_lm_toy import CONFIG, SHARE, highest, model, routes
from benchmark.reference import dots3_ref as ref

LENGTHS = [96, 41, 17]
LAYERS = ref.layer_kinds(CONFIG)


@pytest.fixture(scope="module")
def toy():
    m = model()
    params = jax.jit(m.init)(jax.random.PRNGKey(0))
    ids, lengths, rows_at = routes(0, LENGTHS)
    out = highest(jax.jit(m.apply))(params, ids, lengths, rows_at)
    # one padded length, so that the reference's eager operations
    # compile once; its blocks are exercised on the way
    blocks = ref.Blocks(q_block=32, sel_block=16, head_group=2,
                        row_block=48, pad_to=96)
    want = [ref.forward(params, CONFIG, ids[b, :n], SHARE, list(rows_at[b]),
                        blocks=blocks) for b, n in enumerate(lengths)]
    return m, params, (ids, lengths, rows_at), out, want


@pytest.mark.parametrize("layer", [1, 2], ids=["full", "sliding"])
def test_attention_block_matches_the_reference(toy, layer):
    m, params, _, _, _ = toy
    kind = LAYERS[layer][0]
    p = params["layers"][layer]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(layer), (2, 48, 64))
    rows_at = jnp.asarray([[3, 20, 47], [0, 17, 30]], jnp.int32)
    y, taps = highest(jax.jit(lambda p, x, r: m.attention(
        layer, kind, p, x, r)))(p, x, rows_at)
    a = dict(ref.attention_sizes(CONFIG, kind), eps=CONFIG["rms_norm_eps"])
    for b in range(2):
        want, wt = ref.attention(p, a, x[b], jnp.arange(48),
                                 rows_at=rows_at[b])
        np.testing.assert_allclose(y[b], want, atol=2e-5)
        np.testing.assert_array_equal(taps["n_keys"][b], wt["n_keys"])
        np.testing.assert_array_equal(taps["first_key"][b], wt["first_key"])
        if "selected" in wt:
            np.testing.assert_array_equal(taps["selected"][b],
                                          wt["selected"])


@pytest.mark.parametrize("layer", [0, 1], ids=["dense", "moe"])
def test_ffn_block_matches_the_reference(toy, layer):
    m, params, _, _, _ = toy
    kind = LAYERS[layer][1]
    p = params["layers"][layer]["ffn"]
    x = jax.random.normal(jax.random.PRNGKey(7), (40, 64))
    y, taps = highest(jax.jit(lambda p, x: m.ffn(
        layer, kind, p, x, jnp.ones((40,), bool))))(p, x)
    if kind == "dense":
        want = ref.gated_mlp(x, p)
    else:
        want, chosen, _ = ref.moe(p, x, CONFIG["num_experts_per_tok"], SHARE)
        np.testing.assert_array_equal(np.sort(taps["chosen"], -1),
                                      np.sort(chosen, -1))
    np.testing.assert_allclose(y, want, atol=2e-5)


@pytest.mark.parametrize("b", range(len(LENGTHS)))
@pytest.mark.parametrize("what", ["next_logit", "lse", "rows", "loglik"])
def test_whole_model_matches_the_reference(toy, b, what):
    _, _, (_, lengths, _), out, want = toy
    n = lengths[b]
    got = out[what][b] if what in ("rows", "loglik") else out[what][b, :n]
    np.testing.assert_allclose(got, want[b][what], rtol=2e-6, atol=2e-5)


@pytest.mark.parametrize("b", range(len(LENGTHS)))
def test_taps_match_the_reference(toy, b):
    _, _, (_, lengths, _), out, want = toy
    n, w = lengths[b], want[b]
    for l in range(len(LAYERS)):
        np.testing.assert_array_equal(out["n_keys"][l, b, :n], w["n_keys"][l])
        np.testing.assert_array_equal(out["first_key"][l, b, :n],
                                      w["first_key"][l])
    for j in range(len(w["chosen"])):
        np.testing.assert_array_equal(np.sort(out["chosen"][j, b, :n], -1),
                                      np.sort(w["chosen"][j], -1))
    for j in range(len(w["selected"])):
        np.testing.assert_array_equal(out["selected"][j, b, :, :n],
                                      w["selected"][j])
    # a query of a selecting layer sees min(t + 1, index_topk) keys, a
    # windowed one min(t + 1, window)
    t = np.arange(n) + 1
    np.testing.assert_array_equal(out["n_keys"][1, b, :n],
                                  np.minimum(t, CONFIG["index_topk"]))
    np.testing.assert_array_equal(
        out["n_keys"][2, b, :n], np.minimum(t, CONFIG["sliding_window_size"]))


@pytest.mark.parametrize("how", ["padding", "length_class", "step_mates"])
def test_a_routes_outputs_are_its_own(toy, how):
    """Route 1 (41 arcs) alone at its tightest length against the same
    route in the batch of three padded to 96."""
    m, params, (ids, lengths, rows_at), out, _ = toy
    n = int(lengths[1])
    if how == "padding":           # garbage past its end, same class
        alone_ids = np.concatenate(
            [ids[1:2, :n], np.full((1, 96 - n), 5, np.int32)], 1)
    elif how == "length_class":    # the least class that holds it
        alone_ids = ids[1:2, :48]
    else:                          # other mates beside it
        alone_ids = np.stack([ids[1], ids[2], ids[2]])
    k = alone_ids.shape[0]
    lens = np.asarray([n, 17, 17][:k], np.int32)
    at = np.stack([rows_at[1], rows_at[2], rows_at[2]])[:k]
    alone = highest(jax.jit(m.apply))(params, alone_ids, lens, at)
    for what in ("next_logit", "lse"):
        np.testing.assert_allclose(alone[what][0, :n], out[what][1, :n],
                                   atol=2e-5)
    np.testing.assert_allclose(alone["rows"][0], out["rows"][1], atol=2e-5)
    np.testing.assert_allclose(alone["loglik"][0], out["loglik"][1],
                               rtol=1e-5)


def test_an_empty_slot_of_a_step_scores_nothing(toy):
    m, params, (ids, lengths, rows_at), out, _ = toy
    lens = np.asarray([0, lengths[2]], np.int32)
    got = highest(jax.jit(m.apply))(params, ids[[2, 2], :24], lens,
                                    rows_at[[2, 2]])
    assert float(got["loglik"][0]) == 0.0
    assert np.all(np.isfinite(np.asarray(got["lse"])))
    np.testing.assert_allclose(got["loglik"][1], out["loglik"][2], rtol=1e-5)


def test_the_models_counts_are_the_configurations():
    import json
    import os

    from benchmark import counts_seq

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "dots3-note-prev-ep8.json")
    with open(path) as f:
        cfg = json.load(f)
    from routest_tpu.models.route_lm import RouteLM

    m = RouteLM.from_config(cfg)
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(shapes)
    n = sum(int(np.prod(x.shape)) for x in leaves)
    norms = sum(int(np.prod(x.shape)) for x in leaves if len(x.shape) == 1)
    assert abs(n - 4_087e6) < 1e6          # ISSUE 27's count, by hand
    assert all(x.dtype == jnp.bfloat16 for x in leaves if len(x.shape) > 1)
    assert counts_seq.weight_bytes(cfg) == 2 * (n - norms)
    assert m.share.n_experts == 256 and m.experts_held == 32
    assert [a for a, _ in m.layer_kinds()] == cfg["layer_types"][:5]


# ── the artifact ─────────────────────────────────────────────────────


@pytest.fixture(scope="module")
def bf16_toy(tmp_path_factory):
    from routest_tpu.models.route_lm import RouteLM
    from routest_tpu.train.checkpoint import save_route_lm

    m = RouteLM.from_config(CONFIG)          # the bfloat16 policy
    params = jax.jit(m.init)(jax.random.PRNGKey(3))
    path = str(tmp_path_factory.mktemp("route_lm") / "route_lm.msgpack")
    save_route_lm(path, m, params)
    return m, params, path


def test_artifact_round_trip(bf16_toy):
    from routest_tpu.train.checkpoint import load_route_lm

    m, params, path = bf16_toy
    m2, p2 = load_route_lm(path, expect_share=m.share_header())
    assert m2.share == m.share and m2.policy == m.policy
    assert dict(m2.sizes) == dict(m.sizes)
    a, b = jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(p2)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert np.asarray(x).dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), y)
    ids, lengths, rows_at = routes(1, [24, 9])
    one = jax.jit(m.apply)(params, ids, lengths, rows_at)
    two = jax.jit(m2.apply)(p2, ids, lengths, rows_at)
    np.testing.assert_array_equal(one["lse"], two["lse"])


def test_scorer_from_artifact_scores_as_the_model_it_was_saved_from(
        bf16_toy):
    """The normal path: artifact → ``RouteScorer.from_artifact`` → a
    pass; and the scorer hands the share gate on."""
    from routest_tpu.serve.seq_score import RouteScorer

    m, params, path = bf16_toy
    ids, lengths, rows_at = (jnp.asarray(a) for a in routes(2, [24, 9, 40]))
    got = RouteScorer.from_artifact(
        path, expect_share=m.share_header()).score(ids, lengths, rows_at)
    # the artifact carries the model, not the toy's block sizes: the
    # loaded scorer runs the default blocks, so bfloat16 rounds elsewhere
    want = RouteScorer(m, params).score(ids, lengths, rows_at)
    np.testing.assert_allclose(got.loglik, want.loglik, rtol=5e-3)
    real = np.arange(ids.shape[1])[None] < np.asarray(lengths)[:, None]
    np.testing.assert_allclose(np.where(real, got.lse, 0.0),
                               np.where(real, want.lse, 0.0), rtol=5e-3)
    with pytest.raises(ValueError, match="experts_first"):
        RouteScorer.from_artifact(path, expect_share={"experts_first": 8})


@pytest.mark.parametrize("key,value", [
    ("experts_held", 16), ("experts_first", 8), ("vocab_held", 1024),
    ("layers_held", 10), ("chips_per_layer", 4)])
def test_artifact_of_another_share_is_refused(bf16_toy, key, value):
    from routest_tpu.train.checkpoint import load_route_lm

    with pytest.raises(ValueError, match=key):
        load_route_lm(bf16_toy[2], expect_share={key: value})


def test_artifact_whose_arrays_are_not_its_headers_share_is_refused(
        bf16_toy, tmp_path):
    from routest_tpu.train.checkpoint import load_route_lm, save_route_lm

    m, params, _ = bf16_toy
    liar = dataclasses.replace(m, experts_held=4)
    path = str(tmp_path / "liar.msgpack")
    save_route_lm(path, liar, params)
    with pytest.raises(ValueError, match="not the share"):
        load_route_lm(path)


def test_artifact_of_another_family_is_refused(bf16_toy, tmp_path):
    from routest_tpu.train.checkpoint import load_route_lm

    with pytest.raises(ValueError):
        load_route_lm(__file__.replace("tests/test_route_lm.py",
                                       "artifacts/eta_mlp.msgpack"))
