"""The route-sequence language model's own blocks against its plain
reference (``benchmark/reference/dots3_ref.py``) at a toy size in
float32; an empty slot of a step scores nothing; the artifact → scorer
path and an artifact of another family. What every backbone owes is in
``test_route_lm_contract.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _route_lm_toys import F32, TOYS, highest, model, params, program
from benchmark.reference import dots3_ref as ref
from routest_tpu.core.dtypes import BF16_POLICY

TOY = TOYS["RouteLM"]
CONFIG, SHARE = TOY.config, TOY.share
LAYERS = ref.layer_kinds(CONFIG)


@pytest.fixture(scope="module")
def toy():
    return model("RouteLM", F32), params("RouteLM", F32, TOY.seed)


@pytest.mark.parametrize("layer", [1, 2], ids=["full", "sliding"])
def test_attention_block_matches_the_reference(toy, layer):
    m, params = toy
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
    m, params = toy
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


def test_an_empty_slot_of_a_step_scores_nothing(toy):
    m, params = toy
    run = highest(program("RouteLM", F32))
    ids, lengths, rows_at = TOY.routes(*TOY.table)
    out = run(params, ids, lengths, rows_at)
    lens = np.asarray([0, lengths[2]], np.int32)
    got = run(params, ids[[2, 2], :24], lens, rows_at[[2, 2]])
    assert float(got["loglik"][0]) == 0.0
    assert np.all(np.isfinite(np.asarray(got["lse"])))
    np.testing.assert_allclose(got["loglik"][1], out["loglik"][2], rtol=1e-5)


# ── the artifact ─────────────────────────────────────────────────────


@pytest.fixture(scope="module")
def bf16_toy(tmp_path_factory):
    from routest_tpu.train.checkpoint import save_route_lm

    m = model("RouteLM", BF16_POLICY)
    params_ = params("RouteLM", BF16_POLICY, 3)
    path = str(tmp_path_factory.mktemp("route_lm") / "route_lm.msgpack")
    save_route_lm(path, m, params_)
    return m, params_, path


def test_scorer_from_artifact_scores_as_the_model_it_was_saved_from(
        bf16_toy):
    """The normal path: artifact → ``RouteScorer.from_artifact`` → a
    pass; and the scorer hands the share gate on."""
    from routest_tpu.serve.seq_score import RouteScorer

    m, params, path = bf16_toy
    ids, lengths, rows_at = (jnp.asarray(a)
                             for a in TOY.routes(2, [24, 9, 40]))
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


def test_artifact_of_another_family_is_refused(bf16_toy, tmp_path):
    from routest_tpu.train.checkpoint import load_route_lm

    with pytest.raises(ValueError):
        load_route_lm(__file__.replace("tests/test_route_lm.py",
                                       "artifacts/eta_mlp.msgpack"))
