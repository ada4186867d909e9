"""The fifth route-sequence model (``RouteLMFalconH1``) against its plain
float32 reference (``benchmark/reference/falcon_h1_ref.py``) at a toy
size on the CPU, seeded random weights: in both forms of the scan; each
multiplier where the published forward puts it; a route alone equals
the route in a table; the state stops at a route's last real token; the
parameter count at the published widths; the artifact's round trip; and
the shared MLP traces the accepted models' programs unchanged at a
multiplier of 1."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _route_lm_falcon_h1_toy as toy
from benchmark.reference import falcon_h1_ref
from routest_tpu.models import route_lm_falcon_h1
from routest_tpu.parallel import ssd

LENGTHS = [13, 24, 40]
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _apply(model, params, ids, lengths, rows_at):
    return jax.jit(model.apply)(params, jnp.asarray(ids),
                                jnp.asarray(lengths), jnp.asarray(rows_at))


def _gaps(out, want, r, n):
    """Largest absolute differences of one route's outputs; the
    log-likelihood's and the states' relative."""
    return {"next_logit": np.abs(np.asarray(out["next_logit"][r, :n])
                                 - want["next_logit"]).max(),
            "lse": np.abs(np.asarray(out["lse"][r, :n]) - want["lse"]).max(),
            "rows": np.abs(np.asarray(out["rows"][r]) - want["rows"]).max(),
            "loglik": abs(float(out["loglik"][r]) - want["loglik"])
            / abs(want["loglik"]),
            "state": max(np.abs(np.asarray(out["state"][l, r]) - s).max()
                         / max(np.abs(s).max(), 1e-12)
                         for l, s in enumerate(want["state"]))}


def _check(model, params, cfg, seed=1, tol=2e-5):
    ids, lengths, rows_at = toy.routes(seed, LENGTHS)
    out = _apply(model, params, ids, lengths, rows_at)
    for r, n in enumerate(LENGTHS):
        want = falcon_h1_ref.forward(params, cfg, ids[r, :n],
                                     list(rows_at[r]))
        gaps = _gaps(out, want, r, n)
        assert max(gaps.values()) < tol, gaps
        for l in range(len(want["n_keys"])):
            np.testing.assert_array_equal(out["n_keys"][l, r, :n],
                                          want["n_keys"][l])
            np.testing.assert_array_equal(out["first_key"][l, r, :n],
                                          want["first_key"][l])
    return out


@pytest.fixture(scope="module")
def f32():
    m = toy.model()
    return m, jax.jit(m.init)(jax.random.PRNGKey(0))


def test_the_model_is_the_reference(f32):
    m, params = f32
    assert m.ssm_steps() == "xla"
    out = _check(m, params, toy.CONFIG)
    # the logits are of unit scale after the head's multiplier: neither
    # uniform nor one-hot
    assert 0.3 < float(jnp.std(out["next_logit"][2, :39])) < 3.0


def test_the_kernel_form_is_the_reference(f32, monkeypatch):
    """The scan as the kernel (interpret mode), picked the way the chip
    picks it, inside the model."""
    m, params = f32
    real = ssd._scan_fused
    calls = []

    def interpret(*a, **kw):
        calls.append(1)
        return real(*a, **dict(kw, interpret=True))

    monkeypatch.setattr(ssd, "ssd_path", lambda *a, **kw: "fused")
    monkeypatch.setattr(ssd, "_scan_fused", interpret)
    assert m.ssm_steps() == "fused"
    assert m.step_attrs(40) == {"mixers": "ssm=fused,attn=xla"}
    _check(m, params, toy.CONFIG)
    assert len(calls) == 3          # one kernel a block


# every published multiplier, one at a time, moved off its value: the
# model and the reference still agree, and the outputs move
MULTIPLIERS = [
    ("attention_in_multiplier", 0.5), ("attention_out_multiplier", 0.5),
    ("embedding_multiplier", 2.0), ("key_multiplier", 0.03),
    ("lm_head_multiplier", 0.015), ("ssm_in_multiplier", 0.7),
    ("ssm_out_multiplier", 0.3), ("mlp_multipliers", 0), ("mlp_multipliers", 1),
    ("ssm_multipliers", 0), ("ssm_multipliers", 1), ("ssm_multipliers", 2),
    ("ssm_multipliers", 3), ("ssm_multipliers", 4)]


@pytest.mark.parametrize("key,change", MULTIPLIERS)
def test_each_multiplier_is_where_the_published_forward_puts_it(
        f32, key, change):
    m0, params = f32
    cfg = dict(toy.CONFIG)
    if isinstance(cfg[key], list):
        cfg[key] = list(cfg[key])
        cfg[key][change] = 4.0 * cfg[key][change]
    else:
        cfg[key] = change
    m = route_lm_falcon_h1.RouteLMFalconH1.from_config(cfg, policy=toy.F32)
    out = _check(m, params, cfg)
    ids, lengths, rows_at = toy.routes(1, LENGTHS)
    base = _apply(m0, params, ids, lengths, rows_at)
    moved = float(jnp.abs(out["next_logit"] - base["next_logit"]).max()
                  + jnp.abs(out["state"] - base["state"]).max())
    assert moved > 1e-5, key


def test_a_route_alone_is_the_route_in_a_table(f32):
    m, params = f32
    ids, lengths, rows_at = toy.routes(2, LENGTHS)
    table = _apply(m, params, ids, lengths, rows_at)
    for r, n in enumerate(LENGTHS):
        padded = -(-n // m.length_quantum) * m.length_quantum
        alone = _apply(m, params, np.pad(ids[r:r + 1, :n],
                                         ((0, 0), (0, padded - n))),
                       lengths[r:r + 1], rows_at[r:r + 1])
        for k in ("next_logit", "lse"):
            np.testing.assert_allclose(table[k][r, :n], alone[k][0, :n],
                                       rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(table["state"][:, r], alone["state"][:, 0],
                                   rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(table["rows"][r], alone["rows"][0],
                                   rtol=1e-5, atol=1e-5)


def test_padding_after_a_route_moves_nothing_of_it(f32):
    """Other tokens after a route's end (another route's, or whatever a
    padded table holds) change neither its outputs nor its states."""
    m, params = f32
    ids, lengths, rows_at = toy.routes(3, LENGTHS)
    noisy = ids.copy()
    for r, n in enumerate(LENGTHS):
        noisy[r, n:] = (np.arange(ids.shape[1] - n) * 7 + r) % 112
    a = _apply(m, params, ids, lengths, rows_at)
    b = _apply(m, params, noisy, lengths, rows_at)
    for r, n in enumerate(LENGTHS):
        np.testing.assert_array_equal(a["lse"][r, :n], b["lse"][r, :n])
        np.testing.assert_array_equal(a["state"][:, r], b["state"][:, r])


def test_bfloat16_is_near_the_reference():
    m = toy.model(policy=route_lm_falcon_h1.BF16_POLICY)
    params = jax.jit(m.init)(jax.random.PRNGKey(4))
    ids, lengths, rows_at = toy.routes(4, LENGTHS)
    out = _apply(m, params, ids, lengths, rows_at)
    for r, n in enumerate(LENGTHS):
        want = falcon_h1_ref.forward(params, toy.CONFIG, ids[r, :n],
                                     list(rows_at[r]))
        got = np.asarray(out["next_logit"][r, :n], np.float64)
        gap = np.linalg.norm(got - want["next_logit"]) / np.linalg.norm(
            want["next_logit"])
        assert gap < 0.05, gap


def _cell_config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "falcon-h1-34b-l0-7.json")) as f:
        return json.load(f)


def test_the_cells_parameters_at_the_published_widths():
    cfg = _cell_config()
    m = route_lm_falcon_h1.RouteLMFalconH1.from_config(cfg)
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(shapes)
    count = sum(int(np.prod(x.shape)) for x in leaves)
    assert count == cfg["parameters_held"] == 3_775_198_976   # 3,775.20 M
    # per block, as the issue's table counts it
    block = sum(int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(shapes["layers"][0]))
    assert block == 430_120_032
    ssm = sum(int(np.prod(x.shape))
              for x in jax.tree_util.tree_leaves(shapes["layers"][0]["ssm"]))
    assert ssm == 68_351_072
    nbytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in leaves)
    assert abs(nbytes / 2 ** 30 - 7.03) < 0.01
    assert m.layer_indices() == list(range(8)) and m.vocab_held == 32640
    assert m.sizes["num_hidden_layers"] == 72
    assert m.sizes["vocab_size"] == 261120
    assert m.length_quantum == 256


def test_the_artifact_round_trip(f32, tmp_path):
    from routest_tpu.serve.seq_score import RouteScorer
    from routest_tpu.train.checkpoint import load_route_lm, save_route_lm

    m, params = f32
    path = str(tmp_path / "falcon.msgpack")
    save_route_lm(path, m, params)
    m2, p2 = load_route_lm(path, expect_share={"layers_first": 2,
                                               "vocab_chips": 8})
    assert type(m2) is route_lm_falcon_h1.RouteLMFalconH1
    assert m2.share_header() == m.share_header() and m2.holds(p2)
    with pytest.raises(ValueError):
        load_route_lm(path, expect_share={"layers_first": 0})
    ids, lengths, rows_at = (jnp.asarray(a) for a in toy.routes(5, LENGTHS))
    a = RouteScorer(m, params, max_step_tokens=64).score(ids, lengths,
                                                         rows_at)
    b = RouteScorer.from_artifact(path, max_step_tokens=64).score(
        ids, lengths, rows_at)
    np.testing.assert_array_equal(a.loglik, b.loglik)


@pytest.mark.parametrize("gate_mult,down_mult", [(1.0, 1.0)])
def test_the_shared_mlp_is_unchanged_at_a_multiplier_of_one(gate_mult,
                                                            down_mult):
    """The accepted models call ``gated_mlp`` with no multiplier: their
    programs trace to the same operations as before the two arguments
    came (the jaxpr of the bare call has no multiply of the gate or of
    the output); the fifth model's multipliers appear only where they
    are not 1."""
    from routest_tpu.parallel.expert import gated_mlp

    x = jnp.ones((4, 8), jnp.bfloat16)
    w1, w2, w3 = (jnp.ones(s, jnp.bfloat16) for s in ((8, 16), (8, 16),
                                                     (16, 8)))
    bare = str(jax.make_jaxpr(gated_mlp)(x, w1, w2, w3))
    ones = str(jax.make_jaxpr(lambda *a: gated_mlp(
        *a, gate_mult=gate_mult, down_mult=down_mult))(x, w1, w2, w3))
    assert bare == ones
    assert bare.count("mul") == str(jax.make_jaxpr(
        lambda x, a, b, c: jnp.matmul((jax.nn.silu(jnp.matmul(
            x, a, preferred_element_type=jnp.float32)) * jnp.matmul(
            x, b, preferred_element_type=jnp.float32)).astype(x.dtype), c,
            preferred_element_type=jnp.float32))(x, w1, w2, w3)).count("mul")
    scaled = str(jax.make_jaxpr(lambda *a: gated_mlp(
        *a, gate_mult=0.5, down_mult=0.25))(x, w1, w2, w3))
    assert scaled.count("mul") == bare.count("mul") + 2


def test_the_model_refuses_what_it_was_not_built_for():
    with pytest.raises(ValueError, match="built for"):
        toy.model(mamba_norm_before_gate=True)
    with pytest.raises(ValueError, match="whole groups"):
        toy.model(num_key_value_heads=3)
    with pytest.raises(ValueError, match="published depth"):
        toy.model(num_hidden_layers=5)
