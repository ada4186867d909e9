"""The third route-sequence language model's own: the held layers are
the published pattern; the 8 shares of an expert layer at a routed
scaling of 2.5 add up to the uncut layer. What every backbone owes,
the prediction module's column among it, is in
``test_route_lm_contract.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _route_lm_toys import F32, TOYS, expert_layer, highest, model
from benchmark.reference.dots3_ref import gated_mlp, moe
from routest_tpu.parallel import expert

TOY = TOYS["RouteLMKExaone"]


def test_the_held_layers_are_the_published_pattern():
    m = model("RouteLMKExaone", F32)
    assert m.layer_kinds() == [
        ("sliding_attention", "dense"), ("sliding_attention", "sparse"),
        ("sliding_attention", "sparse"), ("full_attention", "sparse"),
        ("sliding_attention", "sparse")]
    assert m.block_kinds()[-1] == ("full_attention", "sparse")
    assert m.length_quantum == 8 and m.share == (16, 0, 8)
    assert m.step_attrs(96) == {"mixers": "full=xla,window=xla", "mtp": "1",
                                "experts": "xla"}
    with pytest.raises(ValueError, match="whole groups"):
        TOY.model(num_key_value_heads=3)
    with pytest.raises(ValueError, match="scoring_func"):
        TOY.model(scoring_func="softmax")
    with pytest.raises(ValueError, match="prediction module"):
        TOY.model(num_nextn_predict_layers=2)


# ── the share ────────────────────────────────────────────────────────


def test_the_parts_of_the_eight_shares_add_up_to_the_uncut_layer():
    """Every share routes over all 16 experts at a routed scaling of 2.5
    and adds its own two experts' terms; the shared expert, which every
    chip computes alike, is counted once."""
    d, n_exp, top, scaling = 64, 16, 4, 2.5
    p = expert_layer(0, n_exp)
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
