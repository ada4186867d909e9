"""The second route-sequence language model's own: the held layers are
the published run, and an artifact of the first model still loads as
it. What every backbone owes is in ``test_route_lm_contract.py``."""

import jax
import numpy as np
import pytest

from _route_lm_toys import F32, TOYS, model

TOY = TOYS["RouteLMSala"]


def test_the_held_layers_are_the_published_run():
    m = model("RouteLMSala", F32)
    assert m.layer_kinds() == [("minicpm4", 3), ("lightning-attn", 4),
                               ("lightning-attn", 5), ("minicpm4", 6)]
    assert m.length_quantum == 8
    with pytest.raises(ValueError, match="published depth"):
        TOY.model(share={"layers_first": 8, "chips_per_layer": 1})
    with pytest.raises(ValueError, match="key-value head"):
        TOY.model(lightning_nkv=2)
    with pytest.raises(ValueError, match="attn_use_rope"):
        TOY.model(attn_use_rope=True)     # a published flag this model lacks


def test_an_artifact_of_the_first_model_still_loads_as_it(tmp_path):
    """Its header, read back, names the first model for any weights:
    zeros of the bfloat16 toy's shapes."""
    from routest_tpu.core.dtypes import BF16_POLICY
    from routest_tpu.models.route_lm import RouteLM
    from routest_tpu.train.checkpoint import load_route_lm, save_route_lm

    m = model("RouteLM", BF16_POLICY)
    zeros = jax.tree_util.tree_map(
        lambda a: np.zeros(a.shape, a.dtype),
        jax.eval_shape(m.init, jax.random.PRNGKey(0)))
    path = str(tmp_path / "route_lm.msgpack")
    save_route_lm(path, m, zeros)
    assert isinstance(load_route_lm(path)[0], RouteLM)
    with pytest.raises(ValueError, match="layers_first"):
        load_route_lm(path, expect_share={"layers_first": 9})
