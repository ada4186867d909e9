"""The fifth route-sequence model's own (``RouteLMFalconH1``) against
its plain float32 reference (``benchmark/reference/falcon_h1_ref.py``)
at a toy size on the CPU, seeded random weights: the scan's kernel
form; each multiplier where the published forward puts it; the shared
MLP traces the accepted models' programs unchanged at a multiplier of
1; what the model was not built for is refused. What every backbone
owes is in ``test_route_lm_contract.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _route_lm_toys import F32, TOYS, model, params, program, taps_equal
from routest_tpu.parallel import ssd

TOY = TOYS["RouteLMFalconH1"]
LENGTHS = TOY.table[1]


def _check(model, params, cfg):
    """The model on the toy's routes is the reference on ``cfg``, as
    the shared contract holds the float32 toy to it."""
    ids, lengths, rows_at = TOY.routes(*TOY.table)
    out = jax.jit(model.apply)(params, ids, lengths, rows_at)
    for r, n in enumerate(LENGTHS):
        want = TOY.forward(params, ids[r, :n], rows_at[r], config=cfg)
        for what in TOY.outputs:
            TOY.matches(out, want, r, n, what)
        taps_equal(out, want, r, ("n_keys", "first_key"))
    return out


@pytest.fixture(scope="module")
def f32():
    return (model("RouteLMFalconH1", F32),
            params("RouteLMFalconH1", F32, TOY.seed))


def test_the_kernel_form_is_the_reference(f32, monkeypatch):
    """The scan as the kernel (interpret mode), picked the way the chip
    picks it, inside the model."""
    m, params = f32
    assert m.ssm_steps() == "xla"
    real = ssd._scan_fused
    calls = []

    def interpret(*a, **kw):
        calls.append(1)
        return real(*a, **dict(kw, interpret=True))

    monkeypatch.setattr(ssd, "ssd_path", lambda *a, **kw: "fused")
    monkeypatch.setattr(ssd, "_scan_fused", interpret)
    assert m.ssm_steps() == "fused"
    assert m.step_attrs(40) == {"mixers": "ssm=fused,attn=xla"}
    out = _check(m, params, TOY.config)
    assert len(calls) == 3          # one kernel a block
    # the logits are of unit scale after the head's multiplier: neither
    # uniform nor one-hot
    assert 0.3 < float(jnp.std(out["next_logit"][2, :39])) < 3.0


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
    _, params = f32
    cfg = dict(TOY.config)
    if isinstance(cfg[key], list):
        cfg[key] = list(cfg[key])
        cfg[key][change] = 4.0 * cfg[key][change]
    else:
        cfg[key] = change
    out = _check(TOY.model(F32, **{key: cfg[key]}), params, cfg)
    base = program("RouteLMFalconH1", F32)(params,
                                           *TOY.routes(*TOY.table))
    moved = float(jnp.abs(out["next_logit"] - base["next_logit"]).max()
                  + jnp.abs(out["state"] - base["state"]).max())
    assert moved > 1e-5, key


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
        TOY.model(mamba_norm_before_gate=True)
    with pytest.raises(ValueError, match="whole groups"):
        TOY.model(num_key_value_heads=3)
    with pytest.raises(ValueError, match="published depth"):
        TOY.model(num_hidden_layers=5)
