"""Parity: the fused Pallas ETA kernel vs the XLA reference path.

Runs the kernel in Pallas interpreter mode on the CPU backend (compiled
mode needs a TPU); ``EtaMLP.apply`` is the semantics oracle. Covers the
ABI edge cases the kernel re-implements (unknown-category all-zero
one-hots, negative-distance clamping, the normalizer, non-tile batch
sizes) through the row-major wrapper and the feature-major core, and
what chooses the kernel: ``eta_path``, with training and export held
to the XLA body.
"""

import jax
import numpy as np
import pytest

from routest_tpu.core.dtypes import DEFAULT_POLICY, F32_POLICY
from routest_tpu.data.features import batch_from_mapping, encode_requests
from routest_tpu.data.synthetic import generate_dataset
from routest_tpu.models.eta_mlp import (FUSED_MIN_ROWS, EtaMLP, eta_path,
                                        fit_normalizer)
from routest_tpu.ops.fused_mlp import TILE
from routest_tpu.ops import (fused_eta_forward, fused_eta_forward_t,
                             pack_eta_params)


def _model_and_params(policy=F32_POLICY, hidden=(256, 256, 128), seed=0):
    model = EtaMLP(hidden=hidden, policy=policy)
    data = generate_dataset(2048, seed=seed)
    feats = batch_from_mapping(data)
    mean, std = fit_normalizer(feats)
    params = model.init(jax.random.PRNGKey(seed), norm_mean=mean, norm_std=std)
    return model, params, feats


ENTRIES = pytest.mark.parametrize("entry", ["wrapper", "core"])


def _fused(entry, packed, x, n_q=0, **kw):
    """The kernel through its row-major wrapper, or through the
    feature-major core the table scorer calls ((12, B) in, (n_q | 1, B)
    out), in the interpreter."""
    if entry == "wrapper":
        return np.asarray(fused_eta_forward(packed, x, n_q=n_q,
                                            interpret=True, **kw))
    out = np.asarray(fused_eta_forward_t(
        packed, jax.numpy.asarray(x).T, n_q, kw.get("tile", 512), True)).T
    return out if n_q else out[:, 0]


@ENTRIES
def test_fused_matches_apply_f32(entry):
    model, params, feats = _model_and_params()
    packed = pack_eta_params(model, params)
    want = np.asarray(model.apply(params, feats))
    got = _fused(entry, packed, feats, tile=256)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


@ENTRIES
def test_fused_matches_apply_bf16_trunk(entry):
    # Default policy (bf16 matmuls): the kernel keeps bias and gelu in
    # f32 where XLA rounds them to bf16, so allow bf16-scale tolerance;
    # predictions are tens of minutes.
    model, params, feats = _model_and_params(policy=DEFAULT_POLICY)
    packed = pack_eta_params(model, params)
    want = np.asarray(model.apply(params, feats))
    got = _fused(entry, packed, feats, tile=256)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=0.5)


@ENTRIES
def test_fused_odd_batch_sizes(entry):
    model, params, feats = _model_and_params()
    packed = pack_eta_params(model, params)
    for n in (1, 7, 257):
        want = np.asarray(model.apply(params, feats[:n]))
        got = _fused(entry, packed, feats[:n], tile=128)
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


@ENTRIES
def test_fused_unknown_categories_and_negative_distance(entry):
    model, params, _ = _model_and_params()
    packed = pack_eta_params(model, params)
    rows = encode_requests(
        weather=["Fog", "Sunny", "Cloudy", "Sunny"],   # "Fog" → all-zero group
        traffic=["Gridlock", "Medium", "Low", "Low"],  # "Gridlock" → all-zero
        weekday=[0, 6, 3, 2],
        hour=[0, 23, 12, 5],
        distance_km=[5.0, 12.5, 0.0, 3.0],
        driver_age=[30.0, 55.0, 18.0, 40.0],
    )
    rows[2, 10] = -4.0  # malformed negative distance: both paths clamp to 0
    rows[3, 8] = 7.0    # weekday and hour out of range: all-zero one-hots,
    rows[3, 9] = 24.0   # not the neighbouring group's first row
    want = np.asarray(model.apply(params, rows))
    got = _fused(entry, packed, rows)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)
    assert np.isfinite(got).all()


@ENTRIES
def test_fused_non_mxu_hidden_dims(entry):
    # Hidden widths that need padding (not multiples of 128) stay exact:
    # zero pad rows/cols are no-ops through gelu.
    model, params, feats = _model_and_params(hidden=(96, 40))
    packed = pack_eta_params(model, params)
    want = np.asarray(model.apply(params, feats[:64]))
    got = _fused(entry, packed, feats[:64])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


def test_first_layer_operands_equal_expand():
    # The kernel applies the normalizer in f32 before the cast, as
    # EtaMLP._expand does (nothing is folded into the weights): under
    # extreme distance/age stats its expanded block IS _expand's bases,
    # bit for bit, and layer 0's weights are the model's own rows.
    from routest_tpu.ops.fused_mlp import K_ROWS, _expand_t

    model, params, feats = _model_and_params()
    params["norm"]["mean"] = params["norm"]["mean"].at[10].set(37.5).at[11].set(44.0)
    params["norm"]["std"] = params["norm"]["std"].at[10].set(0.25).at[11].set(9.0)
    feats = np.array(feats[:128])
    feats[0, 10] = -4.0          # clamped
    feats[1, 8:10] = (7.0, 24.0)  # no one-hot row
    packed = pack_eta_params(model, params)
    want, want_dist = model._expand(params, jax.numpy.asarray(feats))
    got, got_dist = _expand_t(jax.numpy.asarray(feats).T, packed["scalars"])
    assert got.shape == (K_ROWS, 128)
    np.testing.assert_array_equal(np.asarray(got[:42]).T, np.asarray(want))
    assert not np.asarray(got[42:]).any()
    np.testing.assert_array_equal(np.asarray(got_dist)[0], np.asarray(want_dist))
    np.testing.assert_array_equal(np.asarray(packed["w"][0]),
                                  np.asarray(params["layers"][0]["w"]))
    got = np.asarray(fused_eta_forward(packed, feats, interpret=True))
    np.testing.assert_allclose(got, np.asarray(model.apply(params, feats)),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("n", [1, 64])
def test_fused_under_jit_caller(n):
    # The wrapper must compose with an outer jit (serving wraps it), and
    # the pack with tracers for params (EtaMLP packs inside the trace).
    model, params, feats = _model_and_params()

    @jax.jit
    def run(params, x):
        return fused_eta_forward(pack_eta_params(model, params), x,
                                 interpret=True)

    want = np.asarray(model.apply(params, feats[:n]))
    np.testing.assert_allclose(np.asarray(run(params, feats[:n])), want,
                               rtol=1e-4, atol=1e-3)


def _quantile_model(seed=3):
    model = EtaMLP(hidden=(64, 32), policy=F32_POLICY,
                   quantiles=(0.1, 0.5, 0.9))
    feats = batch_from_mapping(generate_dataset(1024, seed=seed))
    mean, std = fit_normalizer(feats)
    return model, model.init(jax.random.PRNGKey(seed), norm_mean=mean,
                             norm_std=std), feats


@ENTRIES
def test_fused_quantile_epilogue_matches_apply_quantiles(entry):
    # VERDICT r3 #4: the kernel must serve the REAL serving artifact,
    # which carries quantile heads — parity over the fused cumulative
    # softplus epilogue, including the non-crossing guarantee.
    model, params, feats = _quantile_model()
    packed = pack_eta_params(model, params)
    want = np.asarray(model.apply_quantiles(params, feats))
    got = _fused(entry, packed, feats, n_q=3, tile=256)
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


# ── the choice ───────────────────────────────────────────────────────

BF16, F32 = "bfloat16", "float32"
SHIPPED = (256, 256, 128)


@pytest.mark.parametrize("backend,dtype,hidden,rows,want", [
    ("tpu", BF16, SHIPPED, 131072, "fused"),     # one slice of od-score
    ("tpu", BF16, SHIPPED, FUSED_MIN_ROWS, "fused"),
    ("tpu", BF16, (128,), 1 << 20, "fused"),
    ("tpu", BF16, SHIPPED, 4095, "xla"),         # under the threshold
    ("tpu", BF16, SHIPPED, FUSED_MIN_ROWS // 2, "xla"),
    ("tpu", BF16, SHIPPED, 131072 + 8, "xla"),   # not whole tiles
    ("tpu", BF16, SHIPPED, 8, "xla"),            # the serving buckets
    ("tpu", F32, SHIPPED, 131072, "xla"),        # float32 policy
    ("tpu", BF16, (256, 64), 131072, "xla"),     # a width that does not tile
    ("tpu", BF16, (), 131072, "xla"),
    ("tpu", BF16, SHIPPED, None, "xla"),         # a symbolic batch (export)
    ("cpu", BF16, SHIPPED, 131072, "xla"),
    ("gpu", BF16, SHIPPED, 131072, "xla"),
])
def test_eta_path(backend, dtype, hidden, rows, want):
    assert eta_path(backend, dtype, hidden, rows) == want


def test_apply_is_the_xla_body_where_the_kernel_is_not_chosen():
    # CPU, float32, toy widths, small batches: bit for bit the XLA body.
    for policy, hidden in ((DEFAULT_POLICY, SHIPPED), (F32_POLICY, (96, 40))):
        model, params, feats = _model_and_params(policy=policy, hidden=hidden)
        np.testing.assert_array_equal(
            np.asarray(model.apply(params, feats)),
            np.asarray(model.apply_xla(params, feats)))
    model, params, feats = _quantile_model()
    np.testing.assert_array_equal(
        np.asarray(model.apply_quantiles(params, feats)),
        np.asarray(model.apply_quantiles_xla(params, feats)))


def test_grad_through_the_fused_path_raises():
    model, params, feats = _model_and_params()

    def loss(params):
        packed = pack_eta_params(model, params)
        return fused_eta_forward_t(packed, feats[:128].T, 0, 128, True).sum()

    with pytest.raises(TypeError, match="inference-only"):
        jax.grad(loss)(params)
    with pytest.raises(TypeError):
        jax.jvp(loss, (params,), (params,))


@pytest.fixture
def everything_fused(monkeypatch):
    """As on a TPU at a large batch: ``eta_path`` answers "fused" for
    every call, so whatever reaches ``EtaMLP.apply*`` instead of the XLA
    body meets a Mosaic kernel the CPU cannot compile."""
    from routest_tpu.models import eta_mlp

    monkeypatch.setattr(eta_mlp, "eta_path", lambda *a: "fused")


@pytest.mark.parametrize("quantiles", [(), (0.1, 0.5, 0.9)])
def test_train_step_differentiates_the_xla_body(everything_fused, quantiles):
    import optax

    from routest_tpu.train.loop import (Batch, TrainState, make_eval_fn,
                                        make_train_step)

    model = EtaMLP(hidden=(128,), quantiles=quantiles)
    feats = batch_from_mapping(generate_dataset(256, seed=1))
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(Exception):   # the fixture bites
        model.apply(params, feats)
    before = np.asarray(params["layers"][0]["w"]).copy()  # the step donates
    optimizer = optax.sgd(1e-2)
    batch = Batch(jax.numpy.asarray(feats), jax.numpy.ones(256) * 20.0,
                  jax.numpy.ones(256))
    state, loss = make_train_step(model, optimizer)(
        TrainState(params, optimizer.init(params), 0), batch)
    assert np.isfinite(float(loss))
    assert np.abs(np.asarray(state.params["layers"][0]["w"]) - before).max() > 0
    sse, count = make_eval_fn(model)(state.params, batch)
    assert np.isfinite(float(sse)) and float(count) == 256


def test_exported_artifact_holds_no_mosaic_call(everything_fused, tmp_path):
    from jax import export as jax_export

    from routest_tpu.train.checkpoint import (EXPORT_MAGIC,
                                              export_serving_fn,
                                              load_exported_serving_fn)

    model = EtaMLP(quantiles=(0.1, 0.5, 0.9))
    params = model.init(jax.random.PRNGKey(0))
    path = str(tmp_path / "eta.stablehlo")
    export_serving_fn(path, model, params)
    with open(path, "rb") as f:
        assert f.read(len(EXPORT_MAGIC)) == EXPORT_MAGIC
        f.readline()
        text = jax_export.deserialize(bytearray(f.read())).mlir_module()
    assert "tpu_custom_call" not in text
    feats = batch_from_mapping(generate_dataset(64, seed=2))
    np.testing.assert_allclose(
        np.asarray(load_exported_serving_fn(path)(feats)),
        np.asarray(model.apply_quantiles_xla(params, feats)),
        rtol=2e-2, atol=0.25)
