"""The compiled scoring artifact: AOT bucket table, donation safety,
mesh acceptance, fused-head parity, and its observability surface.

ISSUE 10's serving contract: every batch bucket is
``jit().lower().compile()``d at startup (no compile — and no jit
dispatch — on any customer request), the batcher's staging slab is
donated into the compiled call without a defensive copy, the quantile
epilogue is fused (matmul-cumsum form ≡ the scan-form oracle), and a
mesh runtime is ACCEPTED by both the msgpack and StableHLO-export
paths (compiled with shardings) instead of refused.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from routest_tpu.core.config import ServeConfig
from routest_tpu.data.features import batch_from_mapping
from routest_tpu.data.synthetic import generate_dataset
from routest_tpu.models.eta_mlp import (EtaMLP, fit_normalizer,
                                        quantile_heads,
                                        quantile_heads_unfused)
from routest_tpu.train.checkpoint import save_model


@pytest.fixture(scope="module")
def quantile_artifact(tmp_path_factory):
    """A small trained-shape quantile artifact + its params (f32 trunk
    so bitwise comparisons are meaningful)."""
    from routest_tpu.core.dtypes import F32_POLICY

    model = EtaMLP(hidden=(32, 16), policy=F32_POLICY,
                   quantiles=(0.1, 0.5, 0.9))
    data = generate_dataset(512, seed=11)
    feats = np.asarray(batch_from_mapping(data), np.float32)
    mean, std = fit_normalizer(feats)
    params = model.init(jax.random.PRNGKey(11), norm_mean=mean,
                        norm_std=std)
    path = str(tmp_path_factory.mktemp("artifact") / "eta_q.msgpack")
    save_model(path, model, params)
    return path, model, params, feats


def _service(path, **cfg_kw):
    from routest_tpu.serve.ml_service import EtaService

    cfg = ServeConfig(batch_buckets=cfg_kw.pop("batch_buckets", (8, 64)),
                      max_wait_ms=1.0, **cfg_kw)
    return EtaService(cfg, model_path=path)


def test_aot_buckets_bitwise_equal_to_jit(quantile_artifact):
    """Every AOT bucket executable produces BITWISE the jit path's
    output — same program, same compiler, no numeric drift from the
    serving-entry refactor."""
    path, model, params, feats = quantile_artifact
    svc = _service(path)
    assert svc.available and svc._aot_buckets == (8, 64)
    apply_jit = jax.jit(model.apply_quantiles)
    pinned = jax.device_put(svc._params)
    for bucket in svc._aot_buckets:
        x = np.ascontiguousarray(
            np.resize(feats, (bucket, feats.shape[1])), np.float32)
        got = np.asarray(svc._score(x))
        want = np.asarray(apply_jit(pinned, jnp.asarray(x)))
        np.testing.assert_array_equal(got, want)


def test_no_compile_after_startup(quantile_artifact):
    """The compile-seconds metric proves the AOT claim: after bring-up
    every bucket has been compiled exactly as many times as bring-up
    compiled it, and serving traffic at every bucket size adds ZERO new
    observations."""
    from routest_tpu.obs import get_registry

    path, model, params, feats = quantile_artifact
    svc = _service(path)

    def counts():
        metric = get_registry().get("rtpu_replica_aot_compile_seconds")
        return {labels: child.count for labels, child in metric.items()}

    before = counts()
    for labels in (("8",), ("64",)):
        assert labels in before and before[labels] >= 1
    for n in (1, 7, 8, 33, 64, 100):  # every bucket + chunked oversize
        out = svc.predict_batch(np.resize(feats, (n, feats.shape[1]))
                                .astype(np.float32))
        assert out is not None and np.isfinite(out).all()
    assert counts() == before, "a customer request paid a compile"


def test_serve_aot_off_keeps_jit_path(quantile_artifact):
    path, model, params, feats = quantile_artifact
    svc = _service(path, serve_aot=False)
    assert svc.available and svc._aot_buckets == ()
    assert not svc.scoring_info()["aot"]
    out = svc.predict_batch(feats[:4])
    assert out is not None and out.shape == (4, 3)


def test_scoring_info_surface(quantile_artifact):
    path, *_ = quantile_artifact
    svc = _service(path)
    info = svc.scoring_info()
    assert info["kernel"] == "xla"
    assert info["dtype"] == "float32"
    assert info["aot"] is True and info["aot_buckets"] == [8, 64]


def test_health_reports_scoring_block(quantile_artifact, monkeypatch):
    path, *_ = quantile_artifact
    monkeypatch.setenv("ETA_MODEL_PATH", path)
    monkeypatch.setenv("ROUTEST_WARM_BUCKETS", "0")
    from werkzeug.test import Client

    from routest_tpu.core.config import load_config
    from routest_tpu.serve.app import create_app

    client = Client(create_app(load_config()))
    model_block = client.get("/api/health").get_json()["checks"]["model"]
    scoring = model_block["scoring"]
    assert scoring["kernel"] == "xla"
    assert scoring["dtype"] == "float32"
    assert scoring["aot"] is True and scoring["aot_buckets"]


def test_donation_safe_staging_slab_fuzz():
    """Satellite acceptance: 8 threads × random row counts through the
    staging slab with DONATION ON — the per-bucket compiled score
    program donates its input (the device copy of the slab) exactly as
    serving does — and every waiter's answer still equals the direct
    oracle on its OWN rows. Proves the slab-rotation safety argument:
    a donated in-flight buffer is never rewritten under a waiter."""
    import warnings

    from routest_tpu.serve.ml_service import DynamicBatcher

    def forward(x):
        # Row-wise, batch-size-invariant program: per-row results are
        # identical whatever padding the bucket added.
        return (x * 2.0 + 1.0).sum(axis=1)

    buckets = (4, 16, 64)
    table = {}
    jitted = jax.jit(forward, donate_argnums=(0,))
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        for b in buckets:
            table[b] = jitted.lower(
                jax.ShapeDtypeStruct((b, 12), np.float32)).compile()

    def score(x):
        exe = table.get(len(x))
        if exe is None:
            return forward(jnp.asarray(x))
        return exe(np.ascontiguousarray(x, np.float32))

    batcher = DynamicBatcher(score, buckets=buckets, max_batch=64,
                             max_wait_ms=5.0)
    rng = np.random.default_rng(13)
    n_threads, iters = 8, 25
    payloads = [[rng.uniform(-50, 50, size=(int(rng.integers(1, 9)), 12))
                 .astype(np.float32) for _ in range(iters)]
                for _ in range(n_threads)]
    failures = []
    barrier = threading.Barrier(n_threads)

    def worker(t):
        barrier.wait()
        for rows in payloads[t]:
            got = np.asarray(batcher.submit(rows))
            want = (rows * 2.0 + 1.0).sum(axis=1)
            # atol: XLA's reduce order differs from numpy's pairwise
            # sum, so near-zero row sums carry f32 cancellation error —
            # crosstalk (another waiter's rows) would be off by ~1e2.
            if got.shape != want.shape or not np.allclose(got, want,
                                                          rtol=1e-5,
                                                          atol=1e-2):
                failures.append((t, rows.shape))
                return

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not failures, failures[:2]
    assert batcher.stats["rows"] == sum(
        len(r) for p in payloads for r in p)


def test_quantile_heads_fused_matches_unfused_oracle():
    """The matmul-cumsum epilogue ≡ the scan-form oracle to ≤1e-5 rel,
    and non-crossing holds for arbitrary raw head outputs."""
    rng = np.random.default_rng(3)
    out = jnp.asarray(rng.normal(0, 3, size=(257, 14)), jnp.float32)
    dist = jnp.asarray(rng.uniform(0, 40, size=(257,)), jnp.float32)
    fused = np.asarray(quantile_heads(out, dist, 7))
    oracle = np.asarray(quantile_heads_unfused(out, dist, 7))
    np.testing.assert_allclose(fused, oracle, rtol=1e-5, atol=1e-5)
    assert (np.diff(fused, axis=1) >= -1e-5).all()


def test_mesh_runtime_compiles_sharded_aot(quantile_artifact,
                                           mesh_runtime):
    """The msgpack path under a mesh runtime AOT-compiles every bucket
    WITH the mesh's batch sharding (the shard-ready artifact ROADMAP
    item 2 fans out) and still matches the unsharded oracle."""
    from routest_tpu.serve.ml_service import EtaService

    path, model, params, feats = quantile_artifact
    cfg = ServeConfig(batch_buckets=(8, 64), max_wait_ms=1.0)
    svc = EtaService(cfg, model_path=path, runtime=mesh_runtime)
    assert svc.available and svc.kernel == "xla"
    assert svc._aot_buckets == (8, 64)  # align=8 keeps them shardable
    out = svc.predict_batch(feats[:16])
    want = np.asarray(model.apply_quantiles(params, feats[:16]))
    np.testing.assert_allclose(np.asarray(out, np.float64), want,
                               rtol=2e-5, atol=1e-4)


def test_stablehlo_export_accepts_mesh_runtime(quantile_artifact,
                                               mesh_runtime, tmp_path):
    """The StableHLO-export path no longer refuses a mesh runtime: the
    serialized program compiles under the mesh's shardings per bucket
    (kernel ``stablehlo_aot_sharded``) with outputs matching the
    unsharded export call."""
    from routest_tpu.serve.ml_service import EtaService
    from routest_tpu.train.checkpoint import export_serving_fn

    path, model, params, feats = quantile_artifact
    export = str(tmp_path / "eta_q.stablehlo")
    export_serving_fn(export, model, params, platforms=("cpu",))
    cfg = ServeConfig(batch_buckets=(8, 64), max_wait_ms=1.0)
    svc = EtaService(cfg, model_path=export, runtime=mesh_runtime)
    assert svc.available
    assert svc.kernel == "stablehlo_aot_sharded"
    assert svc._aot_buckets == (8, 64)
    out = svc.predict_batch(feats[:16])
    want = np.asarray(model.apply_quantiles(params, feats[:16]))
    np.testing.assert_allclose(np.asarray(out, np.float64), want,
                               rtol=2e-5, atol=1e-4)
