"""Each driver end to end at a toy size on the CPU, skipping only the
harness's look for a chip: the references agree with the program, the
control and every planted fault come out as not correct."""

import time

import jax
import numpy as np
import pytest

from _toy import R, cell_files, manifest

from benchmark import compare, faults


def _execute(name, seed=3, seconds=0.3):
    cell, config, mix = cell_files(name)
    return R.execute(manifest(), cell, config, mix, seed, seconds, False,
                     jax.devices()[:1], time.perf_counter())


def _driver(name, seed=3):
    cell, config, mix = cell_files(name)
    import tempfile

    mod = R.load_module("drivers", mix["driver"])
    scratch = tempfile.mkdtemp(prefix="routest-benchmark-test-")
    return mod, mod.Driver(R.Run(seed, config, mix, R.REPO, scratch))


@pytest.mark.parametrize("name", ["od-score", "gnn-refit"])
def test_a_run_is_correct_and_reports_the_cells_metrics(name):
    result = _execute(name, seed=2 ** 31 + 11)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    want = {m["name"] for m in R.metrics_of(manifest(), "end_to_end", name)}
    assert set(result["metrics"]) == want
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert list(result)[-1] == "checks"
    assert set(result["checks"]) == set(cell_files(name)[2]["limits"])


@pytest.mark.parametrize("name,fault", [
    ("od-score", "answer_altered"), ("od-score", "rows_left_out"),
    ("gnn-refit", "state_unchanged"), ("gnn-refit", "half_batch")])
def test_a_planted_fault_comes_out_not_correct(name, fault):
    with faults.FAULTS[fault]():
        result = _execute(name)
    assert result["correct"] is False
    failed = [k for k, c in result["checks"].items()
              if not c["value"] <= c["limit"]]
    assert failed, result["checks"]


def test_od_control_in_int8_comes_out_not_correct():
    _, driver = _driver("od-score")
    driver.window(0.1)
    limits = driver.mix["limits"]
    control = driver.numbers(answers_precision=driver.mix["control"])
    assert not compare.verdict(compare.with_limits(control, limits))
    assert compare.verdict(compare.with_limits(driver.numbers(), limits))


def test_gnn_control_in_bfloat16_comes_out_not_correct():
    mod, driver = _driver("gnn-refit")
    limits = driver.mix["limits"]
    want = driver.follow()
    control = mod.gaps(driver.follow(dtype_name=driver.mix["control"]), want)
    assert not compare.verdict(compare.with_limits(control, limits))
    program = mod.gaps(driver.program_readings(), want)
    assert compare.verdict(compare.with_limits(program, limits))


def test_od_reference_reads_the_artifact_as_the_program_does():
    from routest_tpu.train.checkpoint import load_model

    from benchmark.reference import eta_mlp_ref

    path = R.REPO + "/artifacts/eta_mlp.msgpack"
    header, ours = eta_mlp_ref.read_artifact(path)
    model, theirs = load_model(path)
    assert tuple(header["quantiles"]) == tuple(model.quantiles)
    for a, b in zip(ours["layers"], theirs["layers"]):
        np.testing.assert_array_equal(a["w"], b["w"])
        np.testing.assert_array_equal(a["b"], b["b"])


def test_od_reference_equals_the_program_in_float32():
    """Same equations: with the program's own compute switched to
    float32 the two agree to rounding."""
    import dataclasses

    import jax.numpy as jnp

    from routest_tpu.core.dtypes import F32_POLICY
    from routest_tpu.train.checkpoint import load_model

    from benchmark import traffic
    from benchmark.reference import eta_mlp_ref

    _, cfg, _ = cell_files("od-score")
    path = R.REPO + "/artifacts/eta_mlp.msgpack"
    model, params = load_model(path)
    model = dataclasses.replace(model, policy=F32_POLICY)
    x = traffic.od_table(5, cfg)[:4096]
    with jax.default_matmul_precision("highest"):
        theirs = model.apply_quantiles(params, x)
    _, ref_params = eta_mlp_ref.read_artifact(path)
    ours = eta_mlp_ref.forward(ref_params, x, 3)
    assert float(eta_mlp_ref.gap(ours, theirs).max()) < 1e-5
    assert bool(jnp.all(ours[:, 1:] >= ours[:, :-1]))


def test_gnn_reference_init_is_the_models_published_init():
    from routest_tpu.core.dtypes import F32_POLICY
    from routest_tpu.models.gnn import N_EDGE_FEATURES, RoadGNN

    from benchmark.reference import road_gnn_ref as ref

    theirs = RoadGNN(n_nodes=10, hidden=64, policy=F32_POLICY).init(
        jax.random.PRNGKey(77))
    ours = ref.init_params(77, 64, N_EDGE_FEATURES)
    for group, i, leaf in ref.LEAVES:
        np.testing.assert_array_equal(np.asarray(ours[group][i][leaf]),
                                      np.asarray(theirs[group][i][leaf]))


def test_a_failed_cycle_counts_as_failed_and_not_correct():
    mod, driver = _driver("gnn-refit")
    driver.trainer.min_obs = 10 ** 9          # every cycle is skipped
    driver.window(0.05)
    assert driver.failed == driver.attempted >= 1
    assert driver.end_to_end()["gnn_edges_per_s"] == 0.0
