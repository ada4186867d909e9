"""The ``route_scan`` driver end to end at a toy size on the CPU,
skipping only the harness's look for a chip: the reference agrees with
the program, the control and every planted fault come out as not
correct, the counts and the per-layer readers give numbers."""

import tempfile
import time

import jax
import numpy as np
import pytest

from _toy_seq import CELL, R, cell_files, manifest

from benchmark import compare, counts_seq, faults_seq, seq_spans, traffic_seq


def _driver(seed=3):
    _, config, mix = cell_files()
    mod = R.load_module("drivers", mix["driver"])
    scratch = tempfile.mkdtemp(prefix="routest-benchmark-test-")
    return mod.Driver(R.Run(seed, config, mix, R.REPO, scratch))


@pytest.fixture(scope="module")
def sound():
    """A sound driver after a short window, and the reference's answers
    for its table (the faulty programs below score the same table with
    the same weights: the faults patch the program, not the inputs)."""
    driver = _driver()
    driver.window(0.05)
    return driver, driver.reference()


def test_a_run_is_correct_and_reports_the_cells_metrics():
    cell, config, mix = cell_files()
    result = R.execute(manifest(), cell, config, mix, 2 ** 31 + 11, 0.2,
                       False, jax.devices()[:1], time.perf_counter())
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    want = {m["name"] for m in R.metrics_of(manifest(), "end_to_end", CELL)}
    assert set(result["metrics"]) == want >= {"od_rows_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert result["compiles"]["window"] == 0
    assert set(result["checks"]) == set(mix["limits"])


def test_the_program_is_inside_every_limit(sound):
    driver, want = sound
    numbers = driver.gaps(driver.program_routes(), want)
    checks = compare.with_limits(numbers, driver.mix["limits"])
    assert compare.verdict(checks), compare.as_lines(checks)
    assert numbers["key_set_gap"] == 0.0


@pytest.mark.parametrize("fault", sorted(faults_seq.FAULTS))
def test_a_planted_fault_comes_out_not_correct(sound, fault):
    _, want = sound
    with faults_seq.FAULTS[fault]():
        faulty = _driver()
        faulty.window(0.01)
    numbers = faulty.gaps(faulty.program_routes(), want)
    checks = compare.with_limits(numbers, faulty.mix["limits"])
    assert not compare.verdict(checks), numbers
    assert [c.name for c in checks if not c.ok]


def test_control_in_fp8_comes_out_not_correct(sound):
    driver, want = sound
    control = driver.gaps(driver.reference(driver.mix["control"]), want)
    assert not compare.verdict(compare.with_limits(control,
                                                   driver.mix["limits"]))


def test_counts_and_readers(sound):
    driver, _ = sound
    c = driver.counts()
    lengths = driver.table["lengths"]
    assert c["passes"] == len(driver.durations) >= 1
    assert c["tokens_real"] == int(lengths.sum())
    assert 0 < c["held_assignments"] <= c["tokens_real"] * 4 * 4
    assert c["flops"] == c["passes"] * counts_seq.pass_flops(
        driver.cfg, lengths, c["held_assignments"])
    ctx = {"counts": c}
    assert 0.0 < seq_spans.host_pct(ctx) < 100.0
    assert 0.0 <= seq_spans.padded_token_pct(ctx) < 100.0
    assert seq_spans.expert_load_max_over_mean(ctx) >= 1.0
    assert seq_spans.host_pct({"counts": {"passes": 10 ** 6}}) is None


def test_readers_give_nothing_where_the_program_left_nothing():
    from routest_tpu.obs import MetricsRegistry, Tracer
    from routest_tpu.obs import registry as reg_mod
    from routest_tpu.obs import trace as trace_mod

    old_t, old_r = trace_mod._tracer, reg_mod._default_registry
    try:
        trace_mod._tracer = Tracer(enabled=False)
        reg_mod._default_registry = MetricsRegistry()
        ctx = {"counts": {"passes": 2}}
        assert seq_spans.host_pct(ctx) is None
        assert seq_spans.padded_token_pct(ctx) is None
        assert seq_spans.expert_load_max_over_mean(ctx) is None
    finally:
        trace_mod._tracer, reg_mod._default_registry = old_t, old_r


# ── the traffic ──────────────────────────────────────────────────────


def test_the_cells_lengths_are_the_quantiles_the_mix_states():
    _, _, mix = R.load_cell(manifest(), CELL)
    lengths = traffic_seq.route_lengths(mix)
    assert lengths == mix["lengths"] and sum(lengths) == 102439
    assert sum(n > 2048 for n in lengths) == 12 and min(lengths) > 513


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5, 2 ** 33 + 1])
def test_route_table_is_walks_on_the_grid_within_the_slice(seed):
    _, config, mix = cell_files()
    table = traffic_seq.route_table(seed, config, mix)
    again = traffic_seq.route_table(seed, config, mix)
    np.testing.assert_array_equal(table["ids"], again["ids"])
    assert sorted(table["lengths"]) == mix["lengths"]
    tail, head, succ = traffic_seq.grid_arcs(config["vocab_size"])
    assert len(tail) <= config["vocab_size"]
    assert int(table["ids"].max()) < len(tail)
    for ids, n, at in zip(table["ids"], table["lengths"], table["rows_at"]):
        assert (head[ids[:n - 1]] == tail[ids[1:n]]).all()
        assert (ids[n:] == 0).all()
        assert (np.diff(at) > 0).all() and at[-1] < n - 1
    other = traffic_seq.route_table(seed + 1, config, mix)
    assert (other["ids"] != table["ids"]).any()


def test_grid_walks_turn_back_only_at_a_dead_end():
    tail, head, succ = traffic_seq.grid_arcs(47)      # a 3 x 3 grid
    assert len(tail) == 24
    for a in range(len(tail)):
        onward = succ[a][succ[a] >= 0]
        assert len(onward) >= 1
        assert (tail[onward] == head[a]).all()
        assert (head[onward] != tail[a]).all()        # no dead end here


# ── the counts ───────────────────────────────────────────────────────


def test_pass_flops_of_the_cell_are_what_the_issue_counted():
    _, config, mix = R.load_cell(manifest(), CELL)
    lengths = mix["lengths"]
    flops = counts_seq.pass_flops(config, lengths, 4 * sum(lengths))
    assert abs(flops / 1e15 - 0.2752) < 5e-4
    core = sum(counts_seq.attention_flops(config, kind, n)
               - 2 * n * counts_seq.attention_weight_count(config, kind)
               for kind, _ in counts_seq.layer_kinds(config) for n in lengths)
    assert 0.20 < core / flops < 0.22
    assert counts_seq.attention_weight_count(
        config, "full_attention") == 144_048_128
    assert counts_seq.attention_weight_count(
        config, "sliding_attention") == 90_832_896
    assert counts_seq.keys_seen(5, 3) == 1 + 2 + 3 + 3 + 3
