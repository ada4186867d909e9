"""The ``route_scan_sala`` driver end to end at a toy size on the CPU,
skipping only the harness's look for a chip: the reference agrees with
the program, the control and every planted fault come out as not
correct — each fault by the gap the mix names —, the counts are a hand
count, the per-layer readers give numbers and give nothing where the
program left nothing."""

import tempfile
import time

import jax
import numpy as np
import pytest

from _toy import both_manifests, reported
from _toy_sala import CELL, R, cell_files, manifest

from benchmark import compare, counts_sala, faults_sala, seq_spans, traffic_seq

# the model-blind readers the cell joins (BENCHMARK.json: its name
# appended to their ``workloads``) and its own, listed since PR 34
READERS = ["seq_mfu_pct", "seq_step_host_pct", "seq_padded_token_pct",
           "sala_sparse_visited_over_chosen"]


def _driver(seed=3):
    _, config, mix = cell_files()
    mod = R.load_module("drivers", mix["driver"])
    scratch = tempfile.mkdtemp(prefix="routest-benchmark-test-")
    return mod.Driver(R.Run(seed, config, mix, R.REPO, scratch))


@pytest.fixture(scope="module")
def sound():
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
    assert set(result["checks"]) == set(mix["limits"]) == {
        "logit_gap", "lse_gap", "rows_gap", "loglik_gap", "block_set_gap",
        "key_set_gap", "state_gap"}


def test_the_program_is_inside_every_limit(sound):
    driver, want = sound
    numbers = driver.gaps(driver.program_routes(), want)
    checks = compare.with_limits(numbers, driver.mix["limits"])
    assert compare.verdict(checks), compare.as_lines(checks)
    assert numbers["key_set_gap"] == 0.0
    # every route selects: a query's last position sees six blocks
    assert all((w["n_keys"][0][-1] < len(w["lse"])).all() for w in want)


@pytest.mark.parametrize("fault", sorted(faults_sala.FAULTS))
def test_a_planted_fault_is_caught_by_the_gap_the_mix_names(sound, fault):
    driver, want = sound
    with faults_sala.FAULTS[fault]():
        faulty = _driver()
        faulty.window(0.01)
    numbers = faulty.gaps(faulty.program_routes(), want)
    checks = compare.with_limits(numbers, faulty.mix["limits"])
    assert not compare.verdict(checks), numbers
    named = driver.mix["faults"][fault]
    assert named in [c.name for c in checks if not c.ok], numbers


def test_the_mix_names_a_gap_for_every_fault():
    _, _, mix = R.load_cell(manifest(), CELL)
    assert set(mix["faults"]) == set(faults_sala.FAULTS)
    assert set(mix["faults"].values()) <= set(mix["limits"])
    assert set(mix["limit_reasons"]) >= set(mix["limits"])


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
    # a (token, group) of two sparse layers sees at most 6 blocks of 8
    assert 0 < c["chosen_keys"] <= c["tokens_real"] * 2 * 2 * 48
    assert c["flops"] == c["passes"] * counts_sala.pass_flops(
        driver.cfg, lengths, c["chosen_keys"])
    ctx = {"counts": c, "device_kind": "TPU v5 lite", "chips": 1}
    assert 0.0 < seq_spans.host_pct(ctx) < 100.0
    assert R.load_module("metrics", "seq_mfu_pct").read(ctx) > 0.0
    assert 0.0 <= R.load_module("metrics",
                                "seq_padded_token_pct").read(ctx) < 100.0
    ratio = R.load_module("metrics",
                          "sala_sparse_visited_over_chosen").read(ctx)
    assert ratio > 1.0


@pytest.mark.parametrize("name", READERS)
def test_a_reader_gives_nothing_where_the_program_left_nothing(name):
    from routest_tpu.obs import MetricsRegistry, Tracer
    from routest_tpu.obs import registry as reg_mod
    from routest_tpu.obs import trace as trace_mod

    old_t, old_r = trace_mod._tracer, reg_mod._default_registry
    try:
        trace_mod._tracer = Tracer(enabled=False)
        reg_mod._default_registry = MetricsRegistry()
        ctx = {"counts": {"passes": 2}, "device_kind": "TPU v5 lite",
               "chips": 1}
        assert R.load_module("metrics", name).read(ctx) is None
        # a family that has counted nothing
        reg_mod._default_registry.counter("rtpu_seq_sparse_keys_total", "",
                                          ("kind",))
        assert R.load_module("metrics", name).read(ctx) is None
    finally:
        trace_mod._tracer, reg_mod._default_registry = old_t, old_r


@both_manifests
def test_the_manifest_lists_the_cell_for_its_five_metrics_and_no_older_cell(
        m):
    """The cell is IN the lists of the scorer's four model-blind metrics
    and of its own ratio, each of which moves ``od_rows_per_s``; the two
    older cells report none of them. Nothing about the lists' other
    members, the entries' places or any other name."""
    e2e = [x["name"] for x in R.metrics_of(m, "end_to_end", CELL)]
    assert {"od_rows_per_s", "setup_s"} <= set(e2e)
    mine = set(READERS) | {"device_idle_pct.seq"}
    assert mine <= set(reported(m, CELL))
    for x in m["per_layer"]:
        if x["name"] in mine:
            assert CELL in x["workloads"]
            assert x["moves"] == "od_rows_per_s"
    for cell in ("od-score", "gnn-refit"):
        assert not mine & set(reported(m, cell))


# ── the traffic ──────────────────────────────────────────────────────


def test_the_cells_lengths_are_the_quantiles_the_mix_states():
    _, config, mix = R.load_cell(manifest(), CELL)
    lengths = traffic_seq.route_lengths(mix)
    assert lengths == mix["lengths"] == [8932, 13664, 18051, 23236, 30696,
                                         46958]
    assert sum(lengths) == 141537
    assert min(lengths) > config["sparse"]["dense_len"]
    tail, _, _ = traffic_seq.grid_arcs(config["vocab_size"])
    assert len(tail) == 73440
    for n, padded in zip(lengths, mix["reference_blocks"]["pad_to"]):
        assert n <= padded and padded % 1024 == 0


# ── the counts ───────────────────────────────────────────────────────


def test_counts_against_a_hand_count_at_a_small_shape():
    cfg = dict(hidden_size=8, intermediate_size=16, head_dim=4,
               num_attention_heads=4, num_key_value_heads=2,
               lightning_nh=2, lightning_head_dim=4, vocab_size=10,
               num_hidden_layers=2, mixer_types=["minicpm4",
                                                 "lightning-attn"],
               sparse={"kernel_size": 4, "kernel_stride": 2,
                       "dense_len": 6})
    # sparse: w_q 8x16, w_k and w_v 8x8 each, gate 8x16, w_o 16x8
    assert counts_sala.mixer_weight_count(cfg, "minicpm4") == 512
    # linear: w_q, w_k, w_v, gate 8x8 each, w_o 8x8
    assert counts_sala.mixer_weight_count(cfg, "lightning-attn") == 320
    assert counts_sala.layer_weight_count(cfg, "minicpm4") == 512 + 384
    # + norms: 2 x 8 a layer, 2 x 4 (sparse), 2 x 4 + 8 (linear), final 8
    assert counts_sala.parameter_count(cfg) == (
        896 + 704 + 16 + 16 + 8 + 16 + 160 + 8)
    # a query at t sees the compressed keys j with 2j + 3 <= t
    assert counts_sala.visible_compressed(cfg, 8) == 0 + 0 + 0 + 1 + 1 + 2 \
        + 2 + 3
    # one route of 8 tokens in chunks of 256: one partial chunk of 8
    # 36 causal pairs, scored and weighted (4 wide each), then q S, k^T v
    assert counts_sala.linear_flops(cfg, 8) == 2 * 2 * (
        36 * 2 * 4 + 2 * 8 * 4 * 4)
    assert counts_sala.linear_flops(cfg, 300, chunk=256) == 2 * 2 * (
        256 * 257 * 4 + 2 * 256 * 16 + 44 * 45 * 4 + 2 * 44 * 16)
    want = (2 * 8 * (896 + 704 + 80)          # matrices and the head
            + 2 * 2 * 2 * 4 * 100             # 100 chosen keys, 2 heads each
            + 2 * 4 * 4 * 9                   # first stage, 4 heads
            + counts_sala.linear_flops(cfg, 8))
    assert counts_sala.pass_flops(cfg, [8], 100.0) == want
    # a route below dense_len selects nothing
    assert counts_sala.pass_flops(cfg, [5], 0.0) == (
        2 * 5 * 1680 + counts_sala.linear_flops(cfg, 5))


def test_pass_flops_of_the_cell_are_what_the_issue_counted():
    _, config, mix = R.load_cell(manifest(), CELL)
    lengths = mix["lengths"]
    # every (token, group) of both sparse layers at its 64 blocks, less
    # the keys of the query's own block that lie in its future
    chosen = 2 * 2 * sum(64 * 64 - (63 - (t % 64)) for n in lengths
                         for t in range(n))
    flops = counts_sala.pass_flops(config, lengths, chosen)
    assert abs(flops / 1e12 - 737.9) < 0.5       # ISSUE 32: 736
    dense = 2 * sum(lengths) * (2 * 253_755_392 + 6 * 285_212_672
                                + 4096 * 73448)
    assert abs(dense / 1e12 - 713.3) < 0.5
    assert abs(6 * sum(counts_sala.linear_flops(config, n)
                       for n in lengths) / 1e12 - 3.6) < 0.2
    assert counts_sala.weight_bytes(config) == 2 * 2_820_569_088
