"""The ``route_scan_kexaone`` driver end to end at a toy size on the CPU,
skipping only the harness's look for a chip: the reference agrees with
the program in both likelihood columns, the control and every planted
fault come out as not correct — each fault by the gap the mix names, and
the fault in the module's input by the module's column alone —, the
counts are a hand count, the per-layer readers give numbers and give
nothing where the program left nothing."""

import tempfile
import time

import jax
import numpy as np
import pytest

from _toy import both_manifests, reported
from _toy_kexaone import CELL, R, cell_files, manifest

from benchmark import (compare, counts_kexaone, faults_kexaone, seq_spans,
                       traffic_seq)

# the readers the cell joins (BENCHMARK.json: its name appended to their
# ``workloads``) and its own two
JOINED = ["seq_mfu_pct", "seq_step_host_pct", "seq_padded_token_pct",
          "seq_expert_load_max_over_mean"]
OWN = ["gqa_window_visited_over_needed", "gqa_full_visited_over_needed"]
FIRST_COLUMN = ("logit_gap", "lse_gap", "rows_gap", "loglik_gap")


def _driver(seed=3):
    _, config, mix = cell_files()
    mod = R.load_module("drivers", mix["driver"])
    scratch = tempfile.mkdtemp(prefix="routest-benchmark-test-")
    return mod.Driver(R.Run(seed, config, mix, R.REPO, scratch))


@pytest.fixture(scope="module")
def sound():
    driver = _driver()
    driver.window(0.05)
    want = driver.reference()
    return driver, want, driver.gaps(driver.program_routes(), want)


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
    assert set(result["checks"]) == set(mix["limits"]) == set(
        FIRST_COLUMN) | {"mtp_logit_gap", "mtp_lse_gap", "mtp_loglik_gap",
                         "expert_gap", "key_set_gap"}


def test_the_program_is_inside_every_limit(sound):
    driver, want, numbers = sound
    checks = compare.with_limits(numbers, driver.mix["limits"])
    assert compare.verdict(checks), compare.as_lines(checks)
    assert numbers["key_set_gap"] == 0.0
    assert "selected_gap" not in numbers
    # the steps hold one to three routes in four length classes
    assert sorted({len(s.routes) for s in driver.plan}) == [1, 2]
    assert len({s.length for s in driver.plan}) == 4
    # the module's block is the last row and one position short
    w = want[0]
    assert len(w["n_keys"]) == 6 and len(w["chosen"]) == 5
    assert len(w["n_keys"][-1]) == len(w["lse"]) - 1 == len(w["mtp_lse"])


@pytest.mark.parametrize("fault", sorted(faults_kexaone.FAULTS))
def test_a_planted_fault_is_caught_by_the_gap_the_mix_names(sound, fault):
    driver, want, own = sound
    with faults_kexaone.FAULTS[fault]():
        faulty = _driver()
        faulty.window(0.01)
    numbers = faulty.gaps(faulty.program_routes(), want)
    checks = compare.with_limits(numbers, faulty.mix["limits"])
    assert not compare.verdict(checks), numbers
    named = driver.mix["faults"][fault]
    assert named in [c.name for c in checks if not c.ok], numbers
    if fault == "module_fed_this_token":
        # the first column does not move: its gaps are the program's own
        assert all(numbers[k] == own[k] for k in FIRST_COLUMN)
        assert numbers["key_set_gap"] == 0.0
    if fault == "window_off_by_one":
        assert numbers["key_set_gap"] > 0.5
    else:
        assert numbers["key_set_gap"] == 0.0


def test_the_mix_names_a_gap_for_every_fault():
    _, _, mix = R.load_cell(manifest(), CELL)
    assert set(mix["faults"]) == set(faults_kexaone.FAULTS)
    assert set(mix["faults"].values()) <= set(mix["limits"])
    assert set(mix["limit_reasons"]) >= set(mix["limits"])
    assert mix["faults"]["window_off_by_one"] == "key_set_gap"
    assert mix["faults"]["module_fed_this_token"].startswith("mtp_")


def test_control_in_fp8_comes_out_not_correct(sound):
    driver, want, _ = sound
    control = driver.gaps(driver.reference(driver.mix["control"]), want)
    assert not compare.verdict(compare.with_limits(control,
                                                   driver.mix["limits"]))


def test_counts_and_readers(sound):
    driver, want, _ = sound
    c = driver.counts()
    lengths = driver.table["lengths"]
    assert c["passes"] == len(driver.durations) >= 1
    assert c["tokens_real"] == int(lengths.sum()) == 282
    assert c["mtp_positions"] == int(lengths.sum()) - 2 * len(lengths)
    # the reference's own choices that land on the held experts 0-7
    held = sum(int((np.asarray(ch) < 8).sum()) for w in want
               for ch in w["chosen"])
    assert abs(c["held_assignments"] - held) <= 0.05 * held
    assert c["flops"] == c["passes"] * counts_kexaone.pass_flops(
        driver.cfg, lengths, c["held_assignments"])
    ctx = {"counts": c, "device_kind": "TPU v5 lite", "chips": 1}
    assert 0.0 < seq_spans.host_pct(ctx) < 100.0
    assert R.load_module("metrics", "seq_mfu_pct").read(ctx) > 0.0
    assert 0.0 <= R.load_module("metrics",
                                "seq_padded_token_pct").read(ctx) < 100.0
    assert R.load_module("metrics",
                         "seq_expert_load_max_over_mean").read(ctx) >= 1.0
    window = R.load_module("metrics", OWN[0]).read(ctx)
    full = R.load_module("metrics", OWN[1]).read(ctx)
    # two blocks of 8 keys a query for at most 8 seen; whole chunks of 16
    assert 2.0 < window < 4.0 and 1.0 < full < 2.0


@pytest.mark.parametrize("name", JOINED + OWN)
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
        # a family that has counted nothing, and one with one kind alone
        keys = reg_mod._default_registry.counter(
            "rtpu_seq_gqa_keys_total", "", ("layer", "kind"))
        assert R.load_module("metrics", name).read(ctx) is None
        keys.labels(layer="window", kind="visited").inc(5)
        keys.labels(layer="full", kind="needed").inc(5)
        assert R.load_module("metrics", name).read(ctx) is None
    finally:
        trace_mod._tracer, reg_mod._default_registry = old_t, old_r


@both_manifests
def test_the_manifest_lists_the_cell_for_its_metrics_and_no_older_cell(m):
    """The cell is IN the lists of the metrics it joins and of its own
    two, each of which moves ``od_rows_per_s``; its own two are reported
    by no older cell. Nothing about the lists' other members, the
    entries' places or any other name."""
    e2e = [x["name"] for x in R.metrics_of(m, "end_to_end", CELL)]
    assert {"od_rows_per_s", "setup_s"} <= set(e2e)
    mine = set(JOINED + OWN) | {"device_idle_pct.seq"}
    assert mine <= set(reported(m, CELL))
    for x in m["per_layer"]:
        if x["name"] in mine:
            assert CELL in x["workloads"]
            assert x["moves"] == "od_rows_per_s"
        if x["name"] in OWN:
            assert (x["unit"], x["better"], x["source"], x["layer"]) == (
                "ratio", "lower", "program_counter", "attention")
    for cell in ("od-score", "gnn-refit", "route-lm-score",
                 "route-lm-sala-long"):
        assert not set(OWN) & set(reported(m, cell))
    (cell,) = [w for w in m["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "k-exaone-236b-ep8", "route-histories-512-27k", 1)
    (config,) = [c for c in m["configs"] if c["name"] == cell["config"]]
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["source"].endswith(
        "LGAI-EXAONE/K-EXAONE-236B-A23B/blob/main/config.json")


# ── the traffic ──────────────────────────────────────────────────────


def test_the_cells_lengths_are_the_quantiles_the_mix_states():
    _, config, mix = R.load_cell(manifest(), CELL)
    lengths = traffic_seq.route_lengths(mix)
    assert lengths == mix["lengths"] == [
        512, 631, 867, 1099, 1338, 1592, 1865, 2164, 2495, 2867, 3291, 3782,
        4362, 5061, 5929, 7052, 8588, 10889, 14966, 26530]
    assert sum(lengths) == 105880 and mix["max_step_tokens"] == 32768
    tail, _, _ = traffic_seq.grid_arcs(config["vocab_size"])
    assert len(tail) == 18768 <= config["vocab_size"]
    pads = mix["reference_blocks"]["pad_to"]
    assert max(lengths) <= max(pads)
    assert all(p % mix["reference_blocks"]["q_block"] == 0
               and p % mix["reference_blocks"]["row_block"] == 0
               for p in pads)


# ── the counts ───────────────────────────────────────────────────────


def test_counts_against_a_hand_count_at_a_small_shape():
    cfg = dict(hidden_size=8, intermediate_size=16, head_dim=4,
               num_attention_heads=4, num_key_value_heads=2,
               moe_intermediate_size=6, num_experts=2, num_shared_experts=1,
               num_experts_per_tok=2, sliding_window=3, vocab_size=10,
               num_hidden_layers=2, num_nextn_predict_layers=1,
               layer_types=["sliding_attention", "full_attention"],
               mlp_layer_types=["dense", "sparse"],
               published={"num_experts": 4})
    # w_q 8x16, w_k and w_v 8x8 each, w_o 16x8
    assert counts_kexaone.attention_weight_count(cfg) == 384
    assert counts_kexaone.ffn_weight_count(cfg, "dense", 0) == 3 * 8 * 16
    # the router over all 4 published experts, the shared expert, 2 held
    assert counts_kexaone.ffn_weight_count(cfg, "sparse", 2) == (
        8 * 4 + 3 * 8 * 6 * 3)
    # a block: the four matrices, q and k norms, two stream norms
    block = 384 + 2 * 4 + 2 * 8
    sparse = block + 8 * 4 + 3 * 8 * 6 * 3 + 4       # ... and the bias
    assert counts_kexaone.parameter_count(cfg) == (
        2 * 8 * 10 + 8                       # embedding, head, final norm
        + block + 3 * 8 * 16                 # the dense layer
        + sparse                             # the sparse layer
        + sparse + 2 * 8 * 8 + 3 * 8)        # the module
    # one route of 5 tokens: a window of 3 sees 1 + 2 + 3 + 3 + 3 keys,
    # the full layer 15, the module's block 10 over its 4 positions
    assert counts_kexaone.attention_products(
        cfg, "sliding_attention", 5) == 2 * 4 * 2 * 4 * 12
    assert counts_kexaone.attention_products(
        cfg, "full_attention", 5) == 2 * 4 * 2 * 4 * 15
    outside = 384 + 8 * 4 + 3 * 8 * 6        # a sparse block less experts
    want = (5 * 2 * 8 * 10                              # the head
            + 2 * 5 * (384 + 3 * 8 * 16) + 2 * 4 * 2 * 4 * 12
            + 2 * 5 * outside + 2 * 4 * 2 * 4 * 15
            + 4 * (2 * (2 * 8 * 8 + outside) + 2 * 8 * 10)  # the module
            + 2 * 4 * 2 * 4 * 10
            + 7 * 2 * 3 * 8 * 6)             # 7 assignments on held experts
    assert counts_kexaone.pass_flops(cfg, [5], 7.0) == want
    bare = dict(cfg, share={"mtp_held": False})
    assert counts_kexaone.pass_flops(bare, [5], 0.0) == want - (
        4 * (2 * (128 + outside) + 160) + 640 + 7 * 288)
    assert counts_kexaone.weight_bytes(cfg) == 2 * (
        counts_kexaone.parameter_count(cfg))


def test_pass_flops_of_the_cell_are_what_the_issue_counted():
    _, config, mix = R.load_cell(manifest(), CELL)
    lengths = mix["lengths"]
    tokens = sum(lengths)
    # one expected assignment of 8 on the 16 held of 128, four trunk
    # blocks and the module's n - 1 positions
    held = (4 * tokens + tokens - len(lengths)) * 8 * 16 / 128
    flops = counts_kexaone.pass_flops(config, lengths, held)
    assert abs(flops / 1e12 - 407.0) < 0.5       # ISSUE 35: 407
    full = sum(counts_kexaone.attention_products(config, "full_attention", n)
               + counts_kexaone.attention_products(config, "full_attention",
                                                   n - 1) for n in lengths)
    assert abs(full / 1e12 - 42.7) < 0.1         # ISSUE 35: 42.7
    window = 4 * sum(counts_kexaone.attention_products(
        config, "sliding_attention", n) for n in lengths)
    assert abs(window / 1e12 - 1.8) < 0.1        # ISSUE 35: 1.8
    assert abs((flops - full - window) / 1e12 - 362.5) < 0.5
    assert counts_kexaone.weight_bytes(config) == 2 * 4_543_318_144
    # per token on this chip, the attention products aside: 3.424 GFLOP
    assert abs((flops - full - window) / tokens / 1e9 - 3.424) < 0.002
