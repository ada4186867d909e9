"""The ``route_scan_gigachat`` driver end to end at a toy size on the
CPU, skipping only the harness's look for a chip: the reference agrees
with the program in both likelihood columns, the control and every
planted fault come out as not correct — each fault by the gap the mix
names, and the fault in the module's input by the module's column alone
—, the counts are a hand count, the cell's two own readers give numbers
and give nothing where the program left nothing."""

import tempfile
import time

import jax
import numpy as np
import pytest

from _toy import both_manifests, reported
from _toy_gigachat import CELL, R, cell_files, manifest

from benchmark import (compare, counts_gigachat, faults_gigachat, seq_spans,
                       traffic_seq)

# the readers the cell joins (BENCHMARK.json: its name appended to their
# ``workloads``) and its own two
JOINED = ["seq_mfu_pct", "device_idle_pct.seq", "seq_padded_token_pct",
          "seq_step_host_pct", "seq_step_device_gap_pct",
          "seq_pass_unaccounted_pct", "seq_longest_class_us_per_token",
          "seq_shortest_class_us_per_token", "host_cpu_stall_ms.seq",
          "setup_compile_s", "setup_trace_lower_s",
          "seq_expert_load_max_over_mean", "grouped_expert_product_roofline",
          "seq_expert_fused_pct", "seq_expert_rows_visited_over_held"]
OWN = {"latent_full_visited_over_needed":
       ("ratio", "lower", "program_counter", "attention"),
       "seq_expert_group_hit_pct":
       ("%", "lower", "program_counter", "expert layer")}
FAMILY = {"latent_full_visited_over_needed": "rtpu_seq_latent_keys_total",
          "seq_expert_group_hit_pct": "rtpu_seq_expert_group_tokens_total"}
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
    result = R.execute(manifest(), cell, config, mix, 2 ** 31 + 19, 0.2,
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
    # steps of one and two routes in four length classes
    assert sorted({len(s.routes) for s in driver.plan}) == [1, 2]
    assert len({s.length for s in driver.plan}) == 4
    # the module's block is the last row and one position short
    w = want[0]
    assert len(w["n_keys"]) == 6 and len(w["chosen"]) == 5
    assert len(w["n_keys"][-1]) == len(w["lse"]) - 1 == len(w["mtp_lse"])
    # what the last TIMED pass wrote is what is compared
    assert driver.attempted >= 1 and driver.scores is not None


@pytest.mark.parametrize("fault", sorted(faults_gigachat.FAULTS))
def test_a_planted_fault_is_caught_by_the_gap_the_mix_names(sound, fault):
    driver, want, own = sound
    with faults_gigachat.FAULTS[fault]():
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
    if fault == "causal_off_by_one":
        assert numbers["key_set_gap"] > 0.9
    else:
        assert numbers["key_set_gap"] == 0.0


def test_the_mix_names_a_gap_for_every_fault():
    _, _, mix = R.load_cell(manifest(), CELL)
    assert set(mix["faults"]) == set(faults_gigachat.FAULTS)
    assert set(mix["faults"].values()) <= set(mix["limits"])
    assert set(mix["limit_reasons"]) >= set(mix["limits"])
    assert mix["faults"]["causal_off_by_one"] == "key_set_gap"
    assert mix["faults"]["groups_unlimited"] == "expert_gap"
    assert mix["faults"]["module_fed_this_token"].startswith("mtp_")
    assert mix["control"] == "fp8"


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
    # the reference's own choices that land on the held experts 0-1
    held = sum(int((np.asarray(ch) < 2).sum()) for w in want
               for ch in w["chosen"])
    assert abs(c["held_assignments"] - held) <= 0.1 * held
    assert c["flops"] == c["passes"] * counts_gigachat.pass_flops(
        driver.cfg, lengths, c["held_assignments"])
    ctx = {"counts": c, "device_kind": "TPU v5 lite", "chips": 1}
    assert 0.0 < seq_spans.host_pct(ctx) < 100.0
    assert R.load_module("metrics", "seq_mfu_pct").read(ctx) > 0.0
    assert 0.0 <= R.load_module("metrics",
                                "seq_padded_token_pct").read(ctx) < 100.0
    assert R.load_module("metrics",
                         "seq_expert_load_max_over_mean").read(ctx) >= 1.0
    visited = R.load_module("metrics",
                            "latent_full_visited_over_needed").read(ctx)
    hit = R.load_module("metrics", "seq_expert_group_hit_pct").read(ctx)
    # whole chunks of 16 keys on the diagonal of routes of 13-96 arcs;
    # a token keeps 4 of 8 groups and takes 4 experts of those 16
    assert 1.0 < visited < 2.0 and 20.0 < hit < 60.0
    # the share of tokens that chose into group 0, from the reference
    in_group = sum(int((np.asarray(ch) // 4 == 0).any(-1).sum())
                   for w in want for ch in w["chosen"])
    tokens = sum(len(ch) for w in want for ch in w["chosen"])
    assert abs(hit - 100.0 * in_group / tokens) < 2.0


@pytest.mark.parametrize("name", sorted(OWN))
def test_a_reader_gives_nothing_where_the_program_left_nothing(name):
    from routest_tpu.obs import MetricsRegistry
    from routest_tpu.obs import registry as reg_mod

    old = reg_mod._default_registry
    try:
        reg_mod._default_registry = MetricsRegistry()
        ctx = {"counts": {"passes": 2}, "device_kind": "TPU v5 lite",
               "chips": 1}
        read = R.load_module("metrics", name).read
        assert read(ctx) is None
        # a family that has counted nothing, and one with one kind alone
        family = reg_mod._default_registry.counter(FAMILY[name], "",
                                                   ("kind",))
        assert read(ctx) is None
        family.labels(kind="visited").inc(5)
        family.labels(kind="held_group").inc(5)
        assert read(ctx) is None
        family.labels(kind="needed").inc(4)
        family.labels(kind="all").inc(20)
        assert read(ctx) == {"latent_full_visited_over_needed": 1.25,
                             "seq_expert_group_hit_pct": 25.0}[name]
    finally:
        reg_mod._default_registry = old


@both_manifests
def test_the_manifest_lists_the_cell_for_its_metrics_and_no_older_cell(m):
    """The cell is IN the lists of the metrics it joins and of its own
    two, each of which moves what the issue says; its own two are
    reported by no older cell. Nothing about the lists' other members,
    the entries' places or any other name."""
    e2e = [x["name"] for x in R.metrics_of(m, "end_to_end", CELL)]
    assert {"od_rows_per_s", "setup_s"} <= set(e2e)
    assert set(JOINED) | set(OWN) <= set(reported(m, CELL))
    for x in m["per_layer"]:
        if x["name"] in JOINED or x["name"] in OWN:
            assert CELL in x["workloads"]
            assert x["moves"] == ("setup_s" if x["name"].startswith("setup_")
                                  else "od_rows_per_s")
        if x["name"] in OWN:
            assert (x["unit"], x["better"], x["source"],
                    x["layer"]) == OWN[x["name"]]
            assert x["workloads"][0] == CELL
    for cell in ("od-score", "gnn-refit", "route-lm-score",
                 "route-lm-sala-long", "route-lm-kexaone-mixed"):
        assert not set(OWN) & set(reported(m, cell))
    (cell,) = [w for w in m["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "gigachat3.1-702b-ep16", "route-histories-2k-26k", 1)
    (config,) = [c for c in m["configs"] if c["name"] == cell["config"]]
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["source"].endswith(
        "ai-sage/GigaChat3.1-702B-A36B/blob/main/config.json")
    assert "5,277 M" in config["why"]


# ── the traffic ──────────────────────────────────────────────────────


def test_the_cells_lengths_are_the_quantiles_the_mix_states():
    from routest_tpu.models.route_lm_gigachat import RouteLMGigaChat
    from routest_tpu.serve.seq_score import plan_pass

    _, config, mix = R.load_cell(manifest(), CELL)
    lengths = traffic_seq.route_lengths(mix)
    assert lengths == mix["lengths"] == [
        2590, 3966, 5109, 6255, 7502, 8945, 10728, 13135, 16923, 25908]
    assert sum(lengths) == 101061 and mix["max_step_tokens"] == 32768
    assert (mix["n_routes"], mix["length_median"], mix["length_sigma"],
            mix["length_min"], mix["length_max"], mix["max_classes"],
            mix["named_rows"], mix["trace_seconds"]) == (
                10, 8192, 0.7, 2048, 32768, 8, 4, 1)
    # every route past 2,048 arcs, 61% of the tokens past YaRN's 4,096
    past = sum(max(n - 4096, 0) for n in lengths) / sum(lengths)
    assert min(lengths) > 2048 and 0.61 < past < 0.612
    tail, _, _ = traffic_seq.grid_arcs(config["vocab_size"])
    assert len(tail) == 15624 <= config["vocab_size"] == 16032
    m = RouteLMGigaChat.from_config(config)
    plan = plan_pass(lengths, m.length_quantum, mix["max_step_tokens"],
                     mix["max_classes"])
    assert [(len(s.routes), s.length) for s in plan] == [
        (1, 26112), (1, 17152), (1, 13312), (1, 10752), (2, 8960), (1, 6400),
        (2, 5120), (1, 2816)]
    assert sum(s.padded_tokens for s in plan) == 3643        # 3.48%
    blocks = mix["reference_blocks"]
    assert max(lengths) <= max(blocks["pad_to"])
    assert all(p % blocks["q_block"] == 0 and p % blocks["row_block"] == 0
               for p in blocks["pad_to"])
    assert config["num_attention_heads"] % blocks["head_group"] == 0


# ── the counts ───────────────────────────────────────────────────────


def test_counts_against_a_hand_count_at_a_small_shape():
    cfg = dict(hidden_size=8, intermediate_size=16, num_attention_heads=2,
               q_lora_rank=4, kv_lora_rank=3, qk_nope_head_dim=4,
               qk_rope_head_dim=2, v_head_dim=6, moe_intermediate_size=6,
               n_routed_experts=2, n_shared_experts=1, num_experts_per_tok=2,
               vocab_size=10, num_hidden_layers=2, first_k_dense_replace=3,
               num_nextn_predict_layers=1,
               published={"n_routed_experts": 4})
    # w_dq 8x4, w_uq 4x2x6, w_dkv 8x5, w_ukv 3x2x10, w_o 12x8
    attention = 32 + 48 + 40 + 60 + 96
    assert counts_gigachat.attention_weight_count(cfg) == attention == 276
    assert counts_gigachat.ffn_weight_count(cfg, "dense", 0) == 3 * 8 * 16
    # the router over all 4 published experts, the shared expert, 2 held
    assert counts_gigachat.ffn_weight_count(cfg, "sparse", 2) == (
        8 * 4 + 3 * 8 * 6 * 3)
    # a block: the five matrices, the two latents' norms, two stream norms
    block = attention + 4 + 3 + 2 * 8
    sparse = block + 8 * 4 + 3 * 8 * 6 * 3 + 4       # ... and the bias
    assert counts_gigachat.parameter_count(cfg) == (
        2 * 8 * 10 + 8                       # embedding, head, final norm
        + block + 3 * 8 * 16                 # the dense layer
        + sparse                             # the expert layer
        + sparse + 2 * 8 * 8 + 3 * 8)        # the module
    # one route of 5 tokens: 15 (query, key) pairs a block, 10 over the
    # module's 4 positions; 2 heads x 2 x (4 + 2 + 6) a pair
    assert counts_gigachat.attention_products(cfg, 5) == 2 * 24 * 15
    outside = attention + 8 * 4 + 3 * 8 * 6   # an expert block less experts
    want = (5 * 2 * 8 * 10                              # the head
            + 2 * 5 * (attention + 3 * 8 * 16) + 2 * 24 * 15
            + 2 * 5 * outside + 2 * 24 * 15
            + 4 * (2 * (2 * 8 * 8 + outside) + 2 * 8 * 10)  # the module
            + 2 * 24 * 10
            + 7 * 2 * 3 * 8 * 6)             # 7 assignments on held experts
    assert counts_gigachat.pass_flops(cfg, [5], 7.0) == want
    bare = dict(cfg, share={"mtp_held": False})
    assert counts_gigachat.pass_flops(bare, [5], 0.0) == want - (
        4 * (2 * (128 + outside) + 160) + 480 + 7 * 288)
    assert counts_gigachat.weight_bytes(cfg) == 2 * (
        counts_gigachat.parameter_count(cfg))


def test_pass_flops_of_the_cell_are_what_the_issue_counted():
    _, config, mix = R.load_cell(manifest(), CELL)
    lengths = mix["lengths"]
    tokens = sum(lengths)
    # one expected assignment of 8 on the 16 held of 256, four trunk
    # blocks and the module's n - 1 positions
    held = (4 * tokens + tokens - len(lengths)) * 8 * 16 / 256
    flops = counts_gigachat.pass_flops(config, lengths, held)
    assert abs(flops / 1e12 - 594.0) < 1.0       # ISSUE 39: 594
    dense = sum(5 * counts_gigachat.attention_products(config, n)
                + counts_gigachat.attention_products(config, n - 1)
                for n in lengths)
    assert abs(dense / 1e12 - 217.0) < 0.5       # ISSUE 39: 217 (37%)
    assert abs((flops - dense) / 1e12 - 377.0) < 0.5
    assert 0.36 < dense / flops < 0.37
    # the longest route alone in one block: 17 TFLOP
    assert abs(counts_gigachat.attention_products(config, 26112) / 1e12
               - 16.8) < 0.1
    assert counts_gigachat.weight_bytes(config) == 2 * 5_277_152_512
