"""The ``route_scan_falcon`` driver end to end at a toy size on the CPU,
skipping only the harness's look for a chip: the reference agrees with
the program, the control and every planted fault come out as not
correct — each fault by the gap the mix names —, the counts are a hand
count and the issue's, the cell's two own readers give numbers and give
nothing where the program left nothing, and the manifest lists the cell
where the issue says."""

import tempfile
import time

import jax
import numpy as np
import pytest

from _toy import both_manifests, reported
from _toy_falcon import CELL, R, cell_files, manifest

from benchmark import compare, counts_falcon, faults_falcon, traffic_seq
from benchmark.trace import DevicePlane, Trace

# the readers the cell joins (BENCHMARK.json: its name appended to their
# ``workloads``) and its own two
JOINED = ["seq_mfu_pct", "device_idle_pct.seq", "seq_padded_token_pct",
          "seq_step_host_pct", "seq_step_device_gap_pct",
          "seq_pass_unaccounted_pct", "seq_longest_class_us_per_token",
          "seq_shortest_class_us_per_token", "host_cpu_stall_ms.seq",
          "setup_compile_s", "setup_trace_lower_s",
          "gqa_full_visited_over_needed"]
OWN = {"ssd_scan_step_roofline":
       ("%", "higher", "device_trace", "state-space mixer"),
       "seq_ssm_fused_pct":
       ("%", "higher", "program_counter", "state-space mixer")}
GAPS = {"logit_gap", "lse_gap", "rows_gap", "loglik_gap", "state_gap",
        "key_set_gap"}


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
    result = R.execute(manifest(), cell, config, mix, 2 ** 31 + 29, 0.2,
                       False, jax.devices()[:1], time.perf_counter())
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    want = {m["name"] for m in R.metrics_of(manifest(), "end_to_end", CELL)}
    assert set(result["metrics"]) == want == {"od_rows_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert result["compiles"]["window"] == 0
    assert set(result["checks"]) == set(mix["limits"]) == GAPS


def test_the_program_is_inside_every_limit(sound):
    driver, want, numbers = sound
    checks = compare.with_limits(numbers, driver.mix["limits"])
    assert compare.verdict(checks), compare.as_lines(checks)
    assert numbers["key_set_gap"] == 0.0
    # steps of one and two routes in four length classes
    assert sorted({len(s.routes) for s in driver.plan}) == [1, 2]
    assert len({s.length for s in driver.plan}) == 4
    # three blocks, each with its state and a key count a token
    w = want[0]
    assert len(w["state"]) == len(w["n_keys"]) == 3
    assert w["state"][0].shape == (16, 8, 16)


@pytest.mark.parametrize("fault", sorted(faults_falcon.FAULTS))
def test_a_planted_fault_is_caught_by_the_gap_the_mix_names(sound, fault):
    driver, want, _ = sound
    with faults_falcon.FAULTS[fault]():
        faulty = _driver()
        faulty.window(0.01)
    numbers = faulty.gaps(faulty.program_routes(), want)
    checks = compare.with_limits(numbers, faulty.mix["limits"])
    assert not compare.verdict(checks), numbers
    named = driver.mix["faults"][fault]
    assert named in [c.name for c in checks if not c.ok], numbers


def test_the_mix_names_a_gap_for_every_fault():
    _, _, mix = R.load_cell(manifest(), CELL)
    assert set(mix["faults"]) == set(faults_falcon.FAULTS)
    assert set(mix["faults"].values()) <= set(mix["limits"])
    assert set(mix["limit_reasons"]) >= set(mix["limits"]) == GAPS


def test_control_in_fp8_comes_out_not_correct(sound):
    driver, want, _ = sound
    control = driver.gaps(driver.reference(driver.mix["control"]), want)
    assert not compare.verdict(compare.with_limits(control,
                                                   driver.mix["limits"]))


def test_the_controls_rounding_is_float8_e4m3_to_the_bit():
    from benchmark.reference import falcon_h1_ref
    from benchmark.reference.dots3_ref import _fp8

    rng = np.random.default_rng(0)
    x = (np.exp(rng.uniform(-20.0, 3.0, 1 << 16))
         * np.sign(rng.standard_normal(1 << 16))).astype(np.float32)
    got = np.asarray(falcon_h1_ref.e4m3(x))
    # the same numbers as the cast to float8 and back, subnormals and
    # ties included, where the backend keeps that cast
    np.testing.assert_array_equal(got, np.asarray(_fp8(x)))
    assert 0.01 < np.linalg.norm(got - x) / np.linalg.norm(x) < 0.05


def test_counts_and_readers(sound):
    driver, _, _ = sound
    c = driver.counts()
    lengths = driver.table["lengths"]
    assert c["passes"] == len(driver.durations) >= 1
    assert c["tokens_real"] == int(lengths.sum())
    assert c["flops"] == c["passes"] * counts_falcon.pass_flops(
        driver.cfg, lengths)
    ctx = {"counts": c, "device_kind": "TPU v5 lite", "chips": 1}
    assert R.load_module("metrics", "seq_mfu_pct").read(ctx) > 0.0
    # the XLA form here: the kernel's share reads 0, its roofline nothing
    assert R.load_module("metrics", "seq_ssm_fused_pct").read(ctx) == 0.0
    assert R.load_module("metrics",
                         "gqa_full_visited_over_needed").read(ctx) > 1.0


@pytest.mark.parametrize("name", sorted(OWN))
def test_a_reader_gives_nothing_where_the_program_left_nothing(name):
    from routest_tpu.obs import MetricsRegistry
    from routest_tpu.obs import registry as reg_mod

    old = reg_mod._default_registry
    _, config, mix = cell_files()
    try:
        reg_mod._default_registry = MetricsRegistry()
        ctx = {"counts": {"passes": 2}, "device_kind": "TPU v5 lite",
               "chips": 1, "config": config, "mix": mix,
               "trace": Trace([DevicePlane("/device:TPU:0", [
                   ("fusion.7 f32[8,16]", 1e6, 5e6, "other")], [])], []),
               "lo": 0.0, "hi": 2e7}
        read = R.load_module("metrics", name).read
        assert read(ctx) is None
        family = reg_mod._default_registry.counter(
            "rtpu_seq_ssm_chunks_total", "", ("path",))
        assert read(ctx) is None
        if name == "seq_ssm_fused_pct":
            family.labels(path="fused").inc(30)
            family.labels(path="xla").inc(10)
            assert read(ctx) == 75.0
    finally:
        reg_mod._default_registry = old


def test_the_roofline_reader_on_a_hand_built_trace():
    """Two kernel launches of 3 and 1 ms in the window, another op
    beside them: the necessary work of two passes over 4 ms."""
    _, config, mix = cell_files()
    ms = 1e6
    ops = [("ssd_scan_step.3 bf16[2,96,128]", 1 * ms, 3 * ms, "other"),
           ("ssd_scan_step.5 bf16[1,96,128]", 5 * ms, 1 * ms, "other"),
           ("fusion.7 f32[8,16]", 7 * ms, 5 * ms, "other")]
    ctx = {"trace": Trace([DevicePlane("/device:TPU:0", ops, [])], []),
           "lo": 0.0, "hi": 20 * ms, "counts": {"passes": 2},
           "config": config, "mix": mix, "device_kind": "TPU v5 lite"}
    flops, nbytes = counts_falcon.ssd_scan_products(config, mix["lengths"])
    least = 2 * max(flops / 197e12, nbytes / 819e9)
    got = R.load_module("metrics", "ssd_scan_step_roofline").read(ctx)
    assert got == pytest.approx(100.0 * least / 4e-3)


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
                 "route-lm-sala-long", "route-lm-kexaone-mixed",
                 "route-lm-gigachat-dense"):
        assert not set(OWN) & set(reported(m, cell))
    (cell,) = [w for w in m["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "falcon-h1-34b-l0-7", "route-histories-1k-15k", 1)
    (config,) = [c for c in m["configs"] if c["name"] == cell["config"]]
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["source"].endswith(
        "tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json")
    assert "3,775 M" in config["why"]


# ── the configuration and the traffic ───────────────────────────────


def test_the_configuration_keeps_every_published_width():
    _, config, _ = R.load_cell(manifest(), CELL)
    published = {
        "hidden_size": 5120, "intermediate_size": 21504,
        "num_attention_heads": 20, "num_key_value_heads": 4, "head_dim": 128,
        "mamba_n_heads": 32, "mamba_d_head": 128, "mamba_d_ssm": 4096,
        "mamba_n_groups": 2, "mamba_d_state": 256, "mamba_d_conv": 4,
        "mamba_chunk_size": 128, "rope_theta": 100000000000,
        "ssm_in_multiplier": 0.25, "ssm_out_multiplier": 0.08838834764831845,
        "attention_out_multiplier": 0.0375, "lm_head_multiplier": 0.0078125}
    assert {k: config[k] for k in published} == published
    assert (config["num_hidden_layers"], config["vocab_size"]) == (8, 32640)
    assert config["published"] == {"num_hidden_layers": 72,
                                   "vocab_size": 261120}
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert counts_falcon.parameter_count(config) == config[
        "parameters_held"] == 3_775_198_976


def test_the_cells_lengths_are_the_quantiles_the_mix_states():
    _, config, mix = R.load_cell(manifest(), CELL)
    lengths = traffic_seq.route_lengths(mix)
    assert lengths == mix["lengths"] == [
        1024, 1024, 1224, 1454, 1679, 1904, 2137, 2381, 2641, 2922, 3230,
        3574, 3964, 4416, 4956, 5622, 6488, 7711, 9718, 14736]
    assert sum(lengths) == 82805 and mix["max_step_tokens"] == 32768
    assert (mix["n_routes"], mix["length_median"], mix["length_sigma"],
            mix["length_min"], mix["length_max"], mix["max_classes"],
            mix["named_rows"]) == (20, 3072, 0.8, 1024, 16384, 8, 4)
    # a 90 x 90 grid's directed segments, inside the held rows
    tail, _, _ = traffic_seq.grid_arcs(config["vocab_size"])
    assert len(tail) == 32040 <= config["vocab_size"]
    # the scan carries a state over 8-116 chunks of 128
    assert [-(-n // 128) for n in (min(lengths), max(lengths))] == [8, 116]
    pads = mix["reference_blocks"]["pad_to"]
    assert all(min(p for p in pads if p >= n) % 2048 == 0 for n in lengths)


# ── the counts ───────────────────────────────────────────────────────


def test_counts_against_a_hand_count_at_a_small_shape():
    cfg = dict(hidden_size=8, intermediate_size=16, head_dim=4,
               num_attention_heads=4, num_key_value_heads=2,
               mamba_n_heads=4, mamba_d_head=2, mamba_d_ssm=8,
               mamba_n_groups=2, mamba_d_state=3, mamba_d_conv=4,
               mamba_chunk_size=4, num_hidden_layers=2, vocab_size=10)
    # q 8x16, k and v 8x8 each (two heads of 4), o 16x8
    assert counts_falcon.attention_weight_count(cfg) == 128 + 64 + 64 + 128
    # in: z 8x8, xBC 8x(8 + 12), dt 8x4; out 8x8
    assert counts_falcon.ssm_weight_count(cfg) == 64 + 160 + 32 + 64
    # + conv 4x20 taps and 20 biases, norm 8, dt_bias/A_log/D 12, MLP
    # 3 x 8 x 16, two norms 16
    block = 384 + 320 + 100 + 8 + 12 + 384 + 16
    assert counts_falcon.block_parameter_count(cfg) == block
    assert counts_falcon.parameter_count(cfg) == 2 * block + 160 + 8
    # a route of 6: one whole chunk (places 0-3) and two of the next
    flops, nbytes = counts_falcon.ssd_scan_products(cfg, [6])
    places = 1 + 2 + 3 + 4 + 1 + 2
    assert flops == 2 * ((2 * 2 * 3 + 4 * 2 * 2) * places
                         + 4 * 4 * 3 * 2 * 6)
    assert nbytes == 2 * (6 * (2 * (2 * 4 * 2 + 2 * 2 * 3) + 4 * 4)
                          + 4 * 4 * 2 * 3)
    # per token and block: 2 x (attention's 384 + the mixer's 320) and
    # the MLP's 2 x 3 x 8 x 16; the head 2 x 8 x 10; 21 causal keys a
    # block, 4 heads x (2 x 4 + 2 x 4) a key; and the scan
    assert counts_falcon.pass_flops(cfg, [6]) == (
        6 * 2 * (2 * 704 + 768) + 6 * 2 * 8 * 10 + 2 * 4 * 16 * 21 + flops)


def test_pass_flops_of_the_cell_are_what_the_issue_counted():
    _, config, mix = R.load_cell(manifest(), CELL)
    lengths = mix["lengths"]
    assert abs(counts_falcon.pass_flops(config, lengths) / 1e12 - 624) < 0.5
    attention = 8 * sum(counts_falcon.attention_products(config, n)
                        for n in lengths)
    assert abs(attention / 1e12 - 23.2) < 0.1
    flops, nbytes = counts_falcon.ssd_scan_products(config, lengths)
    assert abs(flops / 1e12 - 3.17) < 0.01
    assert abs(nbytes / 1e9 - 12.97) < 0.01
    # at the bf16 peak a pass takes ~3.2 s
    assert abs(counts_falcon.pass_flops(config, lengths) / 197e12 - 3.17) \
        < 0.01
    # the MLP is 77% of a block's matrices, the state-space mixer's 16%
    mlp = 3 * 5120 * 21504
    matrices = (mlp + counts_falcon.ssm_weight_count(config)
                + counts_falcon.attention_weight_count(config))
    assert round(100 * mlp / matrices) == 77
    assert round(100 * counts_falcon.ssm_weight_count(config) / matrices) \
        == 16
    assert counts_falcon.weight_bytes(config) == 2 * 3_775_198_976
    assert np.isclose(counts_falcon.weight_bytes(config) / 2 ** 30, 7.03,
                      atol=0.01)
