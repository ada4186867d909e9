"""The three readers of the held experts' grouped product —
``grouped_expert_product_roofline`` on hand-built traces and counts,
``seq_expert_fused_pct`` and ``seq_expert_rows_visited_over_held`` on
hand-made registries and on the registry a toy pass leaves —, the count
of the necessary work against a hand count at the toy shapes and at the
two cells' own, and the three entries in the manifest."""

import tempfile

import pytest

from _toy import R, both_manifests, entry_of, reported
import _toy_kexaone
import _toy_seq

from benchmark import counts_experts, counts_kexaone, counts_seq, peaks
from benchmark.trace import DevicePlane, Trace
from routest_tpu.obs import MetricsRegistry
from routest_tpu.obs import registry as reg_mod

ROOFLINE = "grouped_expert_product_roofline"
FUSED = "seq_expert_fused_pct"
ROWS = "seq_expert_rows_visited_over_held"
CELLS = (_toy_seq.CELL, _toy_kexaone.CELL)
TOYS = {_toy_seq.CELL: _toy_seq, _toy_kexaone.CELL: _toy_kexaone}
MS = 1e6                                 # ns
KERNEL_OPS = [("grouped_expert_product_up.3 bf16[64,32]", 1 * MS, 3 * MS,
               "other"),
              ("grouped_expert_product_down.5 f32[64,64]", 5 * MS, 1 * MS,
               "other")]
OTHER_OPS = [("fusion.7 f32[8,16]", 7 * MS, 5 * MS, "other"),
             # the attention kernels are other metrics'
             ("windowed_attention_step.4 bf16[2,8,16]", 13 * MS, 2 * MS,
              "other")]


@pytest.fixture
def registry():
    """An empty default registry for the length of a test."""
    old = reg_mod._default_registry
    reg_mod._default_registry = MetricsRegistry()
    yield reg_mod._default_registry
    reg_mod._default_registry = old


def _ctx(ops, config, counts):
    return {"trace": Trace([DevicePlane("/device:TPU:0", list(ops), [])],
                           []),
            "lo": 0.0, "hi": 20 * MS, "counts": counts, "config": config,
            "device_kind": "TPU v5 lite"}


def _counted(registry, blocks, real_tokens, padded=0.0):
    """What a scorer leaves in the registry: expert blocks (all fused)
    and tokens since the process began."""
    registry.counter("rtpu_seq_expert_blocks_total", "",
                     ("path",)).labels(path="fused").inc(blocks)
    tokens = registry.counter("rtpu_seq_tokens_total", "", ("kind",))
    tokens.labels(kind="real").inc(real_tokens)
    tokens.labels(kind="padded").inc(padded)


def _read(name, ctx=None):
    return R.load_module("metrics", name).read(
        ctx if ctx is not None else {"counts": {"passes": 1}})


# ── the necessary work ───────────────────────────────────────────────


@pytest.mark.parametrize("cell,blocks,held", [
    (_toy_seq.CELL, 4, 8),              # layers 1-4 of five
    (_toy_kexaone.CELL, 5, 8)])         # four sparse layers, the module
def test_the_necessary_work_against_a_hand_count_at_the_toy_shape(
        cell, blocks, held):
    _, config, _ = TOYS[cell].cell_files()
    assert counts_experts.experts_held(config) == held
    flops, nbytes = counts_experts.grouped_expert_products(
        config, 1000.0, 3 * blocks)
    # widths 64 x 32: three matrices of 2,048 an assignment
    assert flops == 1000 * 2 * 3 * 64 * 32
    assert nbytes == (3 * blocks * held * 3 * 64 * 32 * 2
                      + 1000 * 64 * (2 + 4))


def test_a_configuration_that_names_no_held_experts_is_refused():
    """No model's count stands in for another's: a configuration with
    neither key has no answer."""
    with pytest.raises(KeyError):
        counts_experts.experts_held({"model": "RouteLM", "hidden_size": 64})


def test_the_flops_are_the_part_of_a_pass_that_the_held_experts_are():
    """What ``pass_flops`` of either model adds for its held
    assignments: the same count, not a second opinion."""
    for toy, counts in ((_toy_seq, counts_seq), (_toy_kexaone,
                                                 counts_kexaone)):
        _, config, mix = toy.cell_files()
        with_held = counts.pass_flops(config, mix["lengths"], 700.0)
        without = counts.pass_flops(config, mix["lengths"], 0.0)
        assert counts_experts.grouped_expert_products(
            config, 700.0, 20.0)[0] == pytest.approx(with_held - without)


@pytest.mark.parametrize("cell,blocks,held,assignments,tflop,steps", [
    ("route-lm-score", 4, 32, 409_800, 19.3, 8),
    ("route-lm-kexaone-mixed", 5, 16, 529_000, 40.0, 8)])
def test_the_necessary_work_of_the_cells_is_what_the_issue_reckoned(
        cell, blocks, held, assignments, tflop, steps):
    """ISSUE 38: 409.8k held assignments x 47.2 MFLOP = 19.3 TFLOP a pass
    of ``route-lm-score``, 40 TFLOP on held experts in
    ``route-lm-kexaone-mixed`` (529k assignments of 75.5 MFLOP); the
    compute bound governs both, so the bytes never decide the share."""
    _, config, _ = R.load_cell(R.load_json(R.REPO, "BENCHMARK.json"), cell)
    assert counts_experts.experts_held(config) == held
    flops, nbytes = counts_experts.grouped_expert_products(
        config, float(assignments), float(blocks * steps))
    assert abs(flops / 1e12 - tflop) < 0.1
    peak = peaks.chip_peaks("TPU v5 lite")
    compute, memory = (flops / peak.bf16_flops_per_s,
                       nbytes / peak.hbm_bytes_per_s)
    assert memory < compute < 2.5 * memory


# ── the kernels' roofline ────────────────────────────────────────────


def test_the_blocks_of_a_pass_are_the_counted_ones_over_the_passes(registry):
    """Three passes counted (a warm-up and two timed) of 12 expert blocks
    and 500 real tokens each: the blocks of one pass, whatever the
    padding and whatever form the blocks took."""
    block_steps = R.load_module("metrics", ROOFLINE).block_steps
    assert block_steps({"tokens_real": 500}) is None         # no families
    _counted(registry, 30.0, 1500.0, padded=321.0)
    registry.get("rtpu_seq_expert_blocks_total").labels(path="xla").inc(6.0)
    assert block_steps({"tokens_real": 500}) == pytest.approx(12.0)
    assert block_steps({}) is None          # a driver that reports none


def test_two_kernel_operations_among_others_give_the_hand_computed_share(
        registry):
    _, config, _ = _toy_seq.cell_files()
    counts = {"passes": 2, "held_assignments": 1000.0, "steps": 3,
              "tokens_real": 500}
    _counted(registry, 3 * 12.0, 3 * 500.0)          # three passes of 12
    flops, nbytes = counts_experts.grouped_expert_products(config, 1000.0,
                                                           12.0)
    # at the toy widths the bytes govern: 2 passes over 4 ms of kernels
    assert nbytes / 819e9 > flops / 197e12
    got = _read(ROOFLINE, _ctx(KERNEL_OPS + OTHER_OPS, config, counts))
    assert got == pytest.approx(100.0 * 2 * (nbytes / 819e9) / 4e-3)
    # the kernels' operations alone count, and only inside the window
    ctx = _ctx(KERNEL_OPS + OTHER_OPS, config, counts)
    ctx["hi"] = 3 * MS                   # 2 of the first one's 3 ms
    assert _read(ROOFLINE, ctx) == pytest.approx(
        100.0 * 2 * (nbytes / 819e9) / 2e-3)


def test_at_a_cells_shape_the_predicted_kernel_time_reads_under_100(
        registry):
    _, config, _ = R.load_cell(R.load_json(R.REPO, "BENCHMARK.json"),
                               "route-lm-kexaone-mixed")
    _counted(registry, 2 * 40.0, 2 * 96_000.0)    # five blocks, 8 steps
    ops = [("grouped_expert_product_up.11 bf16[8192,2048]", 0.0, 220.0 * MS,
            "other"),
           ("grouped_expert_product_down.12 f32[8192,6144]", 500 * MS,
            110.0 * MS, "other")]
    ctx = _ctx(ops, config, {"passes": 1, "held_assignments": 529_000.0,
                             "steps": 8, "tokens_real": 96_000})
    ctx["hi"] = 5000 * MS
    # 39.93 TFLOP at 197 TFLOP/s = 0.2027 s of 0.33
    assert _read(ROOFLINE, ctx) == pytest.approx(61.4, abs=0.1)


@pytest.mark.parametrize("case", ["no-kernel", "no-operations", "no-passes",
                                  "no-held-assignments", "no-counted-blocks",
                                  None])
def test_without_the_kernels_or_a_pass_there_is_no_number(registry, case):
    _, config, _ = _toy_seq.cell_files()
    ops = {"no-kernel": OTHER_OPS, "no-operations": []}.get(case, KERNEL_OPS)
    counts = {"passes": 0 if case == "no-passes" else 2,
              "held_assignments": 1000.0, "steps": 3, "tokens_real": 500}
    if case == "no-held-assignments":    # a driver that reports none
        del counts["held_assignments"]
    if case != "no-counted-blocks":      # a program without the counter
        _counted(registry, 36.0, 1500.0)
    assert (_read(ROOFLINE, _ctx(ops, config, counts)) is None) == (
        case is not None)


# ── the two counters' readers ────────────────────────────────────────


def test_without_the_counters_there_are_no_numbers(registry):
    assert _read(FUSED) is None and _read(ROWS) is None   # no families
    registry.counter("rtpu_seq_expert_blocks_total", "", ("path",))
    rows = registry.counter("rtpu_seq_expert_rows_total", "", ("kind",))
    assert _read(FUSED) is None and _read(ROWS) is None   # nothing counted
    # the sliding layers' counter is another metric's
    registry.counter("rtpu_seq_window_blocks_total", "",
                     ("path",)).labels(path="fused").inc(654.0)
    assert _read(FUSED) is None
    rows.labels(kind="held").inc(100.0)       # one kind alone
    assert _read(ROWS) is None


@pytest.mark.parametrize("fused,xla,want", [
    (40.0, 0.0, 100.0), (0.0, 32.0, 0.0), (10.0, 30.0, 25.0)])
def test_the_share_is_the_fused_blocks_of_all(registry, fused, xla, want):
    family = registry.counter("rtpu_seq_expert_blocks_total", "", ("path",))
    if fused:
        family.labels(path="fused").inc(fused)
    if xla:
        family.labels(path="xla").inc(xla)
    assert _read(FUSED) == want


@pytest.mark.parametrize("visited,held,want", [
    (117_760.0, 102_400.0, 1.15), (5000.0, 5000.0, 1.0)])
def test_the_ratio_is_the_visited_rows_over_the_held(registry, visited, held,
                                                     want):
    family = registry.counter("rtpu_seq_expert_rows_total", "", ("kind",))
    family.labels(kind="visited").inc(visited)
    family.labels(kind="held").inc(held)
    assert _read(ROWS) == pytest.approx(want)


@pytest.mark.parametrize("cell,per_step", [(_toy_seq.CELL, 4),
                                           (_toy_kexaone.CELL, 5)])
def test_a_toy_pass_on_the_cpu_reads_no_kernel_and_no_padding(registry, cell,
                                                              per_step):
    """The toy widths are no whole lanes and the backend is no TPU:
    every expert block of the pass takes ``ragged_dot``, which multiplies
    the held rows alone, and the readers say so."""
    from routest_tpu.serve import seq_score

    seq_score._metrics = None        # the scorer's families, made anew
    try:
        _, config, mix = TOYS[cell].cell_files()
        mod = R.load_module("drivers", mix["driver"])
        driver = mod.Driver(R.Run(5, config, mix, R.REPO, tempfile.mkdtemp(
            prefix="routest-benchmark-test-")))
        assert _read(FUSED) == 0.0
        assert _read(ROWS) == 1.0
        blocks = registry.get("rtpu_seq_expert_blocks_total")
        assert {k[0]: c.value for k, c in blocks.items()} == {
            "xla": per_step * len(driver.plan)}
        # the warm-up pass is all the scorer has counted; a timed one
        # more leaves the blocks of ONE pass what they were
        block_steps = R.load_module("metrics", ROOFLINE).block_steps
        real = sum(step.real_tokens for step in driver.plan)
        assert block_steps({"tokens_real": real}) == (
            per_step * len(driver.plan))
        driver.window(0.0)
        assert driver.counts()["tokens_real"] == real
        assert block_steps(driver.counts()) == per_step * len(driver.plan)
        assert sum(c.value for _, c in blocks.items()) == (
            (1 + len(driver.durations)) * per_step * len(driver.plan))
    finally:
        seq_score._metrics = None


# ── the manifest ─────────────────────────────────────────────────────


@both_manifests
@pytest.mark.parametrize("name,unit,better,source", [
    (ROOFLINE, "%", "higher", "device_trace"),
    (FUSED, "%", "higher", "program_counter"),
    (ROWS, "ratio", "lower", "program_counter")])
def test_the_manifest_lists_them_for_the_two_expert_cells(m, name, unit,
                                                          better, source):
    """Their own fields and their own two cells; nothing about their
    place in the list nor about which later cells join them."""
    fields, cells = entry_of(m, name)
    assert fields == {"name": name, "unit": unit, "better": better,
                      "source": source, "layer": "expert layer",
                      "moves": "od_rows_per_s"}
    for cell in ("route-lm-score", "route-lm-kexaone-mixed"):
        assert cell in cells and name in reported(m, cell)
        # beside a kernel's roofline, the whole step's share of the peak
        assert "seq_mfu_pct" in reported(m, cell)
    for cell in ("od-score", "gnn-refit", "route-lm-sala-long"):
        assert name not in reported(m, cell)
