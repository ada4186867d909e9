"""``windowed_attention_step_roofline``: the reader on hand-built traces,
the count of the necessary work against a hand count at the toy shape
and at the cell's own, and the entry in the manifest."""

import pytest

from _toy import ACCEPTED_CELLS, R, both_manifests, entry_of, reported
from _toy_seq import CELL, cell_files

from benchmark import counts_seq, counts_window, peaks
from benchmark.trace import DevicePlane, Trace

NAME = "windowed_attention_step_roofline"
MS = 1e6                                 # ns
KERNEL_OPS = [("windowed_attention_step.6 bf16[2,8,16]", 1 * MS, 3 * MS,
               "other"),
              ("windowed_attention_step.4 bf16[2,8,16]", 5 * MS, 1 * MS,
               "other")]
OTHER_OPS = [("fusion.7 f32[8,16]", 7 * MS, 5 * MS, "other"),
             # the full layers' kernel is another metric's
             ("selected_attention_step.20 bf16[4,8,16]", 13 * MS, 2 * MS,
              "other")]
# the toy shape: 2 heads, key parts of 24 and 8, values of 16, a window
# of 9; routes of 13, 29, 55 and 96 tokens; layers 2, 3 and 4 slide
SEEN = sum(45 + 9 * (n - 9) for n in (13, 29, 55, 96))
FLOPS = 3 * (2 * 2 * (24 + 8 + 16) * SEEN)
BYTES = 3 * (2 * (13 + 29 + 55 + 96) * (2 * (24 + 8)        # queries
                                         + 2 * 24 + 8       # keys
                                         + 2 * 16 + 2 * 16))   # values, out


def _ctx(ops, config, mix, passes=2):
    return {"trace": Trace([DevicePlane("/device:TPU:0", list(ops), [])],
                           []),
            "lo": 0.0, "hi": 20 * MS, "counts": {"passes": passes},
            "config": config, "mix": mix, "device_kind": "TPU v5 lite"}


def _read(ctx):
    return R.load_module("metrics", NAME).read(ctx)


def test_the_necessary_work_against_a_hand_count_at_the_toy_shape():
    _, config, mix = cell_files()
    assert SEEN == 1593
    assert counts_window.window_attention_products(
        config, mix["lengths"]) == (FLOPS, BYTES) == (917_568, 213_072)
    # the flops are the part of ``attention_flops`` that is no projection
    assert counts_window.window_attention_products(config, [29])[0] == 3 * (
        counts_seq.attention_flops(config, "sliding_attention", 29)
        - 2 * 29 * counts_seq.attention_weight_count(config,
                                                     "sliding_attention"))


def test_the_necessary_work_of_the_cell_is_what_the_issue_reckoned():
    _, config, mix = R.load_cell(R.load_json(R.REPO, "BENCHMARK.json"), CELL)
    flops, nbytes = counts_window.window_attention_products(config,
                                                            mix["lengths"])
    seen = sum(min(t + 1, 513) for n in mix["lengths"] for t in range(n))
    assert seen == 50_449_959
    assert flops == 3 * 2 * 64 * 384 * seen
    assert abs(flops / 1e12 - 3 * 2.48) < 0.01     # ISSUE 36: about 2.58
    assert abs(nbytes / 1e9 - 3 * 9.244) < 0.001   # ISSUE 36: 9.1 GB
    peak = peaks.chip_peaks("TPU v5 lite")
    compute, memory = (flops / peak.bf16_flops_per_s,
                       nbytes / peak.hbm_bytes_per_s)
    assert memory < compute < 1.2 * memory         # compute governs, narrowly
    assert abs(compute - 3 * 0.01259) < 1e-4
    # the 768 keys that three tiles of 256 hold, and the padding: 2.4 x
    assert abs(111_616 * 768 / seen - 1.699) < 0.001


def test_two_kernel_operations_among_others_give_the_hand_computed_share():
    _, config, mix = cell_files()
    got = _read(_ctx(KERNEL_OPS + OTHER_OPS, config, mix))
    # at the toy widths the bytes govern: 2 passes over 4 ms of kernel
    assert BYTES / 819e9 > FLOPS / 197e12
    assert got == pytest.approx(100.0 * 2 * (213_072 / 819e9) / 4e-3)
    # the kernel's operations alone count, and only inside the window
    ctx = _ctx(KERNEL_OPS + OTHER_OPS, config, mix)
    ctx["hi"] = 3 * MS                   # 2 of the first one's 3 ms
    assert _read(ctx) == pytest.approx(100.0 * 2 * (213_072 / 819e9) / 2e-3)


def test_at_the_cells_shape_a_tenth_of_a_second_of_kernel_reads_under_100():
    _, config, mix = R.load_cell(R.load_json(R.REPO, "BENCHMARK.json"), CELL)
    ops = [("windowed_attention_step.6 bf16[64,512,128]", 0.0, 60.0 * MS,
            "other"),
           ("windowed_attention_step.4 bf16[64,512,128]", 500 * MS,
            40.0 * MS, "other"),
           ("selected_attention_step.22 bf16[128,256,128]", 1000 * MS,
            426.283 * MS, "other")]
    ctx = _ctx(ops, config, mix, passes=1)
    ctx["hi"] = 5000 * MS
    assert _read(ctx) == pytest.approx(37.76, abs=0.01)
    # nothing can read over 100: all the kernels of a pass cannot take
    # less than the necessary products at the peak
    assert 3 * 0.012587 / 0.1 < 1.0


@pytest.mark.parametrize("case", ["no-kernel", "no-operations", "no-passes"])
def test_without_the_kernel_or_a_pass_there_is_no_number(case):
    _, config, mix = cell_files()
    ops = {"no-kernel": OTHER_OPS, "no-operations": [],
           "no-passes": KERNEL_OPS}[case]
    passes = 0 if case == "no-passes" else 2
    assert _read(_ctx(ops, config, mix, passes)) is None


@both_manifests
def test_the_manifest_lists_it_for_route_lm_score_alone_of_the_accepted(m):
    fields, cells = entry_of(m, NAME)
    assert fields == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "attention",
        "moves": "od_rows_per_s"}
    assert CELL in cells
    assert [c for c in ACCEPTED_CELLS if NAME in reported(m, c)] == [CELL]
    # beside the kernel's roofline, the whole step's share of the peak
    assert "seq_mfu_pct" in reported(m, CELL)
