"""``selected_attention_step_roofline``: the reader on hand-built traces,
the count of the necessary work against a hand count at the toy shape
and at the cell's own, and the entry in the manifest."""

import pytest

from _toy import ACCEPTED_CELLS, R, both_manifests, entry_of, reported
from _toy_seq import CELL, cell_files

from benchmark import counts_seq, peaks
from benchmark.trace import DevicePlane, Trace

NAME = "selected_attention_step_roofline"
MS = 1e6                                 # ns
KERNEL_OPS = [("selected_attention_step.22 bf16[4,8,16]", 1 * MS, 3 * MS,
               "other"),
              ("selected_attention_step.20 bf16[4,8,16]", 5 * MS, 1 * MS,
               "other")]
OTHER_OP = ("fusion.7 f32[8,16]", 7 * MS, 5 * MS, "other")
# the toy shape: 4 heads, key parts of 16 and 8, values of 16, 16 keys
# selected; routes of 13, 29, 55 and 96 tokens; layers 0 and 1 are full
SEEN = 13 * 14 // 2 + (136 + 13 * 16) + (136 + 39 * 16) + (136 + 80 * 16)
FLOPS = 2 * (2 * 4 * (16 + 8 + 16) * SEEN)
BYTES = 2 * (2 * (13 + 29 + 55 + 96) * (4 * (16 + 8)        # queries
                                         + 4 * 16 + 8       # keys
                                         + 4 * 16 + 4 * 16))   # values, out


def _ctx(ops, config, mix, passes=2):
    return {"trace": Trace([DevicePlane("/device:TPU:0", list(ops), [])],
                           []),
            "lo": 0.0, "hi": 20 * MS, "counts": {"passes": passes},
            "config": config, "mix": mix, "device_kind": "TPU v5 lite"}


def _read(ctx):
    return R.load_module("metrics", NAME).read(ctx)


def test_the_necessary_work_against_a_hand_count_at_the_toy_shape():
    _, config, mix = cell_files()
    assert SEEN == 2611
    assert counts_seq.full_attention_products(
        config, mix["lengths"]) == (FLOPS, BYTES) == (1_671_040, 228_512)
    # the flops are the part of ``attention_flops`` that is no projection
    # and no selector: a route short of ``index_topk`` has nothing else
    assert counts_seq.full_attention_products(config, [13])[0] == 2 * (
        counts_seq.attention_flops(config, "full_attention", 13)
        - 2 * 13 * counts_seq.attention_weight_count(config,
                                                     "full_attention"))


def test_the_necessary_work_of_the_cell_is_what_the_issue_counted():
    _, config, mix = R.load_cell(R.load_json(R.REPO, "BENCHMARK.json"), CELL)
    flops, nbytes = counts_seq.full_attention_products(config,
                                                       mix["lengths"])
    assert abs(flops / 1e12 - 2 * 14.53) < 0.01       # ISSUE 34
    assert abs(nbytes / 1e9 - 2 * 15.118) < 0.001
    # a mask over every causal key multiplies 3.72 times as much
    causal = sum(n * (n + 1) // 2 for n in mix["lengths"])
    assert abs(2 * 2 * 128 * 320 * causal / flops - 3.718) < 0.001
    peak = peaks.chip_peaks("TPU v5 lite")
    assert (flops / peak.bf16_flops_per_s
            > 3.9 * nbytes / peak.hbm_bytes_per_s)      # compute governs


def test_two_kernel_operations_and_one_other_give_the_hand_computed_share():
    _, config, mix = cell_files()
    got = _read(_ctx(KERNEL_OPS + [OTHER_OP], config, mix))
    # at the toy widths the bytes govern: 2 passes over 4 ms of kernel
    assert BYTES / 819e9 > FLOPS / 197e12
    assert got == pytest.approx(100.0 * 2 * (228_512 / 819e9) / 4e-3)
    # the kernel's operations alone count, and only inside the window
    ctx = _ctx(KERNEL_OPS + [OTHER_OP], config, mix)
    ctx["hi"] = 3 * MS                   # 2 of the first one's 3 ms
    assert _read(ctx) == pytest.approx(100.0 * 2 * (228_512 / 819e9) / 2e-3)


def test_at_the_cells_shape_the_ledgers_kernel_time_reads_fourteen():
    """1.0546 s of kernel in the one traced pass (ledger, PR 33's
    ``breakdown``): the compute bound over it."""
    _, config, mix = R.load_cell(R.load_json(R.REPO, "BENCHMARK.json"), CELL)
    ops = [("selected_attention_step.22 bf16[128,256,128]", 0.0,
            426.283 * MS, "other"),
           ("selected_attention_step.23 bf16[128,256,128]", 500 * MS,
            426.258 * MS, "other"),
           ("selected_attention_step.21 bf16[128,256,128]", 1000 * MS,
            101.012 * MS, "other"),
           ("selected_attention_step.20 bf16[128,256,128]", 1200 * MS,
            101.010 * MS, "other"),
           ("fusion.377 f32[26624]", 1400 * MS, 54.0 * MS, "other")]
    ctx = _ctx(ops, config, mix, passes=1)
    ctx["hi"] = 5000 * MS
    assert _read(ctx) == pytest.approx(13.99, abs=0.01)


@pytest.mark.parametrize("case", ["no-kernel", "no-operations", "no-passes"])
def test_without_the_kernel_or_a_pass_there_is_no_number(case):
    _, config, mix = cell_files()
    ops = {"no-kernel": [OTHER_OP], "no-operations": [],
           "no-passes": KERNEL_OPS}[case]
    passes = 0 if case == "no-passes" else 2
    assert _read(_ctx(ops, config, mix, passes)) is None


@both_manifests
def test_the_manifest_lists_it_for_route_lm_score_alone_of_the_accepted(m):
    assert entry_of(m, NAME)[0] == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "attention",
        "moves": "od_rows_per_s"}
    assert [c for c in ACCEPTED_CELLS if NAME in reported(m, c)] == [CELL]
    # beside the kernel's roofline, the whole step's share of the peak
    assert "seq_mfu_pct" in reported(m, CELL)
