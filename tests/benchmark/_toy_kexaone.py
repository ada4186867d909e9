"""Toy size of the ``route-lm-kexaone-mixed`` cell for the CPU tests: the
real files, with only the sizes shrunk (every mechanism stays: five
layers of eight — dense then sliding, sliding, full, sliding —, 8 query
heads over 2 key-value heads, a window of 8 keys, 4 of 16 experts a
token at a routed scaling of 2.5 with 8 held, the prediction module,
routes in several length classes and steps of one to three routes)."""

from _toy import R, manifest

CELL = "route-lm-kexaone-mixed"

CONFIG = dict(
    head_dim=16, hidden_size=64, intermediate_size=96,
    layer_types=["sliding_attention", "sliding_attention",
                 "sliding_attention", "full_attention"] * 2,
    mlp_layer_types=["dense"] + ["sparse"] * 7, moe_intermediate_size=32,
    num_attention_heads=8, num_experts=8, num_experts_per_tok=4,
    num_key_value_heads=2, sliding_window=8, vocab_size=112,
    published={"num_hidden_layers": 8, "num_experts": 16,
               "vocab_size": 896},
    share={"chips_per_layer": 2, "experts_first": 0},
    # the scorer's blocks of queries and chunks of keys shrink too
    full_block=8, window_block=8, key_chunk=16, window_rows=16)
MIX = dict(
    n_routes=6, length_median=40, length_sigma=0.8, length_min=12,
    length_max=96, lengths=[13, 23, 34, 47, 69, 96], max_step_tokens=128,
    max_classes=4, named_rows=3,
    # one padded length for the reference, so that it compiles once
    reference_blocks={"q_block": 32, "row_block": 48, "expert_cap": 1,
                      "pad_to": 96},
    # the cell's limits stand between readings at its own widths on the
    # chip; bfloat16 at widths of tens is several times noisier, so the
    # toy size states its own between its own readings (the program
    # reads logit 0.04-0.08, the module's column 0.05-0.08, experts
    # 0.004-0.006; the fp8 control 0.11-0.18, 0.16-0.28, 0.043-0.054;
    # the faults logit 0.22-0.63, the module's 1.41, key sets 0.57)
    limits={"logit_gap": 0.1, "lse_gap": 0.003, "rows_gap": 0.11,
            "loglik_gap": 0.006, "mtp_logit_gap": 0.12, "mtp_lse_gap": 0.003,
            "mtp_loglik_gap": 0.008, "expert_gap": 0.02,
            "key_set_gap": 0.001})


def cell_files():
    cell, config, mix = R.load_cell(manifest(), CELL)
    config.update(CONFIG)
    mix.update(MIX)
    return cell, config, mix
