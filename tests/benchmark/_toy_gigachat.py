"""Toy size of the ``route-lm-gigachat-dense`` cell for the CPU tests:
the real files, with only the sizes shrunk (every mechanism stays: one
dense layer and four expert layers, 4 heads of 16 + 8 wide two-part keys
and 24-wide values from latents of 24 and 16, YaRN stretching 8 original
positions by 8, 4 of 32 experts a token from 4 of 8 routing groups at a
routed scaling of 2.5 with experts 0-1 held, half a group, the
prediction module, routes in several length classes and steps of one
and two routes)."""

from _toy import R, manifest

CELL = "route-lm-gigachat-dense"

CONFIG = dict(
    hidden_size=64, intermediate_size=96, kv_lora_rank=16,
    moe_intermediate_size=32, n_routed_experts=2, num_attention_heads=4,
    num_experts_per_tok=4, q_lora_rank=24, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=24, vocab_size=112,
    rope_scaling={"beta_fast": 32, "beta_slow": 0.0001, "factor": 8,
                  "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 8,
                  "rope_type": "yarn"},
    published={"num_hidden_layers": 8, "n_routed_experts": 32,
               "vocab_size": 896},
    share={"chips_per_layer": 16, "experts_first": 0},
    # the scorer's blocks of queries and chunks of keys shrink too
    full_block=8, key_chunk=16)
MIX = dict(
    n_routes=6, length_median=40, length_sigma=0.8, length_min=12,
    length_max=96, lengths=[13, 23, 34, 47, 69, 96], max_step_tokens=128,
    max_classes=4, named_rows=3,
    # one padded length for the reference, so that it compiles once
    reference_blocks={"q_block": 32, "head_group": 2, "row_block": 48,
                      "expert_cap": 1, "pad_to": 96},
    # the cell's limits stand between readings at its own widths on the
    # chip; bfloat16 at widths of tens is several times noisier (and
    # with two held experts of 32 a flipped choice is a whole term), so
    # the toy size states its own between its own readings on the
    # tests' seeds (the program reads logit 0.02-0.04, the module's
    # column 0.01-0.04, experts 0.007-0.011; the fp8 control 0.20, 0.28,
    # 0.10; the faults logit 0.09-0.66, the module's 1.14, experts 0.14,
    # key sets 0.99)
    limits={"logit_gap": 0.06, "lse_gap": 0.003, "rows_gap": 0.11,
            "loglik_gap": 0.003, "mtp_logit_gap": 0.08, "mtp_lse_gap": 0.003,
            "mtp_loglik_gap": 0.005, "expert_gap": 0.03,
            "key_set_gap": 0.001})

def cell_files():
    cell, config, mix = R.load_cell(manifest(), CELL)
    config.update(CONFIG)
    mix.update(MIX)
    return cell, config, mix
