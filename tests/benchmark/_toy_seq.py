"""Toy size of the ``route-lm-score`` cell for the CPU tests: the real
files, with only the sizes shrunk (every mechanism stays: two kinds of
latent attention, the selector choosing 16 of up to 96 keys, a window
of 9, 4 of 16 experts a token with 8 held)."""

from _toy import R, manifest

CELL = "route-lm-score"

CONFIG = dict(
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    num_attention_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, q_lora_rank=24, kv_lora_rank=16, index_n_heads=4,
    index_head_dim=16, index_topk=16, swa_num_attention_heads=2,
    swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8, swa_v_head_dim=16,
    swa_q_lora_rank=24, swa_kv_lora_rank=24, sliding_window_size=9,
    num_experts_per_tok=4, n_routed_experts=8, vocab_size=112,
    published={"num_hidden_layers": 46, "n_routed_experts": 16,
               "vocab_size": 896},
    share={"chips_per_layer": 2, "experts_first": 0},
    # the scorer's blocks of queries and chunks of keys shrink too
    select_block=8, window_block=8, key_chunk=16)
MIX = dict(
    n_routes=4, length_median=40, length_min=12, length_max=96,
    lengths=[13, 29, 55, 96], max_step_tokens=128, named_rows=3,
    # one padded length for the reference, so that it compiles once
    reference_blocks={"q_block": 32, "sel_block": 16, "head_group": 2,
                      "row_block": 48, "expert_cap": 1, "pad_to": 96},
    # the cell's limits stand between readings at its own widths on the
    # chip; bfloat16 at widths of tens is several times noisier (the
    # program reads logit 0.07-0.09, expert 0.014-0.016, selected
    # 0.004-0.010 here; the fp8 control 0.39, 0.105, 0.066; the faults
    # logit 0.30-0.97, selected 0.39, key sets 0.50), so the toy size
    # states its own between those
    limits={"logit_gap": 0.2, "lse_gap": 0.006, "rows_gap": 0.2,
            "loglik_gap": 0.009, "expert_gap": 0.04, "selected_gap": 0.03,
            "key_set_gap": 0.001})

def cell_files():
    cell, config, mix = R.load_cell(manifest(), CELL)
    config.update(CONFIG)
    mix.update(MIX)
    return cell, config, mix
