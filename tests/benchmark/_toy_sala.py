"""Toy size of the ``route-lm-sala-long`` cell for the CPU tests: the
real files, with only the sizes shrunk (every mechanism stays: a run of
four layers out of ten — sparse, linear, linear, sparse —, 4 query heads
over 2 key-value heads, 6 blocks of 8 keys chosen of up to 18, every
route longer than ``dense_len``, a linear state over 7-18 chunks of
8)."""

from _toy import R, manifest

CELL = "route-lm-sala-long"

CONFIG = dict(
    dim_model_base=16, head_dim=16, hidden_size=64, intermediate_size=96,
    lightning_head_dim=16, lightning_nh=4, lightning_nkv=4,
    mixer_types=["minicpm4", "lightning-attn", "lightning-attn", "minicpm4",
                 "lightning-attn", "lightning-attn", "minicpm4", "minicpm4",
                 "lightning-attn", "minicpm4"],
    num_attention_heads=4, num_hidden_layers=4, num_key_value_heads=2,
    vocab_size=112,
    sparse={"kernel_size": 4, "kernel_stride": 2, "block_size": 8, "topk": 6,
            "init_blocks": 1, "window_size": 16, "dense_len": 48},
    published={"num_hidden_layers": 10},
    share={"layers_first": 3, "chips_per_layer": 1},
    # the scorer's blocks of queries and chunks shrink too
    q_block=8, key_chunk=16, scan_chunk=8)
MIX = dict(
    n_routes=4, length_median=72, length_min=50, length_max=160,
    lengths=[50, 59, 87, 144], max_step_tokens=160, named_rows=3,
    # one padded length for the reference, so that it compiles once
    reference_blocks={"q_block": 16, "row_block": 48, "pad_to": 144},
    # the cell's limits stand between readings at its own widths on the
    # chip; bfloat16 at widths of tens is several times noisier, so the
    # toy size states its own between its own readings
    limits={"logit_gap": 0.2, "lse_gap": 0.006, "rows_gap": 0.2,
            "loglik_gap": 0.009, "block_set_gap": 0.02,
            "key_set_gap": 0.001, "state_gap": 0.05})


def cell_files():
    cell, config, mix = R.load_cell(manifest(), CELL)
    config.update(CONFIG)
    mix.update(MIX)
    return cell, config, mix
