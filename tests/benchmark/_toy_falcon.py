"""Toy size of the ``route-lm-falcon-hybrid`` cell for the CPU tests:
the real files, with only the sizes shrunk (every mechanism stays:
three hybrid blocks of six, a state-space mixer of 16 heads of 8 in two
groups of a state of 16 over chunks of 8, 10 query heads over 2
key-value heads, every multiplier at the published value, routes in
several length classes and steps of one and two routes)."""

from _toy import R, manifest

CELL = "route-lm-falcon-hybrid"

CONFIG = dict(
    head_dim=8, hidden_size=64, intermediate_size=96, mamba_chunk_size=8,
    mamba_d_head=8, mamba_d_ssm=128, mamba_d_state=16, mamba_n_heads=16,
    num_attention_heads=10, num_hidden_layers=3, num_key_value_heads=2,
    vocab_size=112,
    published={"num_hidden_layers": 6, "vocab_size": 896},
    share={"layers_first": 2, "vocab_chips": 8},
    # the scorer's blocks of queries and chunks of keys shrink too
    full_block=8, key_chunk=16)
MIX = dict(
    n_routes=6, length_median=40, length_sigma=0.8, length_min=12,
    length_max=96, lengths=[13, 23, 34, 47, 69, 96], max_step_tokens=128,
    max_classes=4, named_rows=3,
    # one padded length for the reference, so that it compiles once
    reference_blocks={"q_block": 32, "row_block": 48, "pad_to": 96},
    # the cell's limits stand between readings at its own widths on the
    # chip; bfloat16 at widths of tens is noisier, so the toy size
    # states its own between its own readings on the tests' seeds (the
    # program reads logit 0.005, state 0.001; the fp8 control 0.043 and
    # 0.016; the faults state 0.033-1.6 or logit 0.18)
    limits={"logit_gap": 0.02, "lse_gap": 0.0006, "rows_gap": 0.015,
            "loglik_gap": 0.0008, "state_gap": 0.008, "key_set_gap": 0.001})


def cell_files():
    cell, config, mix = R.load_cell(manifest(), CELL)
    config.update(CONFIG)
    mix.update(MIX)
    return cell, config, mix
