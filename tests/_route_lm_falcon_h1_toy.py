"""A toy size of the fifth route-sequence language model for the CPU
tests: every mechanism of the published architecture at widths of tens —
a run of three hybrid blocks out of six (the published blocks 2-4), a
state-space mixer of 16 heads of 8 in two groups of a state of 16 (8
heads a group: one tile of the kernel), a convolution of 4 taps, chunks
of 8; 10 query heads over 2 key-value heads (5 a group); every
multiplier at the published value."""

import jax.numpy as jnp
import numpy as np

from routest_tpu.core.dtypes import Policy
from routest_tpu.models.route_lm_falcon_h1 import RouteLMFalconH1

CONFIG = dict(
    attention_bias=False, attention_in_multiplier=1,
    attention_out_multiplier=0.0375, embedding_multiplier=5.656854249492381,
    head_dim=8, hidden_size=64, intermediate_size=96,
    key_multiplier=0.011048543456039804, lm_head_multiplier=0.0078125,
    mamba_chunk_size=8, mamba_conv_bias=True, mamba_d_conv=4,
    mamba_d_head=8, mamba_d_ssm=128, mamba_d_state=16, mamba_n_groups=2,
    mamba_n_heads=16, mamba_norm_before_gate=False, mamba_proj_bias=False,
    mamba_rms_norm=True, mlp_bias=False,
    mlp_multipliers=[0.1767766952966369, 0.011160714285714284],
    num_attention_heads=10, num_hidden_layers=3, num_key_value_heads=2,
    projectors_bias=False, rms_norm_eps=1e-05, rope_scaling=None,
    rope_theta=100000000000, ssm_in_multiplier=0.25,
    ssm_multipliers=[0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738],
    ssm_out_multiplier=0.08838834764831845, tie_word_embeddings=False,
    vocab_size=112,
    published={"num_hidden_layers": 6, "vocab_size": 896},
    share={"layers_first": 2, "vocab_chips": 8},
    full_block=8, key_chunk=16)
F32 = Policy(param_dtype=jnp.float32, compute_dtype=jnp.float32)


def model(policy=F32, **changes) -> RouteLMFalconH1:
    return RouteLMFalconH1.from_config(dict(CONFIG, **changes),
                                       policy=policy)


def routes(seed: int, lengths, named: int = 3):
    """ids (R, max length), lengths, rows_at (R, named), as numpy."""
    rng = np.random.default_rng(seed)
    lengths = np.asarray(lengths, np.int32)
    ids = rng.integers(0, CONFIG["vocab_size"],
                       (len(lengths), int(lengths.max()))).astype(np.int32)
    ids = np.where(np.arange(ids.shape[1])[None] < lengths[:, None], ids, 0)
    rows_at = np.stack([np.sort(rng.choice(int(n) - 1, named, replace=False))
                        for n in lengths]).astype(np.int32)
    return ids, lengths, rows_at
