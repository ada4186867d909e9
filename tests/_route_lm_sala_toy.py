"""A toy size of the second route-sequence language model for the CPU
tests: every mechanism of the published architecture at widths of tens —
a run of four layers out of ten (sparse, linear, linear, sparse: the
published layers 3-6), 4 query heads over 2 key-value heads, compressed
keys of 4 at a stride of 2, blocks of 8 keys of which a query takes 6
(the first and a local window of 16 forced), dense below 48 tokens, a
linear state of 16 x 16 a head over chunks of 8."""

import jax.numpy as jnp
import numpy as np

from routest_tpu.core.dtypes import Policy
from routest_tpu.models.route_lm_sala import RouteLMSala

CONFIG = dict(
    attn_use_output_gate=True, attn_use_rope=False, dim_model_base=16,
    head_dim=16, hidden_size=64, intermediate_size=96, lightning_head_dim=16,
    lightning_nh=4, lightning_nkv=4, lightning_use_rope=True,
    mixer_types=["minicpm4", "lightning-attn", "lightning-attn", "minicpm4",
                 "lightning-attn", "lightning-attn", "minicpm4", "minicpm4",
                 "lightning-attn", "minicpm4"],
    num_attention_heads=4, num_hidden_layers=4, num_key_value_heads=2,
    qk_norm=True, rms_norm_eps=1e-6, rope_theta=10000, scale_depth=1.4,
    scale_emb=12, use_output_gate=True, use_output_norm=True, vocab_size=128,
    sparse={"kernel_size": 4, "kernel_stride": 2, "block_size": 8, "topk": 6,
            "init_blocks": 1, "window_size": 16, "dense_len": 48},
    published={"num_hidden_layers": 10},
    share={"layers_first": 3, "chips_per_layer": 1},
    q_block=8, key_chunk=16, scan_chunk=8)
F32 = Policy(param_dtype=jnp.float32, compute_dtype=jnp.float32)


def model(policy=F32, **changes) -> RouteLMSala:
    return RouteLMSala.from_config(dict(CONFIG, **changes), policy=policy)


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
