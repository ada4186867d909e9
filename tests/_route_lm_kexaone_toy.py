"""A toy size of the third route-sequence language model for the CPU
tests: every mechanism of the published architecture at widths of tens —
five layers of eight (sliding, sliding, sliding, full, sliding), 8 query
heads over 2 key-value heads of 16, a window of 8 keys, a dense first
layer then 16 experts of which a token takes 4 at a routed scaling of
2.5 and a share holds 8, a shared expert, a vocabulary slice of 128 and
the prediction module."""

import jax.numpy as jnp
import numpy as np

from routest_tpu.core.dtypes import Policy
from routest_tpu.models.route_lm_kexaone import RouteLMKExaone

CONFIG = dict(
    first_k_dense_replace=1, head_dim=16, hidden_size=64,
    intermediate_size=96,
    layer_types=["sliding_attention", "sliding_attention",
                 "sliding_attention", "full_attention"] * 2,
    mlp_layer_types=["dense"] + ["sparse"] * 7, moe_intermediate_size=32,
    mtp_layer_types=["full_attention"], n_group=1, norm_topk_prob=True,
    num_attention_heads=8, num_experts=8, num_experts_per_tok=4,
    num_hidden_layers=5, num_key_value_heads=2, num_nextn_predict_layers=1,
    num_shared_experts=1, rms_norm_eps=1e-5,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    routed_scaling_factor=2.5, scoring_func="sigmoid", sliding_window=8,
    topk_group=1, vocab_size=128,
    published={"num_hidden_layers": 8, "num_experts": 16,
               "vocab_size": 1024},
    share={"chips_per_layer": 2, "experts_first": 0},
    full_block=8, window_block=8, key_chunk=16, window_rows=16)
SHARE = (0, 8)
F32 = Policy(param_dtype=jnp.float32, compute_dtype=jnp.float32)


def model(policy=F32, **changes) -> RouteLMKExaone:
    return RouteLMKExaone.from_config(dict(CONFIG, **changes), policy=policy)


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
