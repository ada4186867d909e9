"""A toy size of the fourth route-sequence language model for the CPU
tests: every mechanism of the published architecture at widths of tens —
five held layers of eight (one of the leading dense layers, then four
expert layers), 4 heads with 16 + 8 wide two-part keys and 24-wide
values from latents of 24 and 16, YaRN stretching 8 original positions
by 8 with a blend over all four rotary pairs (most of a route lies past
the original positions), 32 experts in 8 routing
groups of which a token keeps 4 and takes 4 experts at a routed scaling
of 2.5, a share that holds experts 0-1 (half of group 0), a shared
expert, a vocabulary slice of 128 and the prediction module."""

import jax.numpy as jnp
import numpy as np

from routest_tpu.core.dtypes import Policy
from routest_tpu.models.route_lm_gigachat import RouteLMGigaChat

CONFIG = dict(
    first_k_dense_replace=3, hidden_size=64, intermediate_size=96,
    kv_lora_rank=16, moe_intermediate_size=32, n_group=8,
    n_routed_experts=2, n_shared_experts=1, norm_topk_prob=True,
    num_attention_heads=4, num_experts_per_tok=4, num_hidden_layers=5,
    num_nextn_predict_layers=1, q_lora_rank=24, qk_nope_head_dim=16,
    qk_rope_head_dim=8, rms_norm_eps=1e-6, rope_theta=100000,
    rope_scaling={"beta_fast": 32, "beta_slow": 0.0001, "factor": 8, "mscale": 1,
                  "mscale_all_dim": 1,
                  "original_max_position_embeddings": 8,
                  "rope_type": "yarn"},
    routed_scaling_factor=2.5, scoring_func="sigmoid", topk_group=4,
    topk_method="noaux_tc", v_head_dim=24, vocab_size=128,
    published={"num_hidden_layers": 8, "n_routed_experts": 32,
               "vocab_size": 1024},
    share={"chips_per_layer": 16, "experts_first": 0},
    full_block=8, key_chunk=16)
SHARE = (0, 2)
F32 = Policy(param_dtype=jnp.float32, compute_dtype=jnp.float32)


def model(policy=F32, **changes) -> RouteLMGigaChat:
    return RouteLMGigaChat.from_config(dict(CONFIG, **changes), policy=policy)


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
