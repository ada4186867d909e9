"""A toy size of the route-sequence language model for the CPU tests:
every mechanism of the published architecture at widths of tens —
two full layers (4 heads, a selector choosing 16 keys) and three sliding
ones (2 heads, a window of 9), a dense first layer then 16 experts of
which a token takes 4 and a share holds 8, a vocabulary slice of 128."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from routest_tpu.core.dtypes import Policy
from routest_tpu.models.route_lm import RouteLM

CONFIG = dict(
    apply_mla_qkv_lora_rescale=True, first_k_dense_replace=1, hidden_size=64,
    index_head_dim=16, index_n_heads=4, index_topk=16, intermediate_size=96,
    kv_lora_rank=16,
    layer_types=["full_attention", "full_attention", "sliding_attention",
                 "sliding_attention", "sliding_attention"] * 2,
    moe_intermediate_size=32, n_routed_experts=8, n_shared_experts=1,
    norm_topk_prob=True, num_attention_heads=4, num_experts_per_tok=4,
    num_hidden_layers=5, q_lora_rank=24, qk_nope_head_dim=16,
    qk_rope_head_dim=8, rms_norm_eps=1e-5, rope_theta=8e7,
    routed_scaling_factor=1, sliding_window_size=9, swa_kv_lora_rank=24,
    swa_num_attention_heads=2, swa_q_lora_rank=24, swa_qk_nope_head_dim=24,
    swa_qk_rope_head_dim=8, swa_rope_theta=5e4, swa_v_head_dim=16,
    v_head_dim=16, vocab_size=128,
    published={"n_routed_experts": 16, "vocab_size": 1024,
               "num_hidden_layers": 10},
    share={"chips_per_layer": 2, "experts_first": 0},
    select_block=8, window_block=8, key_chunk=16)
SHARE = (0, 8)
F32 = Policy(param_dtype=jnp.float32, compute_dtype=jnp.float32)


def model(**changes) -> RouteLM:
    return dataclasses.replace(RouteLM.from_config(CONFIG, policy=F32),
                               **changes)


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


def highest(fn):
    def run(*a, **k):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **k)
    return run
