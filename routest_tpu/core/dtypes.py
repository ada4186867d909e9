"""Dtype policy: f32 parameters, bf16 compute, f32 outputs.

The MXU natively consumes bfloat16; keeping parameters in float32 and
casting at the matmul boundary is the standard TPU mixed-precision recipe.
ETA targets are small magnitudes (minutes), so f32 accumulation is plenty.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Policy:
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    output_dtype: Any = jnp.float32

    def cast_to_compute(self, tree):
        return jax.tree_util.tree_map(
            lambda x: x.astype(self.compute_dtype)
            if jnp.issubdtype(x.dtype, jnp.floating)
            else x,
            tree,
        )

    def cast_to_output(self, x):
        return x.astype(self.output_dtype)


DEFAULT_POLICY = Policy()
# Full-f32 policy for CPU-emulated meshes and parity tests.
F32_POLICY = Policy(compute_dtype=jnp.float32)
# bf16 parameters too: models too wide to hold in float32 on one chip
# (the route-sequence language model), inference only.
BF16_POLICY = Policy(param_dtype=jnp.bfloat16)


def backend_compute_policy(model):
    """Swap a model's compute dtype to f32 on the CPU backend.

    bf16 compute on CPU is EMULATED — measured ~1.8× slower than f32
    on one core with zero bandwidth payoff (bf16 pays on the TPU's
    MXU/HBM, which is why artifacts train and ship with it). Serving
    and the bench apply this when they land on the CPU fallback: same
    params, same output dtype, strictly less rounding.
    ``RTPU_CPU_COMPUTE=bf16`` keeps the artifact's policy (e.g. to
    reproduce TPU numerics on a CPU host). Models without a dtype
    policy (GBDT, AOT exports) pass through unchanged."""
    import os

    policy = getattr(model, "policy", None)
    if policy is None:
        return model
    if (jax.default_backend() == "cpu"
            and policy.compute_dtype == jnp.bfloat16
            and os.environ.get("RTPU_CPU_COMPUTE", "").lower() != "bf16"):
        return dataclasses.replace(
            model, policy=dataclasses.replace(policy,
                                              compute_dtype=jnp.float32))
    return model
