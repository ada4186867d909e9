"""Pallas TPU kernels for the framework's hot ops.

The compute path is JAX/XLA first (SURVEY.md §7.1); kernels live here only
where measurement shows XLA leaving performance on the table. Every kernel
has a pure-XLA reference implementation it is parity-tested against, and
callers must degrade to the XLA path when Pallas is unavailable.
"""

from routest_tpu.ops.fused_mlp import (  # noqa: F401
    fused_eta_forward,
    fused_eta_forward_t,
    pack_eta_params,
    resolve_kernel_dtype,
)
