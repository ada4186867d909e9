"""Device-mesh runtime: one place that owns the Mesh and shardings.

The reference has no parallelism at all (SURVEY.md §2.4 — single-row CPU
inference, ``Flaskr/ml.py:51-53``). Here the mesh is the foundation: OD-pair
batches shard over the ``data`` axis (the 10k preds/sec axis) and the
``model`` axis is reserved for tensor-parallel weights. XLA emits the
collectives (psum/all_gather over ICI); nothing here speaks NCCL/MPI.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from routest_tpu.core.config import MeshConfig


# Dense peak (TFLOP/s for bf16 matmul, HBM GB/s) by device_kind
# substring, lowercase. Sources: public TPU spec sheets (one v5e chip:
# Google Cloud documentation, "TPU v5e"). JAX reports a v5e as
# "TPU v5 lite".
_CHIP_PEAKS = {
    "v5 lite": (197.0, 819.0), "v5e": (197.0, 819.0),
    "v5p": (459.0, 2765.0),
    "v4": (275.0, 1228.0),
    "v3": (123.0, 900.0),
    "v6": (918.0, 1640.0), "trillium": (918.0, 1640.0),
}


def chip_peaks(device_kind: str):
    """(peak_tflops_bf16, peak_hbm_gbps) for a TPU ``device_kind``.

    An unknown kind raises: a utilization computed against a guessed
    peak is worse than none, so a new chip gets a table row first."""
    kind = (device_kind or "").lower()
    for key, peaks in _CHIP_PEAKS.items():
        if key in kind:
            return peaks
    raise ValueError(
        f"no peak-rate table row for device kind {device_kind!r}; add "
        f"one to core.mesh._CHIP_PEAKS with its source")


def create_mesh(cfg: Optional[MeshConfig] = None,
                devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    cfg = cfg or MeshConfig()
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    model = max(1, cfg.model)
    data = cfg.data if cfg.data > 0 else max(1, n // model)
    if data * model > n:
        raise ValueError(f"mesh {data}x{model} needs {data * model} devices, have {n}")
    grid = np.asarray(devices[: data * model]).reshape(data, model)
    return Mesh(grid, cfg.axis_names)


@dataclasses.dataclass(frozen=True)
class MeshRuntime:
    """Mesh + the shardings every layer above needs."""

    mesh: Mesh

    @classmethod
    def create(cls, cfg: Optional[MeshConfig] = None,
               devices: Optional[Sequence[jax.Device]] = None) -> "MeshRuntime":
        return cls(mesh=create_mesh(cfg, devices))

    @property
    def data_axis(self) -> str:
        return self.mesh.axis_names[0]

    @property
    def model_axis(self) -> str:
        return self.mesh.axis_names[1]

    @property
    def n_data(self) -> int:
        return self.mesh.shape[self.data_axis]

    def batch_sharding(self) -> NamedSharding:
        """Rows sharded over the data axis; feature dim replicated."""
        return NamedSharding(self.mesh, P(self.data_axis))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def shard_batch(self, tree):
        """Device-put a pytree of host arrays with rows over the data axis."""
        sharding = self.batch_sharding()
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, sharding), tree
        )

    def replicate(self, tree):
        sharding = self.replicated()
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, sharding), tree
        )


def pad_to_multiple(n: int, multiple: int) -> int:
    """Smallest m >= n with m % multiple == 0 (and m >= multiple)."""
    if multiple <= 0:
        return n
    return max(multiple, ((n + multiple - 1) // multiple) * multiple)


def pad_rows(array: np.ndarray, target_rows: int) -> np.ndarray:
    """Zero-pad axis 0 up to target_rows (static shapes keep XLA happy)."""
    n = array.shape[0]
    if n == target_rows:
        return array
    if n > target_rows:
        raise ValueError(f"cannot pad {n} rows down to {target_rows}")
    pad_widths = [(0, target_rows - n)] + [(0, 0)] * (array.ndim - 1)
    return np.pad(array, pad_widths)
