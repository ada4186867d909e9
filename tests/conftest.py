"""Hermetic test environment: 8 virtual CPU devices emulating a v5e-8 mesh.

The reference's one isolation idea — swap real backends for in-memory
fakes (its phpunit sqlite-:memory: config, SURVEY.md §4) — generalized:
tests run on the CPU backend with ``xla_force_host_platform_device_count=8``
so every sharding/collective path compiles and executes without TPU
hardware. Must run before any jax backend initialization, hence conftest.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Hermetic: tests must not read (or seed) the per-user overlay cache —
# a stale entry from an earlier run would mask precompute regressions.
# Plain assignment (not setdefault): a developer's exported cache dir
# must not leak into the suite. The dedicated cache test opts back in
# through a tmp dir.
os.environ["ROUTEST_HIER_CACHE"] = "0"

# Flight-recorder bundles (5xx-burst fuzz phases legitimately trip the
# automatic triggers) go to a throwaway dir, not the repo's artifacts/.
# setdefault: a test that pins its own dir (tmp_path) still wins.
import tempfile  # noqa: E402

os.environ.setdefault(
    "RTPU_RECORDER_DIR", tempfile.mkdtemp(prefix="rtpu-postmortems-"))

# Tests never touch an accelerator, whatever the developer's shell
# exports: plain assignment, and through the environment so every
# server / bench subprocess a test spawns inherits it.
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

# Catch NaNs early in the functional core (SURVEY.md §5.2).
jax.config.update("jax_debug_nans", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh_runtime():
    from routest_tpu.core.mesh import MeshRuntime

    rt = MeshRuntime.create()
    assert rt.n_data == 8, f"expected 8 virtual devices, got {rt.n_data}"
    return rt


@pytest.fixture(scope="session")
def tiny_dataset():
    from routest_tpu.data.synthetic import generate_dataset, train_eval_split

    data = generate_dataset(4096, seed=42)
    return train_eval_split(data, eval_frac=0.25)


@pytest.fixture()
def rng():
    return np.random.default_rng(0)


@pytest.fixture()
def tracer():
    """A fresh always-sampling tracer as the process tracer, so that a
    test reads its own span buffer; the old one is put back after."""
    from routest_tpu.obs import Tracer, configure_tracer, get_tracer

    old = get_tracer()
    yield configure_tracer(Tracer(enabled=True, sample_rate=1.0))
    configure_tracer(old)
