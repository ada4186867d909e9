"""One described TPU v5e chip for the compile tests
(``test_tpu_compile.py``, ``test_tpu_compile_steps.py``), and a tree of
shapes placed on it."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or it logs under /tmp

import jax
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def v5e():
    """One described v5e chip as a sharding. The persistent compile
    cache is off while this module runs: an entry compiled for a
    described chip cannot be read back without one, and the next
    compile would warn instead of staying silent."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)
