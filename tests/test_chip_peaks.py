"""The peak-rate table that utilization figures are computed against
knows the v5e under the name JAX gives it, and refuses a chip it does
not know."""

import pytest

from routest_tpu.core.mesh import chip_peaks


def test_chip_peaks_knows_the_v5e_as_jax_names_it():
    assert chip_peaks("TPU v5 lite") == (197.0, 819.0)
    assert chip_peaks("TPU v5e") == chip_peaks("TPU v5 lite")


@pytest.mark.parametrize("kind", ["cpu", "", None, "TPU v9000", "NVIDIA H100"])
def test_chip_peaks_raises_on_an_unknown_kind(kind):
    with pytest.raises(ValueError, match="no peak-rate table row"):
        chip_peaks(kind)
