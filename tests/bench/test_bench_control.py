"""The check's control, the reference in float32 in the program's place,
fails every cell's limits (at the rehearsal sizes here; PERF.md gives its
readings at the cells' own sizes on the chip)."""

import pytest

from perfbench.lib import control, spec

CELLS = [w["name"] for w in spec.benchmark()["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_float32_control_is_refused(name):
    limits = spec.limits(name)
    got = control.readings(name, 3000000031, rehearse=True)
    assert any(got[k] > v for k, v in limits.items()), (got, limits)


@pytest.mark.parametrize("name", CELLS)
def test_float64_reference_in_the_programs_place_passes(name):
    """The same path with the float64 reference as the answer reads 0."""
    import numpy as np

    limits = spec.limits(name)
    got = control.readings(name, 3000000032, rehearse=True, dtype=np.float64)
    assert all(got[k] <= v for k, v in limits.items()), (got, limits)
