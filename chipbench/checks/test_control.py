"""The control of every cell fails its limits, and the program passes them,
at a size a CPU test can hold.

    JAX_PLATFORMS=cpu python -m pytest chipbench/checks

The control is the plain reference computed in bfloat16, one precision
below the float32 the configurations state, put in the program's place.
"""

from __future__ import annotations

import pytest

from chipbench import harness
from chipbench.checks.limits import readings
from chipbench.checks.small import CELLS, small_cell


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_a_limit(name):
    workload, config, traffic = small_cell(name)
    harness.setup_jax(False, 1)
    cell = harness.load_entry(traffic["entry"]).build(config, traffic, 2**31 + 7)
    limits = harness.limits_for(name)
    control = readings(cell, [1, 2], control=True)
    assert any(control[k] > limit for k, limit in limits.items()), control
    program = readings(cell, [1, 2], control=False)
    assert all(program[k] <= limit for k, limit in limits.items()), program
