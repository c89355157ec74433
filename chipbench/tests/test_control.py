"""The control (the program computing in bfloat16, the precision below
the configuration's float32) reads ``correct`` false in every cell."""
import pytest

from chipbench import control
from chipbench.tests.small import SERVE, SOLVE, cell_for, run


@pytest.mark.parametrize("workload", [SOLVE, SERVE, "mesh", "mesh2x2"])
def test_control_reads_not_correct(workload):
    cell = control.as_control(cell_for(workload))
    assert cell.config["operator"]["dtype"] == "bfloat16"
    out = run(cell)
    assert not out.correct, out.checks
