"""The command's contract: no result without a TPU, and the shape of the
result line a run prints."""
import jax
import pytest

from chipbench import run as run_mod
from chipbench.peaks import chip_peaks
from chipbench.tests.small import SERVE, SOLVE, run, small_cell


def test_no_tpu_no_result(capsys):
    assert run_mod.main(["--workload", SOLVE, "--seed", "1",
                         "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""


def test_result_line():
    cell = small_cell(SERVE)
    out = run(cell)
    line = run_mod.result_line(cell, out, jax.devices(), False)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert set(line["metrics"]) == {"served_rps", "served_p95_s",
                                    "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert all(set(c) == {"value", "limit"}
               for c in line["checks"].values())


def test_traced_result_line():
    cell = small_cell(SOLVE)
    out = run(cell, seconds=2.5, trace=True)
    line = run_mod.result_line(cell, out, jax.devices(), True)
    assert list(line)[-1] == "checks"
    # a CPU has no device plane: what the trace gives is left out, what
    # the program counts is there
    assert {"iters.solve", "refines.solve"} <= set(line["metrics"])
    assert "idle.solve" not in line["metrics"]
    assert {"busy_s", "window_s"} <= set(line["device"])


def test_unknown_device_kind_is_an_error():
    assert chip_peaks("TPU v5 lite").hbm_bytes_per_s == 819e9
    with pytest.raises(KeyError):
        chip_peaks("TPU v99")
