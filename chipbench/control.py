"""The readings a cell's limits are set from: the program's own runs over
many seeds (the lower readings) and the control (the upper readings).

    python3 -m chipbench.control --workload <cell> --seeds 1,2,3 \
        --seconds 5 [--program]

Runs the cell's window once per seed, all in one process, and prints each
seed's compared numbers beside their limits, one JSON line per seed.  The
control is the cell with the program computing in bfloat16, the precision
below the configuration's float32: the operator (built by its kind's
module in the configuration's ``dtype``), the right-hand sides and so
every vector of the solver and of the engine are bfloat16, while the
benchmark's own checks stay float32 and the reference float64.  A
sound benchmark reads ``correct`` false for it on every seed.
``--program`` runs the cell as configured instead.

Without a TPU it exits 2.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if __package__ in (None, ""):
    sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))
# the TPU runtime logs to /tmp unless told otherwise: a run writes nothing
# outside its checkout and the directories it is given
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import cells, harness  # noqa: E402
from chipbench.peaks import ChipPeaks  # noqa: E402

CONTROL_DTYPE = "bfloat16"


def as_control(cell: cells.Cell) -> cells.Cell:
    """The cell with the program's precision one step below its
    configuration's."""
    cell = copy.deepcopy(cell)
    cell.config["operator"]["dtype"] = CONTROL_DTYPE
    return cell


def readings(cell: cells.Cell, seeds, seconds: float, *, devices,
             peaks: ChipPeaks, control: bool):
    """``(seed, outcome)`` for each seed, in one process."""
    if control:
        cell = as_control(cell)
    t = T_START
    for seed in seeds:
        yield seed, harness.run_cell(cell, seed, seconds, False,
                                     devices=devices, peaks=peaks,
                                     t_start=t)
        t = time.perf_counter()


def main(argv=None) -> int:
    from chipbench.peaks import chip_peaks

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--program", action="store_true",
                    help="run the cell as configured (lower readings)")
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"chipbench.control: {args.workload} needs {cell.chips} TPU "
              f"chips; JAX sees {len(devices)} {devices[0].platform}",
              file=sys.stderr)
        return 2
    peaks = chip_peaks(devices[0].device_kind)
    seeds = [int(s) for s in args.seeds.split(",")]
    for seed, out in readings(cell, seeds, args.seconds, devices=devices,
                              peaks=peaks, control=not args.program):
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "control": not args.program, "correct": out.correct,
            "attempted": out.attempted, "failed": out.failed,
            "checks": {k: {"value": v, "limit": lim}
                       for k, (v, lim) in out.checks.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
