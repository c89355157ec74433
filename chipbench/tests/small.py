"""The benchmark's cells cut to sizes a CPU test can run; everything but
the grid (and how long the serve cell waits for stragglers) is as the
cell's files state it.  ``sharded_cell`` is the solve cell split over
four host devices, which drives the harness's mesh path."""
import copy
import dataclasses
import time

from chipbench import cells, harness
from chipbench.peaks import PEAKS

SOLVE, SERVE = "atmosmodd-solve", "atmosmodd-serve"
#: grids of unequal sides, so that an axis taken for another shows
GRIDS = {1: [16, 12, 8], 4: [16, 8, 12]}
PEAKS_V5E = PEAKS["TPU v5 lite"]


def small_cell(name: str) -> cells.Cell:
    cell = copy.deepcopy(cells.load_cell(name))
    cell.config["operator"]["grid"] = GRIDS[int(cell.config["shards"])]
    if "drain_s" in cell.traffic:
        cell.traffic["drain_s"] = 3.0
    return cell


def sharded_cell() -> cells.Cell:
    cell = copy.deepcopy(cells.load_cell(SOLVE))
    cell.config["shards"] = 4
    cell.config["operator"]["grid"] = GRIDS[4]
    return dataclasses.replace(cell, name=f"{SOLVE}-x4", chips=4)


def cell_for(key: str) -> cells.Cell:
    """``small_cell`` of a cell of ``BENCHMARK.json``; ``"mesh"``, the
    sharded one."""
    return sharded_cell() if key == "mesh" else small_cell(key)


def run(cell: cells.Cell, seed: int = 2 ** 33 + 5, seconds: float = 1.0,
        trace: bool = False) -> harness.Outcome:
    import jax

    return harness.run_cell(cell, seed, seconds, trace,
                            devices=jax.devices(), peaks=PEAKS_V5E,
                            t_start=time.perf_counter())
