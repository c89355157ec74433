"""The benchmark's cells cut to sizes a CPU test can run; everything but
the grid (and how long the serve cell waits for stragglers) is as the
cell's files state it.  ``sharded_cell`` is the solve cell split over
four host devices by a process grid, which drives the harness's mesh
path."""
import copy
import dataclasses
import time

from chipbench import cells, harness
from chipbench.peaks import PEAKS

SOLVE, SERVE = "atmosmodd-solve", "atmosmodd-serve"
#: grids of unequal sides, so that an axis taken for another shows, keyed
#: by the chips of the process grid
GRIDS = {1: [16, 12, 8], 4: [16, 8, 12]}
#: ``cell_for`` keys of the sharded solve cell, and their process grids
MESHES = {"mesh": [4, 1, 1], "mesh2x2": [2, 2, 1]}
PEAKS_V5E = PEAKS["TPU v5 lite"]


def small_cell(name: str) -> cells.Cell:
    cell = copy.deepcopy(cells.load_cell(name))
    cell.config["operator"]["grid"] = GRIDS[cells.chips(cell.config)]
    if "drain_s" in cell.traffic:
        cell.traffic["drain_s"] = 3.0
    return cell


def sharded_cell(process_grid=MESHES["mesh"]) -> cells.Cell:
    cell = copy.deepcopy(cells.load_cell(SOLVE))
    cell.config["process_grid"] = list(process_grid)
    chips = cells.chips(cell.config)
    cell.config["operator"]["grid"] = GRIDS[chips]
    name = f"{SOLVE}-{'x'.join(str(p) for p in process_grid)}"
    return dataclasses.replace(cell, name=name, chips=chips)


def cell_for(key: str) -> cells.Cell:
    """``small_cell`` of a cell of ``BENCHMARK.json``; a key of
    ``MESHES``, the sharded one on that process grid."""
    if key in MESHES:
        return sharded_cell(MESHES[key])
    return small_cell(key)


def run(cell: cells.Cell, seed: int = 2 ** 33 + 5, seconds: float = 1.0,
        trace: bool = False) -> harness.Outcome:
    import jax

    return harness.run_cell(cell, seed, seconds, trace,
                            devices=jax.devices(), peaks=PEAKS_V5E,
                            t_start=time.perf_counter())
