"""Finding a cell's parts by name: ``BENCHMARK.json`` names the cells, and
each configuration, traffic mix and per-layer metric lives in a file of
its own, found from its name alone:

- ``chipbench/configs/<config>.json`` (the ``file`` of the configuration);
- ``chipbench/traffic/<mix>.json``;
- ``chipbench/metrics/<metric>.py``, whose ``read(record)`` returns the
  metric's value, or None where the run gave it nothing to read;
- ``chipbench/operators/<kind>.py``, the configuration's ``operator.kind``
  (see :func:`operator`).

A configuration's ``process_grid`` ([px, py, pz], one factor per grid
axis) says how its grid is split over chips; the chips are the product.

A later cell, configuration, operator or metric is new files and new
entries; no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
from pathlib import Path
from typing import Callable, Dict, List

from . import traffic as traffic_mod

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str, reported: set) -> bool:
    """A metric with ``workloads`` is reported in those cells; one
    without, in every cell that reports what it moves (per-layer) or in
    every cell (end-to-end)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reported


def load_cell(name: str) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its configuration and
    mix read from their files."""
    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: "
                       f"{sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"])
                        .read_text())
    mix = traffic_mod.load(HERE / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, set())]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _applies(m, name, names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=mix, end_to_end=e2e, per_layer=layer)


def chips(config: dict) -> int:
    """The chips the configuration's ``process_grid`` spans."""
    return math.prod(int(p) for p in config["process_grid"])


def _load(group: str, name: str, what: str):
    path = HERE / group / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {what} {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{group}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str) -> Callable:
    """``read`` of ``chipbench/metrics/<name>.py``."""
    return _load("metrics", name, "reader for metric").read


def operator(kind: str):
    """The module ``chipbench/operators/<kind>.py`` of an operator kind.
    It gives ``params(operator_cfg)``, what the two ``apply`` forms take
    first; ``build(operator_cfg)``, the program's operator through its
    public API in the configuration's ``dtype``; ``apply_f32(params, u)``,
    the benchmark's jnp device check on the 3-D grid; and
    ``apply_f64(params, u)``, the reference's float64 numpy operator,
    which couples each x-plane only to its two neighbours and imports
    nothing of the program."""
    return _load("operators", kind, "operator kind")


def read_metrics(metrics: List[dict], record) -> Dict[str, dict]:
    """Each metric's reader applied to the run's record; a metric whose
    reader finds nothing is left out."""
    out = {}
    for m in metrics:
        value = metric_reader(m["name"])(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
