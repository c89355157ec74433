"""Run one cell of the benchmark once, on the chips of this machine.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell's configuration, traffic mix and metrics are found by name from
``BENCHMARK.json`` (see :mod:`chipbench.cells`).  ``--trace 0`` reports
the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics from a
profiled run of the same window.  The last line of standard output is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` and, traced, ``breakdown``; the numbers compared with the
reference come last, under ``checks``), and the last lines of standard
error repeat those numbers beside their limits.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
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
from chipbench.peaks import chip_peaks  # noqa: E402


def result_line(cell, outcome, devices, trace: bool) -> dict:
    record = outcome.record
    metrics = cells.read_metrics(
        cell.per_layer if trace else cell.end_to_end, record)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": outcome.memory_peak_bytes}
    out = {"correct": outcome.correct, "attempted": outcome.attempted,
           "failed": outcome.failed, "metrics": metrics, "device": device}
    if trace:
        t = record.trace
        device["busy_s"] = t.busy_s if t else 0.0
        device["window_s"] = t.window_s if t else record.window_s
        if t:
            out["breakdown"] = {"device_ops": [list(p) for p in t.top_ops],
                                "idle_gaps": [list(p) for p in t.idle_gaps]}
    out["checks"] = checks(outcome)
    return out


def checks(outcome) -> dict:
    """The numbers compared with the reference, each beside its limit."""
    return {name: {"value": v, "limit": lim}
            for name, (v, lim) in outcome.checks.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = cells.load_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: no TPU (JAX platform {devices[0].platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} chips; JAX "
              f"sees {len(devices)}", file=sys.stderr)
        return 2
    peaks = chip_peaks(devices[0].device_kind)
    harness.say(f"set-up: {len(devices)} {devices[0].device_kind} at "
                f"{time.perf_counter() - T_START:.3f} s")
    outcome = harness.run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), devices=devices,
                               peaks=peaks, t_start=T_START)
    line = result_line(cell, outcome, devices, bool(args.trace))
    harness.say(f"programs lowered in the window: "
                f"{outcome.lowered_in_window}, compiled: "
                f"{outcome.compiled_in_window}")
    for name, c in line["checks"].items():
        harness.say(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
