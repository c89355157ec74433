"""§Roofline: the three roofline terms per (arch x shape) from the
compiled dry-run artifacts (experiments/dryrun/).

    compute term    = HLO_FLOPs / (chips x 197 TFLOP/s bf16)
    memory term     = HLO_bytes / (chips x 819 GB/s)
    collective term = wire_bytes / (chips x 50 GB/s/link); wire bytes are
                      parsed from the compiled HLO (hlo_analysis.py) since
                      cost_analysis() does not report collectives.

Also reported: MODEL_FLOPS = 6*N(_active)*D and the usefulness ratio
MODEL_FLOPS / HLO_FLOPs (catches remat/redundancy waste), the dominant
term, and what would move it (EXPERIMENTS.md §Roofline).

NOTE on chips: dry-run cost_analysis is for the per-device SPMD program,
so the terms below use the per-device numbers directly (no extra /chips).
"""
from __future__ import annotations

import json
from pathlib import Path

from repro.launch.peaks import chip_peaks

from .common import fmt_table, run_cpu_child, write_json

_CHIP = chip_peaks()
PEAK = _CHIP.bf16_flops            # bf16 FLOP/s per chip
HBM = _CHIP.hbm_bytes_per_s        # B/s per chip
LINK = _CHIP.ici_link_bytes_per_s  # B/s per ICI link

# parameter counts (total, active) in billions — from the configs
PARAMS_B = {
    "phi3-mini-3.8b": (3.7, 3.7),
    "qwen2.5-32b": (32.8, 32.8),
    "qwen3-8b": (8.0, 8.0),
    "qwen1.5-110b": (111.2, 111.2),
    "deepseek-v3-671b": (672.0, 37.0),
    "llama4-scout-17b-a16e": (108.6, 16.8),
    "zamba2-1.2b": (1.2, 1.2),
    "xlstm-350m": (0.35, 0.35),
    "whisper-tiny": (0.039, 0.039),
    "qwen2-vl-72b": (72.7, 72.7),
}

TOKENS = {"train_4k": 4096 * 256, "prefill_32k": 32768 * 32,
          "decode_32k": 128, "long_500k": 1}
TRAIN_MULT = {"train_4k": 3.0}     # fwd+bwd = 3x fwd model flops


def roofline_row(rec: dict, chips: int):
    """Terms per chip.

    compute/memory: analytic jaxpr counts (global / chips) — the compiled
    cost_analysis undercounts scan bodies (counted once) and oneDNN
    matmuls (zero flops on CPU backend), so it is kept only as an
    auxiliary lower bound ("hlo_flops").  memory uses matmul-adjacent
    bytes (fusion-optimistic).  collective: wire bytes parsed from the
    compiled per-device HLO with layer-scan trip-count correction.
    """
    arch, shape = rec["arch"], rec["shape"]
    flops = (rec.get("analytic_global_flops") or 0.0) / chips
    if arch.startswith("solver-"):
        # stencil matvecs have no dot_general: elementwise streams ARE the
        # HBM traffic -> unfused byte count (upper bound; select/where
        # chains double-count), and shard_map jaxprs are already
        # per-shard so no /chips.  No bf16 discount (genuine f64/f32).
        byts = rec.get("analytic_global_bytes") or 0.0
        flops = rec.get("analytic_global_flops") or 0.0
        coll = rec.get("collectives") or {}
        wire = coll.get("total_wire_bytes", 0.0)
    else:
        byts = (rec.get("analytic_global_dot_bytes")
                or rec.get("analytic_global_bytes") or 0.0) / chips
        coll = rec.get("collectives") or {}
        wire = coll.get("tpu_wire_bytes", coll.get("total_wire_bytes", 0.0))
    t_c = flops / PEAK
    t_m = byts / HBM
    t_x = wire / LINK
    dom = max((t_c, "compute"), (t_m, "memory"), (t_x, "collective"))[1]
    if arch in PARAMS_B and shape in TOKENS:
        tot, act = PARAMS_B[arch]
        mult = TRAIN_MULT.get(shape, 1.0)
        model_flops = 2 * act * 1e9 * TOKENS[shape] * mult / chips
        useful = model_flops / flops if flops else 0.0
        bound = max(t_c, t_m, t_x)
        frac = (model_flops / PEAK) / bound if bound else 0.0
    else:  # solver cells: useful flops == analytic flops (per iteration)
        model_flops = flops
        useful = 1.0
        bound = max(t_c, t_m, t_x)
        frac = t_c / bound if bound else 0.0
    return {
        "arch": arch, "shape": shape,
        "t_compute_s": t_c, "t_memory_s": t_m, "t_collective_s": t_x,
        "dominant": dom, "model_flops_per_chip": model_flops,
        "hlo_flops_per_chip": rec.get("flops"),
        "useful_ratio": useful, "roofline_fraction": frac,
    }


QUICK_GRID = (256, 32, 32)       # nx must divide by the 256-chip mesh
QUICK_SOLVERS = ("p-bicgsafe", "ssbicgsafe2")


def _ensure_quick_artifacts(out: Path, mesh: str) -> None:
    """Compile the small-grid solver cells in a subprocess (the dry-run
    module forces 512 fake host devices via XLA_FLAGS at import — it
    must not pollute this process)."""
    nx, ny, nz = QUICK_GRID
    for solver in QUICK_SOLVERS:
        cell = out / mesh / f"solver-{solver}__poisson{nx}x{ny}x{nz}.json"
        if cell.exists():
            continue
        run_cpu_child(
            ["-m", "repro.launch.dryrun_solver", "--solver", solver,
             "--nx", nx, "--ny", ny, "--nz", nz, "--maxiter", 50,
             "--out", out, "--force"], timeout=600)


def overlap_claims(recs: dict) -> dict:
    """Roofline-model form of the paper's claim: the pipelined solver's
    per-iteration reduction wire time fits inside the matvec stream
    (compute + HBM terms) it is scheduled to overlap with.  The (9, m)
    fused reduction moves scalars; the halo exchange moves faces — so
    the reduction term should be orders of magnitude under the window.
    """
    pip = next((r for k, r in recs.items()
                if k.startswith("solver-p-bicgsafe__")), None)
    if pip is None:
        return {}
    raw = pip.get("_collectives", {})
    red_wire = (raw.get("wire_bytes") or {}).get("all-reduce", 0.0)
    t_red = red_wire / LINK
    window = pip["t_compute_s"] + pip["t_memory_s"]
    return {
        "pipelined_hides_reduction": bool(t_red <= window),
        "reduction_wire_bytes_per_iter": red_wire,
        "t_reduction_s": t_red,
        "overlap_window_s": window,
    }


def run(quick: bool = False, mesh: str = "pod16x16"):
    if quick:
        base = Path("experiments/runtime/dryrun_quick")
        _ensure_quick_artifacts(base, mesh)
        d = base / mesh
    else:
        d = Path("experiments/dryrun") / mesh
    chips = 256 if mesh == "pod16x16" else 512
    rows, recs = [], {}
    if not d.exists():
        print(f"(no dry-run artifacts under {d}; run repro.launch.dryrun)")
        return {}
    for f in sorted(d.glob("*.json")):
        rec = json.loads(f.read_text())
        if rec.get("status") != "ok":
            continue
        r = roofline_row(rec, chips)
        r["_collectives"] = rec.get("collectives") or {}
        recs[f"{r['arch']}__{r['shape']}"] = r
        rows.append([
            r["arch"], r["shape"],
            f"{r['t_compute_s']*1e3:.2f}", f"{r['t_memory_s']*1e3:.2f}",
            f"{r['t_collective_s']*1e3:.2f}", r["dominant"],
            f"{r['useful_ratio']:.2f}", f"{r['roofline_fraction']:.3f}"])
    print(f"\n== bench_roofline ({mesh}, per-chip terms"
          f"{', quick grid' if quick else ''}) ==")
    print(fmt_table(rows, ["arch", "shape", "t_comp ms", "t_mem ms",
                           "t_coll ms", "dominant", "useful",
                           "roofline_frac"]))
    claims = overlap_claims(recs)
    if claims:
        print(f"  pipelined reduction {claims['t_reduction_s']:.2e}s vs "
              f"overlap window {claims['overlap_window_s']:.2e}s -> "
              f"hidden={claims['pipelined_hides_reduction']}")
    doc = {"mesh": mesh, "mode": "quick" if quick else "full",
           "cells": recs, "claims": claims}
    write_json("bench_roofline.json", doc)
    return doc


if __name__ == "__main__":
    run()
