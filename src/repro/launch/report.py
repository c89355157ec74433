"""Generates the EXPERIMENTS.md §Dry-run and §Roofline tables from the
dry-run artifacts.

  PYTHONPATH=src python -m repro.launch.report > experiments/report.md
"""
from __future__ import annotations

import json
from pathlib import Path

from .peaks import chip_peaks

_CHIP = chip_peaks()
PEAK = _CHIP.bf16_flops
HBM = _CHIP.hbm_bytes_per_s
LINK = _CHIP.ici_link_bytes_per_s

PARAMS_B = {
    "phi3-mini-3.8b": (3.7, 3.7), "qwen2.5-32b": (32.8, 32.8),
    "qwen3-8b": (8.0, 8.0), "qwen1.5-110b": (111.2, 111.2),
    "deepseek-v3-671b": (672.0, 37.0),
    "llama4-scout-17b-a16e": (108.6, 16.8),
    "zamba2-1.2b": (1.2, 1.2), "xlstm-350m": (0.35, 0.35),
    "whisper-tiny": (0.039, 0.039), "qwen2-vl-72b": (72.7, 72.7),
}
TOKENS = {"train_4k": 4096 * 256, "prefill_32k": 32768 * 32,
          "decode_32k": 128, "long_500k": 1}


def cell_terms(rec: dict, chips: int) -> dict:
    flops = (rec.get("analytic_global_flops") or 0.0) / chips
    coll = rec.get("collectives") or {}
    if rec["arch"].startswith("solver-"):
        # shard_map jaxprs are per-shard already: no /chips
        byts = rec.get("analytic_global_bytes") or 0.0
        flops = rec.get("analytic_global_flops") or 0.0
        wire = coll.get("total_wire_bytes", 0.0)
    else:
        byts = (rec.get("analytic_global_dot_bytes") or 0.0) / chips
        wire = coll.get("tpu_wire_bytes", coll.get("total_wire_bytes", 0.0))
    t = {"t_c": flops / PEAK, "t_m": byts / HBM, "t_x": wire / LINK}
    t["dominant"] = max(("compute", t["t_c"]), ("memory", t["t_m"]),
                        ("collective", t["t_x"]), key=lambda kv: kv[1])[0]
    arch, shape = rec["arch"], rec["shape"]
    if arch in PARAMS_B and shape in TOKENS:
        act = PARAMS_B[arch][1]
        mult = 3.0 if shape == "train_4k" else 1.0
        mf = 2 * act * 1e9 * TOKENS[shape] * mult / chips
        t["useful"] = mf / flops if flops else 0.0
        bound = max(t["t_c"], t["t_m"], t["t_x"])
        t["frac"] = (mf / PEAK) / bound if bound else 0.0
    return t


def dryrun_table(mesh: str) -> str:
    d = Path("experiments/dryrun") / mesh
    chips = 256 if mesh == "pod16x16" else 512
    lines = [
        f"### {mesh} ({chips} chips)",
        "",
        "| arch | shape | status | compile s | peak GiB/chip | "
        "flops/chip | HBM bytes/chip | wire B/chip | collectives |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for f in sorted(d.glob("*.json")):
        r = json.loads(f.read_text())
        if r["status"] == "skip":
            lines.append(f"| {r['arch']} | {r['shape']} | SKIP "
                         f"({r['reason'][:40]}...) | | | | | | |")
            continue
        if r["status"] != "ok":
            lines.append(f"| {r['arch']} | {r['shape']} | ERROR | | | | | | |")
            continue
        peak = r["memory"].get("peak_memory_in_bytes", 0) / 2 ** 30
        cc = r["collectives"]["counts"]
        cstr = " ".join(f"{k.split('-')[-1][:4]}:{v}"
                        for k, v in sorted(cc.items()))
        lines.append(
            f"| {r['arch']} | {r['shape']} | ok | {r['compile_s']:.0f} "
            f"| {peak:.2f} | {(r.get('analytic_global_flops') or 0)/chips:.2e} "
            f"| {(r.get('analytic_global_dot_bytes') or 0)/chips:.2e} "
            f"| {r['collectives']['total_wire_bytes']:.2e} | {cstr} |")
    return "\n".join(lines)


def roofline_table(mesh: str = "pod16x16") -> str:
    d = Path("experiments/dryrun") / mesh
    chips = 256 if mesh == "pod16x16" else 512
    lines = [
        "| arch | shape | t_compute ms | t_memory ms | t_collective ms | "
        "dominant | MODEL/HLO | roofline frac |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for f in sorted(d.glob("*.json")):
        r = json.loads(f.read_text())
        if r["status"] != "ok":
            continue
        t = cell_terms(r, chips)
        lines.append(
            f"| {r['arch']} | {r['shape']} | {t['t_c']*1e3:.2f} "
            f"| {t['t_m']*1e3:.2f} | {t['t_x']*1e3:.2f} | {t['dominant']} "
            f"| {t.get('useful', 0):.2f} | {t.get('frac', 0):.3f} |")
    return "\n".join(lines)


def main():
    for mesh in ("pod16x16", "pod2x16x16"):
        print(dryrun_table(mesh))
        print()
    print("### Roofline (single-pod, per-chip)")
    print()
    print(roofline_table("pod16x16"))


if __name__ == "__main__":
    main()
