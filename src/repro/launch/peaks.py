"""Published per-chip peak rates, keyed by JAX's ``device_kind``.

The roofline terms of the dry-run report, the roofline benchmark and the
overlap latency model all divide by these.  A device kind that is not in
the table is an error, never a default: rates from one chip silently
applied to another would make every derived share wrong.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float        # FLOP/s
    hbm_bytes_per_s: float   # B/s
    hbm_bytes: float         # capacity, B
    ici_link_bytes_per_s: float  # B/s per inter-chip link
    source: str


PEAKS: Dict[str, ChipPeaks] = {
    # 1,600 Gbit/s of chip-to-chip interconnect over 4 links = 50 GB/s
    # per link
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        ici_link_bytes_per_s=50e9,
        source='Google Cloud documentation, "TPU v5e"'),
}

#: the chip the analytic models (dry-run report, roofline, overlap
#: latency model) describe — the system's deployment target
MODEL_TARGET = "TPU v5 lite"


def chip_peaks(device_kind: str = MODEL_TARGET) -> ChipPeaks:
    """Peak rates of ``device_kind``; raises ``KeyError`` for a kind the
    table does not list."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak rates for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}); add them to "
            "repro.launch.peaks.PEAKS with their source") from None
