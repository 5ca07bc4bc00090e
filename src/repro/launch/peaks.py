"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

The one table every roofline in the repo reads: the benchmarks' achieved-
bandwidth columns (``benchmarks/common.py``), the dry-run's roofline terms
(``launch/dryrun.py``) and the reports built from them. A device kind that
is not in the table raises — a guessed peak would make every roofline
share wrong without saying so.
"""
from __future__ import annotations

import dataclasses

import jax


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    bf16_flops: float       # dense bf16 FLOP/s per chip
    hbm_bytes_s: float      # HBM bandwidth, bytes/s per chip
    ici_bytes_s: float      # chip-to-chip bandwidth, bytes/s per link
    hbm_bytes: int          # HBM capacity per chip
    source: str


PEAKS = {
    "TPU v5 lite": DevicePeaks(
        bf16_flops=197e12, hbm_bytes_s=819e9,
        # 1,600 Gbit/s of interconnect per chip over four links
        ici_bytes_s=50e9, hbm_bytes=16 * 2 ** 30,
        source='Google Cloud documentation, "TPU v5e"'),
    "cpu": DevicePeaks(
        bf16_flops=1e12, hbm_bytes_s=50e9, ici_bytes_s=10e9,
        hbm_bytes=64 * 2 ** 30,
        source="nominal round numbers for CPU test runs, not a "
               "measurement"),
}


def peaks_for(kind: str | None = None) -> DevicePeaks:
    """The peaks of ``kind`` (default: this process's first device)."""
    if kind is None:
        kind = jax.devices()[0].device_kind
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {kind!r}; add it to "
            f"repro.launch.peaks.PEAKS with its source (known: "
            f"{sorted(PEAKS)})") from None
