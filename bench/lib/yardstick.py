"""Peaks, bytes and operations: the fixed arithmetic behind every rate
and roofline share the benchmark reports.

The counts depend on the work a call requires, never on how the program
implements it: padding or transposes that an implementation adds are not
counted.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float       # dense bf16 FLOP/s per chip
    hbm_bytes_s: float      # HBM bandwidth, bytes/s per chip
    hbm_bytes: int          # HBM capacity per chip
    source: str


# Published per-chip peaks, keyed by jax.Device.device_kind. A kind that
# is not here is an error: a guessed peak would make every share wrong.
PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, hbm_bytes_s=819e9, hbm_bytes=16 * 2 ** 30,
        source='Google Cloud documentation, "TPU v5e"'),
}


def peaks_for(kind: str) -> Peaks:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r} "
                       f"(known: {sorted(PEAKS)})") from None


# ---------------------------------------------------------------------------
# the paper's primitives


def op_bytes(op: str, rows: int, seg: int, itemsize: int = 4) -> int:
    """Algorithmic bytes of one segmented call on ``(rows, seg)``: the
    input read once plus the output written once."""
    n = rows * seg * itemsize
    if op == "reduce":
        return n + rows * itemsize
    if op == "scan":
        return 2 * n
    raise ValueError(f"unknown op {op!r}")


def op_adds(op: str, rows: int, seg: int) -> int:
    """Additions the op requires: ``seg - 1`` per segment for a reduce,
    the same for an inclusive scan."""
    if op not in ("reduce", "scan"):
        raise ValueError(f"unknown op {op!r}")
    return rows * (seg - 1)


def op_least_s(op: str, rows: int, seg: int, peaks: Peaks) -> float:
    """The least time the chip could take for one call: the larger of
    bytes over peak bandwidth and additions over peak FLOP/s."""
    return max(op_bytes(op, rows, seg) / peaks.hbm_bytes_s,
               op_adds(op, rows, seg) / peaks.bf16_flops)

