"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture table):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
inter-chip interconnect.  Copied from ``repro.roofline.analysis.DEVICE_PEAKS``
so that a change to the program cannot move the yardstick.  A device kind
that is not in the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float     # dense bf16 FLOP/s per chip
    hbm_bw: float    # HBM bytes/s per chip
    hbm_bytes: int   # HBM capacity per chip
    ici_bw: float    # inter-chip interconnect bytes/s per chip


PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, hbm_bytes=16 * 10**9,
                         ici_bw=1600e9 / 8),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def least_time_s(flops: float, bytes_: float, peaks: Peaks) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peaks.flops, bytes_ / peaks.hbm_bw)
