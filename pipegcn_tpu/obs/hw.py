"""Published per-chip peaks, keyed by the `device_kind` JAX reports.

One table shared by bench.py, chip_smoke.py and the report CLI: the
denominators of MFU and of every roofline share. Only kinds whose
`device_kind` string has been SEEN on a machine are listed. A kind that
is not in the table raises: a guessed peak (the old "any v5* is a v5p")
makes a utilization figure that is wrong by 2x and looks fine.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float   # dense bf16 FLOP/s per chip
    hbm_bytes_s: float  # HBM bytes/s per chip
    source: str


# device_kind (exact, as jax.devices()[0].device_kind prints it) -> peaks
PEAKS = {
    # seen on the v5e machines of this repository's chip tool
    # (chip_smoke.py, PR 21)
    "TPU v5 lite": ChipPeaks(
        197e12, 819e9,
        'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
        '16 GB HBM2e at 819 GB/s per chip'),
}


class UnknownDevice(LookupError):
    """device_kind is not in the peaks table."""


def peaks_for(kind: str) -> ChipPeaks:
    try:
        return PEAKS[kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device_kind {kind!r} in "
            f"pipegcn_tpu/obs/hw.py (known: {sorted(PEAKS)}). Add the "
            f"kind with its bf16 FLOP/s, HBM bytes/s and the source "
            f"before reporting a utilization on it.") from None


def peak_flops_for(kind: str) -> float:
    return peaks_for(kind).bf16_flops
