"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

A device kind that is not in :data:`PEAKS` has no peak: :func:`chip_peak`
raises, and callers that need a duty figure on such a device (the CPU in
tests) pass their peak explicitly.
"""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ChipPeak:
    flops_bf16: float    # FLOP/s
    hbm_bw: float        # bytes/s
    hbm_bytes: int


PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GiB HBM
    # at 819 GB/s per chip.
    "TPU v5 lite": ChipPeak(flops_bf16=197e12, hbm_bw=819e9,
                            hbm_bytes=16 * 1024 ** 3),
}


def chip_peak(device_kind: str) -> ChipPeak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}); pass peak_flops explicitly") from None


def resolve_peak_flops(peak_flops: Optional[float]) -> float:
    """``peak_flops`` if given, else the table's peak for the local device."""
    if peak_flops is not None:
        return peak_flops
    import jax

    return chip_peak(jax.devices()[0].device_kind).flops_bf16


# The roofline target (TPU v5e), for the compile-time analysis.
_V5E = PEAKS["TPU v5 lite"]
PEAK_FLOPS_BF16 = _V5E.flops_bf16
HBM_BW = _V5E.hbm_bw
HBM_BYTES = _V5E.hbm_bytes
ICI_BW_PER_LINK = 50e9       # bytes/s per ICI link
VMEM_BYTES = 128 * 1024 * 1024

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "u4": 1, "s4": 1,
    "f4e2m1fn": 1, "f8e8m0fnu": 1, "f8e4m3": 1, "f8e3m4": 1,
    "f8e4m3b11fnuz": 1, "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
}
