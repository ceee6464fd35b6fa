"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

The benchmark's own copy, so that a change to the program cannot move the
yardstick.  A device kind that is not listed has no peak and is an error.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GiB of HBM at 819 GB/s per chip.
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16 * 1024 ** 3},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(f"chipbench: no published peak for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None
