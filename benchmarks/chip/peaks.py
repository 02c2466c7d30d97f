"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A device that is not here is an error.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s in
bfloat16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect).
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flop_per_s": 197e12,
        "int8_op_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 1600e9 / 8,
    },
}


def lookup(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it to peaks.py")
    return PEAKS[device_kind]
