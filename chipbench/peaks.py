"""Published peaks of each chip, keyed by ``device_kind`` as JAX reports
it.  A chip not in the table is an error, never a default."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 16 GB HBM2 at 819 GB/s per chip
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to chipbench/peaks.py")
    return PEAKS[device_kind]
