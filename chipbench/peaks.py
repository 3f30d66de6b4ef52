"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

A kind that is not in the table is an error, never a default: a share of a
peak is only as true as the peak.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": 'Google Cloud documentation, "TPU v5e"'},
}


def peaks(device_kind: str) -> dict:
    """The peak table row for ``device_kind``; KeyError for an unknown
    kind."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
