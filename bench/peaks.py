"""Published peaks of each accelerator the benchmark may run on, keyed by
the ``device_kind`` JAX reports. A kind that is not here is an error.

TPU v5e: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 394 TOP/s int8, 16 GB HBM at 819 GB/s).
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/peaks.py") from None
