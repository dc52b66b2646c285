"""Published peaks of each chip the benchmark runs on, keyed by the
``device_kind`` JAX reports. A chip missing from the table is an error."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 394 TOP/s
    # int8, 16 GB of HBM2 at 819 GB/s per chip.
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12,
                    "hbm_bytes": 16e9, "source": "Google Cloud, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add them "
                       f"to benchmarks/chip/peaks.py with their source")
