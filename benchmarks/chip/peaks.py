"""Published peaks of the accelerators the benchmark runs on.

Keyed by the ``device_kind`` JAX reports.  A kind that is not in the
table is an error, never a default.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture
page): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s,
1,600 Gbit/s inter-chip interconnect per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
}


def for_kind(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a "
            "sourced row to benchmarks/chip/peaks.py") from None
