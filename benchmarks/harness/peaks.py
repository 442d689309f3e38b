"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(cloud.google.com/tpu/docs/v5e): per chip 197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB HBM2e at 819 GB/s, 1 600 Gbit/s inter-chip interconnect. JAX
names that chip "TPU v5 lite". A device that is not in the table is an
error, never a default: a share of an unknown peak is no number.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud docs, 'TPU v5e' (cloud.google.com/tpu/docs/v5e)",
    },
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]  # the name newer runtimes report


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it to "
            "benchmarks/harness/peaks.py with its source"
        ) from None
