"""The chip's peaks, by ``device_kind``, each with its source.  A device
that is not in the table is an error, never a default."""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
# 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip.  JAX reports the
# chip as "TPU v5 lite".
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "memory_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
    "TPU v5e": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                "memory_bytes": 16e9,
                "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks for device_kind {device_kind!r}: add it, with its "
            "source, to benchmarks/peaks.py") from None
