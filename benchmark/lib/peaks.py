"""Published peaks of the chips the benchmark may run on, keyed by the
`device_kind` JAX reports. A kind that is not here is an error, never a
default: a roofline share against a guessed peak says nothing.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" system architecture page: one
    # chip has 197 TFLOP/s in bf16, 16 GB of HBM2e at 819 GB/s.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e: 197 TFLOP/s "
                  "bf16, 819 GB/s HBM, 16 GB per chip)",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of `device_kind`; raises for a kind the table lacks."""
    if device_kind not in PEAKS:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/lib/peaks.py; "
            f"add its published peaks with their source before measuring "
            f"on it (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]
