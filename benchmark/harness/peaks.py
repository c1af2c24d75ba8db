"""The table of peaks, and the least time a chip needs for given work.  What a
step or a scored row has to move and compute is the model's to say
(``models/<name>.py``).  A device that is not in the table is an error, never
a default."""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
    "TPU v5e": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise SystemExit(f"device kind {device_kind!r} is not in the benchmark's table of peaks")
    return PEAKS[device_kind]


def least_seconds(flops: float, hbm_bytes: float, device_kind: str) -> tuple[float, str]:
    """The least time the chip needs, and which peak bounds it."""
    p = peaks_for(device_kind)
    tf, tb = flops / p["flops_per_s"], hbm_bytes / p["hbm_bytes_per_s"]
    return (tf, "flops") if tf > tb else (tb, "hbm")
