"""The table of peaks and the work model: what a step or a scored row has to
move and compute, from shapes and ids alone.  A device that is not in the
table is an error, never a default."""

from __future__ import annotations

import numpy as np

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
    "TPU v5e": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise SystemExit(f"device kind {device_kind!r} is not in the benchmark's table of peaks")
    return PEAKS[device_kind]


def modeled_step_bytes(ids: np.ndarray, row_dim: int, accum_cols: int) -> tuple[int, int]:
    """Copy of ``profiling.modeled_step_bytes``: the HBM bytes one order-2
    sparse train step cannot avoid (ids read, gather, backward re-read,
    row-gradient and segment-sum writes, table and accumulator read-modify-
    write over the unique rows).  Returns (bytes, unique ids)."""
    ids = np.asarray(ids)
    m = int(ids.size)
    uniq = int(np.unique(ids).size)
    row = int(row_dim) * 4
    total = m * 4 + 4 * m * row + 2 * uniq * row + 2 * uniq * int(accum_cols) * 4
    return int(total), uniq


def modeled_step_flops(m: int, uniq: int, row_dim: int) -> int:
    """Forward and backward of the order-2 interaction per occurrence
    (about 7 per factor and 4 for the bias) and 6 per element of Adagrad."""
    k = row_dim - 1
    return int(m * (7 * k + 4) + uniq * row_dim * 6)


def modeled_score_bytes(rows: int, nnz: int, row_dim: int) -> int:
    """One scored row: its ids and values read, its table rows gathered, one
    score written."""
    return int(rows * (nnz * (4 + 4 + row_dim * 4) + 4))


def least_seconds(flops: float, hbm_bytes: float, device_kind: str) -> tuple[float, str]:
    """The least time the chip needs, and which peak bounds it."""
    p = peaks_for(device_kind)
    tf, tb = flops / p["flops_per_s"], hbm_bytes / p["hbm_bytes_per_s"]
    return (tf, "flops") if tf > tb else (tb, "hbm")
