"""Work the algorithms need, counted from shapes alone.

These counts are the yardstick for roofline shares: they are what an
N-server LU of the given shape must do, whatever implements it, so a
later change to the program cannot move them.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def lu_flops(batch: int, n: int) -> float:
    """Operations of an LU of `batch` (n, n) matrices: 2n³/3 each (the
    multiply-adds of Gaussian elimination, counted as two operations)."""
    return batch * 2.0 * n**3 / 3.0


def lu_bytes(batch: int, n: int, itemsize: int = 4) -> float:
    """Bytes an LU must move at the least: read the matrix once and write
    L and U once."""
    return batch * 3.0 * n * n * itemsize


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of `device_kind`; an unknown kind is
    an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def lu_least_seconds(batch: int, n: int, peak: dict) -> tuple[float, str]:
    """The least time an LU of this shape can take on a chip with `peak`,
    and which bound sets it ("flops" or "bytes")."""
    t_flops = lu_flops(batch, n) / peak["matmul_flops_per_s"]
    t_bytes = lu_bytes(batch, n) / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
