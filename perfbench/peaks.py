"""Chip peaks keyed by ``device_kind`` (``peaks.json``), and the roofline
and utilisation arithmetic on them."""

from __future__ import annotations

import json
from pathlib import Path

_TABLE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, table: Path = _TABLE) -> dict:
    """The published peaks of one chip; a kind not in the table is an
    error, never a default."""
    rows = json.loads(table.read_text())
    if device_kind not in rows:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {table.name}; "
            f"known: {sorted(rows)}")
    return rows[device_kind]


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict, dtype: str = "fp32") -> tuple[float, str]:
    """Least time the chip could take for the work over the time it took,
    in percent, and which bound sets that least time ("compute" or
    "memory")."""
    if seconds <= 0:
        raise ValueError(f"kernel time must be > 0, got {seconds}")
    t_compute = flops / peak[f"{dtype}_flops"]
    t_memory = nbytes / peak["hbm_bytes_per_s"]
    bound = "compute" if t_compute >= t_memory else "memory"
    return 100.0 * max(t_compute, t_memory) / seconds, bound


def mfu(flops: float, seconds: float, peak: dict, chips: int = 1,
        dtype: str = "fp32") -> float:
    """Model FLOPs done per second over the chips' peak, in percent."""
    if seconds <= 0:
        raise ValueError(f"window must be > 0 s, got {seconds}")
    return 100.0 * flops / seconds / (chips * peak[f"{dtype}_flops"])
