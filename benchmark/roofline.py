"""Least work of one EigenTrust iteration, from the graph's sizes alone,
and the device peaks it is held against.

The bytes are those no implementation can avoid in one step over a
graph of ``n`` peers and ``e`` edges (self-edges dropped): each edge's
source index and weight read once (4 B each), and the score vector read
once and written once (4 B per peer each way).  They are not taken from
any plan's padded layout, so the same work is counted whatever
implements it.  The operations are one multiply and one add per edge;
at two operations per eight bytes the step is bound by memory.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS.name}")
    return table[device_kind]


def iteration_bytes(n: int, e: int) -> int:
    return 8 * e + 8 * n


def iteration_flops(n: int, e: int) -> int:
    return 2 * e


def least_seconds(n: int, e: int, peak: dict) -> float:
    """The least time one iteration could take on the device: the larger
    of its bytes over the memory bandwidth and its operations over the
    compute peak."""
    return max(
        iteration_bytes(n, e) / peak["hbm_bytes_per_s"],
        iteration_flops(n, e) / peak["bf16_flops_per_s"],
    )
