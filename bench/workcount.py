"""The work the capacity sweep needs, counted from what was asked.

A drain (one ``device_sweep`` span) asks for ``scenarios`` capacities
whose own rows number ``rows``: the sum over scenarios of m_max x
rows_per_m, before any padding to lanes or to M and R buckets.  Each
such row needs one node visit per tree level of every tree and one add
per tree; the bytes it needs are its float32 features and its float32
limit, plus the forest once per drain and one int32 answer per scenario.
Padding lanes, padded m and rows, and nodes descended off a row's path
are not work, so a kernel that does them shows a lower share.
"""
from __future__ import annotations

from typing import Dict


def sweep_work(rows: int, scenarios: int, trees: int, depth: int,
               features: int) -> Dict[str, float]:
    """Operations and bytes one drain's asked scenarios need."""
    forest_bytes = trees * ((2 ** depth - 1) * 8 + 2 ** depth * 4)
    return {
        "ops": float(rows) * trees * (depth + 1),
        "bytes": float(rows) * (features + 1) * 4 + forest_bytes
        + 4.0 * scenarios,
    }


def least_seconds(work: Dict[str, float], peak: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of operations over
    the peak rate and bytes over the peak HBM bandwidth."""
    return max(work["ops"] / peak["ops_per_s"],
               work["bytes"] / peak["hbm_bytes_per_s"])
