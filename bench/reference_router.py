"""The plain reference for the cell router's traffic shares.

A sharded control plane splits each function's rate over its cells
every tick.  The rule, in the plainest float64 Python:

  * a function with no saturated instance in any cell (cold) goes whole
    to its home cell, ``crc32(name) % cells``;
  * otherwise the cells that hold saturated instances of it are taken
    in order of contention (the cell's instances that are not this
    function's, per saturated instance of it, at least one), then cell
    id; each is loaded up to ``load_cap`` x its saturated instances x
    the function's ``saturated_rps``;
  * what is left after every hosting cell is full (the function
    spilled) is spread over the hosting cells in proportion to their
    saturated instances;
  * a rate of at most 1e-9 is not routed, and a share of at most 1e-12
    is not served.

It imports nothing of the program: the fleet comes as plain arrays.
"""
from __future__ import annotations

import zlib
from typing import Dict, List, Sequence, Tuple

#: a rate at or below this is not routed; a share at or below
#: ``MIN_SHARE`` is not served
MIN_RATE = 1e-9
MIN_SHARE = 1e-12


def home(name: str, cells: int) -> int:
    return zlib.crc32(name.encode()) % cells


def split(names: Sequence[str], rates: Sequence[float],
          sat: Sequence[Sequence[float]], cached: Sequence[Sequence[float]],
          inst_totals: Sequence[float], saturated_rps: Sequence[float],
          load_cap: float) -> Tuple[List[List[float]], Dict[str, int]]:
    """Shares ``[cell][function]`` of ``rates`` and the plan's counts.

    ``sat[c][j]`` and ``cached[c][j]`` are cell c's saturated and cached
    instances of function j, ``inst_totals[c]`` all of cell c's
    instances.  The counts are ``fns`` routed, ``homed`` (cold),
    ``split`` (served by more than one cell) and ``spilled``."""
    n_cells = len(inst_totals)
    shares = [[0.0] * len(names) for _ in range(n_cells)]
    counts = {"fns": 0, "homed": 0, "split": 0, "spilled": 0}
    for j, name in enumerate(names):
        rate = float(rates[j])
        if rate <= MIN_RATE:
            continue
        counts["fns"] += 1
        hosting = [c for c in range(n_cells) if sat[c][j] > 0]
        if not hosting:
            shares[home(name, n_cells)][j] = rate
            counts["homed"] += 1
            continue
        contention = {
            c: (float(inst_totals[c]) - float(sat[c][j])
                - float(cached[c][j])) / max(float(sat[c][j]), 1.0)
            for c in hosting}
        hosting.sort(key=lambda c: (contention[c], c))
        take = {}
        left = rate
        for c in hosting:
            cap = float(sat[c][j]) * float(saturated_rps[j]) * load_cap
            take[c] = min(left, cap)
            left -= take[c]
        if left > MIN_RATE:
            counts["spilled"] += 1
            total_sat = sum(float(sat[c][j]) for c in hosting)
            for c in hosting:
                take[c] += left * float(sat[c][j]) / total_sat
        served = [c for c in hosting if take[c] > MIN_SHARE]
        for c in served:
            shares[c][j] = take[c]
        counts["split"] += len(served) > 1
    return shares, counts
