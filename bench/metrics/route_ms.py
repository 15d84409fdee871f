"""Per-layer metric ``route_ms``: the ``route`` phases (the cell
router's per-tick split of every function's rate over the cells) per
fleet second; None where the program routes over no cells."""
from bench.spans import _total_per_fleet_second


def read(run):
    return _total_per_fleet_second(run, "route")
