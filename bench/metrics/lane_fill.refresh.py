"""Per-layer metric ``lane_fill.refresh``: see ``bench/layers.py:lane_fill``."""
from bench.layers import lane_fill as read  # noqa: F401
