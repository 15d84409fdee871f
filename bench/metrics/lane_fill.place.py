"""Per-layer metric ``lane_fill.place``: see ``bench/layers.py:lane_fill``."""
from bench.layers import lane_fill as read  # noqa: F401
