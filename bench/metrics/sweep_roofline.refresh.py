"""Per-layer metric ``sweep_roofline.refresh``: see ``bench/layers.py:sweep_roofline``."""
from bench.layers import sweep_roofline as read  # noqa: F401
