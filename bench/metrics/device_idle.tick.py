"""Per-layer metric ``device_idle.tick``: see ``bench/layers.py:device_idle``."""
from bench.layers import device_idle as read  # noqa: F401
