"""Per-layer metric ``drain_ms.refresh``: see ``bench/layers.py:drain_ms``."""
from bench.layers import drain_ms as read  # noqa: F401
