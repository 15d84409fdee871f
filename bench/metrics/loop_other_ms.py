"""Per-layer metric ``loop_other_ms``: see ``bench/layers.py:loop_other_ms``."""
from bench.layers import loop_other_ms as read  # noqa: F401
