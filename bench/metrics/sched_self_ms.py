"""Per-layer metric ``sched_self_ms``: see ``bench/layers.py:sched_self_ms``."""
from bench.layers import sched_self_ms as read  # noqa: F401
