"""Per-layer metric ``autoscale_self_ms``: see ``bench/spans.py:autoscale_self_ms``."""
from bench.spans import autoscale_self_ms as read  # noqa: F401
