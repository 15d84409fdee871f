"""Per-layer metric ``measure_ms``: see ``bench/spans.py:measure_ms``."""
from bench.spans import measure_ms as read  # noqa: F401
