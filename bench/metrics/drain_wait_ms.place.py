"""Per-layer metric ``drain_wait_ms.place``: see ``bench/spans.py:drain_wait_ms``."""
from bench.spans import drain_wait_ms as read  # noqa: F401
