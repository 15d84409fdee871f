"""Per-layer metric ``lookup_ms.refresh``: see ``bench/spans.py:lookup_ms``."""
from bench.spans import lookup_ms as read  # noqa: F401
