"""Per-layer metric ``migrate_ms``: see ``bench/spans.py:migrate_ms``."""
from bench.spans import migrate_ms as read  # noqa: F401
