"""Per-layer metric ``drain_assemble_ms.refresh``: see ``bench/spans.py:drain_assemble_ms``."""
from bench.spans import drain_assemble_ms as read  # noqa: F401
