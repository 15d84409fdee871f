"""Per-layer metric ``place_drains``: see ``bench/spans.py:place_drains``."""
from bench.spans import place_drains as read  # noqa: F401
