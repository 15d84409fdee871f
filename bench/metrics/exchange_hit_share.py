"""Per-layer metric ``exchange_hit_share``: the share of capacity cache
hits that a sibling cell's solve answered through the capacity exchange
(``exchanged_hits`` over ``cache_hits`` of the ``solve.lookup``
phases), in %; None where no service has joined an exchange or no
lookup hit."""
from bench.spans import _named


def read(run):
    spans = [s for s in _named(run, "solve.lookup")
             if "exchanged_hits" in s[4]]
    hits = sum(s[4]["cache_hits"] for s in spans)
    if not hits:
        return None
    return 100.0 * sum(s[4]["exchanged_hits"] for s in spans) / hits
