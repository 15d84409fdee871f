"""Per-layer metric ``idle_cell_share``: the share of cell-ticks whose
scheduling pass the event core skipped (``cells_idle`` over ``cells``
of the ``schedule`` spans), in %; None where the program runs no
cells."""
from bench.spans import _named


def read(run):
    spans = [s for s in _named(run, "schedule") if "cells" in s[4]]
    cells = sum(s[4]["cells"] for s in spans)
    if not cells:
        return None
    return 100.0 * sum(s[4]["cells_idle"] for s in spans) / cells
