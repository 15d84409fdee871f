"""The cell ``jiagu-4k.sparse``: four Jiagu cells behind the router, with
the capacity exchange.  It is found from ``BENCHMARK.json`` with its
metrics, and its three readers of the cells' counters read a number on a
traced tiny run of it and nothing on a run of the single-cell storm."""
import copy

import pytest

from bench import harness
from bench.tests import helpers

NEW = ["route_ms", "idle_cell_share", "exchange_hit_share"]
SHARED = ["measure_ms", "place_drains", "drain_ms.place",
          "sweep_roofline.place", "device_idle.tick"]


def _traced(cell, tmp_path):
    runs = []
    real = harness.Run

    def keep(*a, **kw):
        runs.append(real(*a, **kw))
        return runs[-1]

    harness.Run = keep
    try:
        result = helpers.run(cell, seconds=3.0, traced=True,
                             trace_dir=tmp_path)
    finally:
        harness.Run = real
    return result, runs[0]


def _with_readers(cell, names):
    cell = copy.copy(cell)
    cell.per_layer = list(names)
    cell.readers = {m: harness.load_reader(helpers.BENCH, m) for m in names}
    cell.units = dict(cell.units, **{m: "%" for m in names})
    return cell


@pytest.fixture(scope="module")
def sparse(tmp_path_factory):
    found = harness.find_cell("jiagu-4k.sparse")
    cell = harness.Cell("tiny.sparse4", 1, helpers.tiny_config(
        "jiagu-4k", n_functions=16, target_nodes=64), found.traffic,
        found.end_to_end, [], {}, dict(found.units))
    return _traced(_with_readers(cell, NEW),
                   tmp_path_factory.mktemp("sparse"))


def test_the_cell_is_found_with_its_metrics():
    cell = harness.find_cell("jiagu-4k.sparse")
    assert cell.chips == 1
    assert cell.config["cells"] == 4
    assert cell.config["target_nodes"] == 4096
    assert cell.traffic["generator"] == "azure-sparse"
    assert set(cell.end_to_end) == {"place_ms_p50", "place_ms_p90",
                                    "tick_ms", "setup_s"}
    assert sorted(cell.per_layer) == sorted(SHARED + NEW)


def test_a_tiny_traced_run_reads_the_cells_counters(sparse):
    result, run = sparse
    assert result["correct"], result["checks"]
    for name in NEW:
        value = result["metrics"][name]["value"]
        assert value >= 0, name
    assert result["metrics"]["route_ms"]["value"] > 0
    assert 0 < result["metrics"]["idle_cell_share"]["value"] < 100
    assert result["metrics"]["exchange_hit_share"]["value"] <= 100
    route = [s for s in run.spans if s[0] == "route"]
    assert len(route) == run.fleet_s
    assert all({"fns", "homed", "split", "spilled"} <= set(s[4])
               for s in route)
    sched = [s for s in run.spans if s[0] == "schedule"]
    assert all({"cells", "cells_idle", "published", "accepted"}
               <= set(s[4]) for s in sched)


def test_the_readers_read_nothing_on_one_cell(tmp_path):
    result, run = _traced(_with_readers(helpers.tiny_cell("storm"), NEW),
                          tmp_path)
    assert result["correct"]
    assert any(s[0] == "solve.lookup" for s in run.spans)
    for name in NEW:
        assert name not in result["metrics"], name
        assert harness.load_reader(helpers.BENCH, name)(run) is None
