"""A cell of four Jiagu cells behind the router, with the capacity
exchange, runs through the harness as it stands and reads correct."""
from bench import harness
from bench.tests import helpers

SPARSE = {"generator": "azure-sparse",
          "params": {"hot_frac": 0.1, "zipf_s": 1.5},
          "warmup_s": 30, "window": "ticks"}


def test_four_cells_decide_exchange_and_read_correct(monkeypatch):
    config = helpers.tiny_config(cells=4, n_functions=16, target_nodes=64)
    units = {"setup_s": "s", "tick_ms": "ms/fleet_s",
             "place_ms_p50": "ms", "place_ms_p90": "ms"}
    cell = harness.Cell("tiny.sparse4", 1, config, SPARSE, list(units), [],
                        {}, units)
    built = []
    instrument = harness._instrument

    def keep(plat, run):
        built.append(plat)
        instrument(plat, run)

    monkeypatch.setattr(harness, "_instrument", keep)
    res = helpers.run(cell, seconds=3.0)
    (plat,) = built
    assert len(plat.simulation.cells) == 4
    assert all(s.metrics.decisions > 0 for s in harness._schedulers(plat))
    assert plat.simulation.exchange.published > 0
    assert res["correct"], res["checks"]
    assert res["checks"]["cap_gap"][0] == 0.0
