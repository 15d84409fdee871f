"""The capacity exchange on a tiny 4-cell run with the host drain: every
cache hit that ``solve.lookup`` counts as exchanged was answered by an
entry another cell published, with the reference's capacity at the
current forest, and after a retrain no entry of the old epoch is taken
or counted.

The fleet starts with every function hosted in every cell, so that the
router splits their rates and the cells solve the same colocations;
from an empty fleet each function lives in its home cell alone and no
sibling ever asks what another solved."""
import copy

import pytest

from bench import reference, world
from bench.tests import helpers
from repro.platform import Platform

SPARSE = {"generator": "azure-sparse",
          "params": {"hot_frac": 0.1, "zipf_s": 1.5},
          "warmup_s": 30, "window": "ticks", "trace_seed": 0}
TICKS = 60


def _hits(svc, log):
    """Wrap ``svc.solve_many`` to log, for each query that will hit the
    cache, the cell whose entry answers it (the last writer of the key,
    as the exchange's and the service's own puts were logged) and the
    scenario."""
    real = svc.solve_many

    def solve_many(queries):
        svc._check_epoch()
        for coloc, fn, m_max, *rest in queries:
            key = svc.signature(coloc, fn, m_max, *rest)
            ent = svc._cache.get(key)
            if ent is not None and ent[0] == svc.epoch:
                log.append((svc, key, ent[1], copy.deepcopy(coloc), fn,
                            m_max))
        return real(queries)

    svc.solve_many = solve_many


def _writers(svc, origin):
    """Keep ``origin[(svc, key)]``: the service whose capacity the
    entry holds (itself, or the sibling that published it)."""
    put, accept, inval = svc._cache_put, svc.accept_exchange, svc.invalidate

    def cache_put(key, cap):
        origin[(svc, key)] = svc
        put(key, cap)

    def invalidate():
        for k in [k for k in origin if k[0] is svc]:
            del origin[k]
        inval()

    svc._cache_put, svc.invalidate = cache_put, invalidate
    return accept


@pytest.fixture(scope="module")
def fleet():
    config = helpers.tiny_config("jiagu-4k", n_functions=16,
                                 target_nodes=64)
    scenario, manifest = world.build_inputs(config, SPARSE, 5, spans=True)
    plat = Platform.build(scenario=scenario, config=manifest)
    sim = plat.simulation
    names = sorted(sim.specs)
    for cell in sim.cells:
        for fn in names[::2]:
            cell.cluster.add_node().deploy(fn, 2)
    services = sim.services()
    assert len(services) == 4
    assert all(s.cfg.drain == "host" for s in services)
    ex = sim.exchange
    published = []
    publish = ex.publish

    def logged(src, key, epoch, cap):
        published.append((src, key, epoch, cap))
        publish(src, key, epoch, cap)

    ex.publish = logged
    origin, log = {}, []
    for svc in services:
        accept = _writers(svc, origin)

        def accept_exchange(key, epoch, cap, _svc=svc, _accept=accept):
            took = _accept(key, epoch, cap)
            if took:
                origin[(_svc, key)] = ("sibling", epoch, cap)
            return took

        svc.accept_exchange = accept_exchange
        _hits(svc, log)
    plat.run(TICKS)
    data = reference.load_world(config["world_file"])
    return plat, services, published, origin, log, data


def test_every_exchanged_hit_was_published_by_another_cell(fleet):
    plat, services, published, origin, log, data = fleet
    spans = [s for s in plat.telemetry.tracer.spans
             if s.name == "solve.lookup"]
    counted = sum(s.attrs["exchanged_hits"] for s in spans)
    assert sum(s.attrs["cache_hits"] for s in spans) >= counted
    # the hits whose entry a sibling wrote, as the writers were logged
    mine = [h for h in log if origin.get((h[0], h[1]), h[0]) is not h[0]]
    assert counted == len(mine) > 0
    by_key = {}
    for src, key, epoch, cap in published:
        by_key.setdefault(key, []).append((src, epoch, cap))
    for svc, key, cap, coloc, fn, m_max in mine:
        assert any(src is not svc and e == svc.epoch and c == cap
                   for src, e, c in by_key[key])
        got = reference.evaluate(data, [(reference.scenario_key(
            0, coloc, fn, m_max), cap)])
        assert got["wrong"] == 0 and got["gap"] == 0.0, (fn, cap)


def test_no_entry_of_an_old_epoch_is_taken_or_counted(fleet):
    plat, services, published, origin, log, data = fleet
    ex = plat.simulation.exchange
    assert any(s._exchanged for s in services)
    old = services[0].epoch
    services[0].predictor.retrain_count += 1
    for svc in services:
        svc._check_epoch()
        assert svc.epoch == old + 1
        assert not svc._cache and not svc._exchanged
    accepted = ex.accepted
    src, key, _epoch, cap = published[-1]
    ex.publish(src, key, old, cap)
    assert ex.accepted == accepted
    assert all(key not in s._cache and not s._exchanged for s in services)
    # the old entry's scenario is solved afresh, and counted nowhere
    svc, _key, _cap, coloc, fn, m_max = log[-1]
    tracer = plat.telemetry.tracer
    n0 = len(tracer.spans)
    svc.solve_many([(coloc, fn, m_max)])
    look = [s for s in tracer.spans[n0:] if s.name == "solve.lookup"]
    assert [s.attrs["exchanged_hits"] for s in look] == [0]
    assert [s.attrs["cache_hits"] for s in look] == [0]
