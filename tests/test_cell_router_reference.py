"""``CellRouter.split`` against the plain reference
(``bench/reference_router.py``): seeded random fleets with cold,
spilled and split functions, the single-cell identity, and the ``route``
span's counters on a traced 4-cell run."""
import numpy as np
import pytest

from bench import reference_router
from repro.core import make_scenario, scenario_world
from repro.core.cells import Cell, CellRouter, cell_scenario_simulation
from repro.telemetry.spans import SpanTracer

#: the largest gap allowed between a share and the reference's, as a
#: share of the function's rate: summation order in float64
REL = 1e-9


class _Scaler:
    on_fn_dirty = None


def fleet_arrays(router, names):
    """The plain arrays the reference takes, read from the router's
    cells as they stand."""
    cl = [c.cluster for c in router.cells]
    return ([[c.sat_count(fn) for fn in names] for c in cl],
            [[c.cached_count(fn) for fn in names] for c in cl],
            [c.total_instances() for c in cl])


def reference_plan(router, rps, specs):
    names = sorted(rps)
    sat, cached, inst = fleet_arrays(router, names)
    shares, counts = reference_router.split(
        names, [rps[n] for n in names], sat, cached, inst,
        [specs[n].saturated_rps for n in names], router.load_cap)
    return names, shares, counts


def assert_plan_matches(router, rps, specs, got):
    """Every share within ``REL`` of the rate of the reference's, and
    each routed function's shares summing to its rate."""
    names, want, counts = reference_plan(router, rps, specs)
    for j, fn in enumerate(names):
        rate = rps[fn]
        mine = [s.get(fn, 0.0) for s in got]
        if rate <= reference_router.MIN_RATE:
            assert not any(mine), fn
            continue
        for c, share in enumerate(mine):
            assert abs(share - want[c][j]) <= REL * rate, (fn, c)
        assert abs(sum(mine) - rate) <= REL * rate, fn
    return counts


def _random_fleet(rng, n_cells, n_fns):
    scenario = make_scenario("burst-storm", n_functions=n_fns,
                             duration_s=10, target_nodes=8,
                             seed=int(rng.integers(1 << 30)))
    specs = scenario.specs
    cells = [Cell(i, scenario.build_cluster(64), None, _Scaler())
             for i in range(n_cells)]
    nodes = [[c.cluster.add_node() for _ in range(3)] for c in cells]
    router = CellRouter(cells, load_cap=0.85)
    rps = {}
    for j, fn in enumerate(sorted(specs)):
        kind = ("cold", "one", "split", "spill", "idle")[j % 5]
        hosts = [] if kind in ("cold", "idle") else list(rng.choice(
            n_cells, size=1 if kind == "one" else int(
                rng.integers(2, n_cells + 1)), replace=False))
        for c in hosts:
            node = nodes[c][int(rng.integers(3))]
            node.deploy(fn, int(rng.integers(1, 6)))
        for c in rng.choice(n_cells, size=int(rng.integers(0, 3))):
            nodes[int(c)][int(rng.integers(3))].add_cached(
                fn, int(rng.integers(1, 3)))
        total = sum(cells[c].cluster.sat_count(fn) for c in hosts)
        cap = total * specs[fn].saturated_rps * router.load_cap
        if kind == "idle":
            rps[fn] = float(rng.choice([0.0, 1e-10]))
        elif kind == "spill":
            rps[fn] = cap * float(rng.uniform(1.1, 3.0))
        elif kind == "split":
            rps[fn] = cap * float(rng.uniform(0.6, 0.99))
        else:
            rps[fn] = float(rng.uniform(0.5, 400.0))
    return router, rps, specs


@pytest.mark.parametrize("seed", range(6))
def test_split_equals_the_reference_on_random_fleets(seed):
    rng = np.random.default_rng(seed)
    n_cells = int(rng.integers(2, 9))
    n_fns = int(rng.integers(16, 65))
    router, rps, specs = _random_fleet(rng, n_cells, n_fns)
    got_counts = {}
    got = router.split(rps, specs, got_counts)
    assert len(got) == n_cells
    counts = assert_plan_matches(router, rps, specs, got)
    assert got_counts == counts
    assert counts["homed"] > 0 and counts["spilled"] > 0
    assert counts["split"] > 0


def test_one_cell_returns_its_input_untouched():
    scenario = make_scenario("burst-storm", n_functions=4, duration_s=10,
                             target_nodes=8, seed=2)
    router = CellRouter([Cell(0, scenario.build_cluster(8), None,
                              _Scaler())])
    fns = sorted(scenario.specs)
    rps = {fns[0]: 3.0, fns[1]: 0.0, fns[2]: 1e-12}
    (plan,) = router.split(rps, scenario.specs)
    assert plan is rps
    assert plan == {fns[0]: 3.0, fns[1]: 0.0, fns[2]: 1e-12}


def test_route_counters_equal_the_reference_on_a_traced_run():
    scenario = make_scenario("azure-sparse", n_functions=12,
                             duration_s=60, target_nodes=16, seed=7)
    world = scenario_world(scenario, n_train=300, n_trees=8)
    sim = cell_scenario_simulation(scenario, "jiagu", n_cells=4,
                                   world=world)
    tracer = SpanTracer()
    sim.tracer = tracer
    router = sim.cell_router
    want = []
    real = router.split

    def checked(rps, specs, counts=None):
        plan = real(rps, specs, counts)
        want.append(assert_plan_matches(router, rps, specs, plan))
        return plan

    router.split = checked
    sim.run()
    got = [{k: s.attrs[k] for k in ("fns", "homed", "split", "spilled")}
           for s in tracer.spans if s.name == "route"]
    assert len(got) == 60
    assert got == want
    assert sum(c["homed"] for c in got) > 0
    assert sum(c["spilled"] for c in got) > 0
    sched = [s for s in tracer.spans if s.name == "schedule"]
    assert len(sched) == 60
    assert all(s.attrs["cells"] == 4 for s in sched)
    assert sum(s.attrs["cells_idle"] for s in sched) == sim.idle_cell_ticks
    assert sum(s.attrs["published"] for s in sched) <= \
        sim.exchange.published
    assert sum(s.attrs["accepted"] for s in sched) <= sim.exchange.accepted
