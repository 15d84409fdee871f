"""The benchmark's generator copies equal the program's, and the traces
its cells read stay as they are."""
import hashlib
import json

import numpy as np
import pytest

from bench import generators as g
from bench import world
from bench.tests import helpers
from repro.core import scenarios, traces

SEEDS = [0, 7, 3_000_000_019]
NAMES = [f"sfn{i:03d}" for i in range(12)]


def _same(a, b):
    assert sorted(a) == sorted(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])


@pytest.mark.parametrize("seed", SEEDS)
def test_population_equals_program(seed):
    ours = g.scenario_functions(12, seed=seed)
    theirs = scenarios.scenario_functions(12, seed=seed)
    assert {k: theirs[k].__dict__ for k in theirs} == ours
    np.testing.assert_array_equal(g.zipf_weights(12, 1.2, seed),
                                  scenarios.zipf_weights(12, 1.2, seed))


def _sparse_departures(kw):
    """The names in the order the sparse copy walks them, and its head.

    The copy departs from the program's ``azure_sparse_trace`` twice.
    Given shares, its head is the most popular functions: it walks the
    names by share, largest first, ties by name, where the program
    walks them as given.  And its head's period is a day
    (``g._head_period``), where the program keeps the period it draws.
    Handed the names in the copy's order, the program draws what the
    copy draws: its tail equals the copy's, and its head equals the
    copy's with the drawn period put back."""
    names = list(NAMES)
    if kw["scale_rps"] is not None:
        names.sort(key=lambda fn: (-kw["scale_rps"][fn], fn))
    return names, names[:max(1, round(kw["hot_frac"] * len(names)))]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("kind,ours,theirs,kw", [
    ("storm", g.burst_storm, traces.burst_storm_trace,
     {"storms_per_hour": 30.0, "coherence": 0.6}),
    ("sparse", g.azure_sparse, traces.azure_sparse_trace,
     {"hot_frac": 0.1, "zipf_s": 1.5}),
    ("sparse-unscaled", g.azure_sparse, traces.azure_sparse_trace,
     {"hot_frac": 0.1, "zipf_s": 1.5, "scale_rps": None}),
    ("sparse-wide-head", g.azure_sparse, traces.azure_sparse_trace,
     {"hot_frac": 0.25, "zipf_s": 1.5}),
])
def test_traces_equal_program(seed, kind, ours, theirs, kw, monkeypatch):
    """Each copy equals the program's on the same inputs; the sparse
    copy but for its two departures (``_sparse_departures``)."""
    kw = {"scale_rps": g.popularity(NAMES, 1.2, seed), **kw}
    got = ours(NAMES, duration_s=400, seed=seed, **kw)
    names, head = NAMES, []
    if ours is g.azure_sparse:
        names, head = _sparse_departures(kw)
    want = theirs(names, duration_s=400, seed=seed, **kw).rps
    _same({fn: got[fn] for fn in got if fn not in head},
          {fn: want[fn] for fn in want if fn not in head})
    if head:
        assert not any(np.array_equal(got[fn], want[fn]) for fn in head)
        monkeypatch.setattr(g, "_head_period", lambda drawn: drawn)
        _same(ours(NAMES, duration_s=400, seed=seed, **kw), want)


def _fleet_demand(rps, functions, node_classes):
    """Requested-CPU demand per fleet second, in mean-shaped nodes."""
    w = [max(int(c["weight"]), 1) for c in node_classes]
    node_cpu = sum(c["cpu_mcores"] * k for c, k in zip(node_classes, w)) \
        / sum(w)
    return sum(rps[fn] / f["saturated_rps"] * f["cpu_req"]
               for fn, f in functions.items()) / node_cpu


@pytest.mark.parametrize("trace_seed", [0, 1, 2])
@pytest.mark.parametrize("world_seed", [0, 1])
def test_sparse_head_is_most_popular_and_demand_steady(world_seed,
                                                       trace_seed):
    """At 64 functions the head carries the load: no tail episode fires
    at a head-sized peak, so demand holds steady over a window's span."""
    config = json.loads((helpers.BENCH / "configs" / "jiagu-1k.json")
                        .read_text())
    functions = g.scenario_functions(64, seed=world_seed)
    names = sorted(functions)
    pop = g.popularity(names, 1.2, world_seed)
    rps = g.azure_sparse(names, duration_s=30 + world.HORIZON_S,
                         seed=trace_seed, scale_rps=pop, hot_frac=0.1,
                         zipf_s=1.5)
    rps = g.scale_to_nodes(rps, functions, 2560, config["node_classes"])
    head = {fn for fn in names if (rps[fn] > 0).all()}
    assert head == set(sorted(names, key=lambda fn: (-pop[fn], fn))[:6])
    demand = _fleet_demand(rps, functions, config["node_classes"])
    tens = np.convolve(demand[30:1030], np.ones(10) / 10, mode="valid")
    assert tens.max() / tens.min() <= 1.5
    assert np.percentile(demand, 99) <= 1.6 * demand.mean()


#: sha256 of the trace ``world.build_inputs`` offers each ``jiagu-1k``
#: cell, at microsecond-rps resolution (rounding leaves the last bits
#: of numpy's vectorised sin and exp out of it)
TRACE_SHA256 = {
    "storm": "753abdb5c682ea711b77d674895397fef7daf1a4404dff0cf3f90ab7c8baa462",
    "refresh": "c84cb8727af4f61ac91db65bb044d5eb4f4bb03d6d6e300f168c373b8e6d46d2",
}


@pytest.mark.parametrize("traffic", sorted(TRACE_SHA256))
def test_cell_traces_pinned(traffic):
    """Each cell's trace is pinned: a change to a generator that moves
    what a cell reads shows here."""
    config = json.loads((helpers.BENCH / "configs" / "jiagu-1k.json")
                        .read_text())
    mix = json.loads((helpers.BENCH / "traffic" / f"{traffic}.json")
                     .read_text())
    rps = world.build_inputs(config, mix, seed=0)[0].trace.rps
    h = hashlib.sha256()
    for fn in sorted(rps):
        h.update(fn.encode())
        h.update(np.round(np.asarray(rps[fn], np.float64), 6).tobytes())
    assert h.hexdigest() == TRACE_SHA256[traffic]


@pytest.mark.parametrize("seed", SEEDS)
def test_scenario_scaling_equals_make_scenario(seed):
    """Population, popularity and fleet scaling as ``make_scenario``."""
    scn = scenarios.make_scenario("burst-storm", n_functions=12,
                                  duration_s=300, target_nodes=256,
                                  seed=seed, storms_per_hour=20.0)
    funcs = g.scenario_functions(12, seed=seed)
    names = sorted(funcs)
    rps = g.burst_storm(names, duration_s=300, seed=seed,
                        scale_rps=g.popularity(names, 1.2, seed),
                        storms_per_hour=20.0)
    classes = [{"cpu_mcores": c.res.cpu_mcores, "weight": c.weight}
               for c in scn.node_classes]
    _same(g.scale_to_nodes(rps, funcs, 256, classes), scn.trace.rps)


class _PeriodicRng:
    """The program's generator with storm starts, widths and heights
    replaced by the periodic mix's, every draw still consumed."""

    def __init__(self, real, starts, width, amp):
        self.real, self.starts, self.width, self.amp = real, starts, width, amp
        self.k = 0

    def poisson(self, lam):
        self.real.poisson(lam)
        return len(self.starts)

    def integers(self, *a, **kw):
        v = self.real.integers(*a, **kw)
        if len(a) == 2 and a[0] == 0 and self.k < len(self.starts):
            self.k += 1
            return self.starts[self.k - 1]
        return v

    def uniform(self, lo=0.0, hi=1.0, size=None):
        v = self.real.uniform(lo, hi, size)
        if size is None and (lo, hi) == (20, 90) and self.width is not None:
            return float(self.width)
        if size is None and (lo, hi) == (3.0, 8.0) and self.amp is not None:
            return self.amp
        return v

    def __getattr__(self, name):
        return getattr(self.real, name)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("width,amp", [(None, None), (20, 5.5)])
def test_periodic_storms_differ_only_in_starts_and_sizes(seed, width, amp,
                                                         monkeypatch):
    duration, period, offset = 400, 30, 35
    starts = list(range(offset, duration, period))
    real = np.random.default_rng
    monkeypatch.setattr(traces.np.random, "default_rng",
                        lambda s: _PeriodicRng(real(s), starts, width, amp))
    theirs = traces.burst_storm_trace(NAMES, duration_s=duration, seed=seed,
                                      storms_per_hour=30.0).rps
    monkeypatch.setattr(traces.np.random, "default_rng", real)
    ours = g.burst_storm(NAMES, duration_s=duration, seed=seed,
                         storms_per_hour=30.0, period_s=period,
                         offset_s=offset, width_s=width, amp=amp)
    _same(ours, theirs)
