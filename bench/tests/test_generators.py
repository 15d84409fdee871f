"""The benchmark's generator copies equal the program's."""
import numpy as np
import pytest

from bench import generators as g
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


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("kind,ours,theirs,kw", [
    ("storm", g.burst_storm, traces.burst_storm_trace,
     {"storms_per_hour": 30.0, "coherence": 0.6}),
    ("sparse", g.azure_sparse, traces.azure_sparse_trace,
     {"hot_frac": 0.1, "zipf_s": 1.5}),
])
def test_traces_equal_program(seed, kind, ours, theirs, kw):
    pop = g.popularity(NAMES, 1.2, seed)
    _same(ours(NAMES, duration_s=400, seed=seed, scale_rps=pop, **kw),
          theirs(NAMES, duration_s=400, seed=seed, scale_rps=pop, **kw).rps)


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
