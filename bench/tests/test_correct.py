"""``correct`` comes out true on a sound run, and false when the timed
path is broken underneath or replaced by the lower-precision control."""
import ml_dtypes
import numpy as np
import pytest

from bench import reference
from bench.tests import helpers
from repro.core.prediction_service import PredictionService
from repro.core.predictor import RandomForestRegressor


def test_sound_runs_are_correct():
    for traffic in ("storm", "refresh"):
        res = helpers.run(helpers.tiny_cell(traffic))
        assert res["correct"], res["checks"]
        assert res["run"]["answers_checked"] > 0
        assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"cap_gap", "table_gap"}
    assert res["run"]["cycles"] >= 2


def _altered(self, queries, _orig=PredictionService.solve_many):
    out = _orig(self, queries)
    return [(c + 1 if c < q[2] else c - 1, r) for q, (c, r) in
            zip(queries, out)]


def _half_left_out(self, queries, _orig=PredictionService.solve_many):
    out = _orig(self, queries)
    return [(c, r) if i % 2 == 0 else (0, 0)
            for i, (c, r) in enumerate(out)]


def _state_unchanged(self, queries, _orig=PredictionService.solve_many):
    first = self.__dict__.setdefault("_first_answer", {})
    out = _orig(self, queries)
    return [(first.setdefault(q[1], c), r) for q, (c, r) in
            zip(queries, out)]


@pytest.mark.parametrize("fault", [_altered, _half_left_out,
                                   _state_unchanged])
@pytest.mark.parametrize("traffic", ["storm", "refresh"])
def test_broken_solver_is_not_correct(fault, traffic, monkeypatch):
    monkeypatch.setattr(PredictionService, "solve_many", fault)
    res = helpers.run(helpers.tiny_cell(traffic))
    assert not res["correct"], res["checks"]


def _keep_cache(monkeypatch):
    """A cache that survives ``invalidate`` and serves across epochs."""
    monkeypatch.setattr(PredictionService, "invalidate", lambda self: None)

    def stale_get(self, key):
        ent = self._cache.get(key)
        return None if ent is None else ent[1]

    monkeypatch.setattr(PredictionService, "_cache_get", stale_get)


def _skip_refresh(monkeypatch):
    """A refresh that returns without re-solving any table."""
    monkeypatch.setattr(PredictionService, "refresh_tables",
                        lambda self, nodes, m_max=None: 0)


@pytest.mark.parametrize("fault", [_keep_cache, _skip_refresh])
def test_stale_refresh_is_not_correct(fault, monkeypatch):
    """A refresh cycle that serves what the previous forest answered."""
    fault(monkeypatch)
    res = helpers.run(helpers.tiny_cell("refresh"))
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("traffic", ["storm", "refresh"])
def test_forest_rounded_at_fit_is_not_correct(traffic, monkeypatch):
    """A program that fits the configuration's forest and then keeps its
    thresholds and leaves in bfloat16 departs from the frozen world."""
    fit = RandomForestRegressor.fit

    def rounded(self, X, y):
        fit(self, X, y)
        a = self.arrays
        for name in ("thr", "leaf"):
            v = getattr(a, name)
            setattr(a, name, v.astype(ml_dtypes.bfloat16).astype(v.dtype))
        return self

    cell = helpers.tiny_cell(traffic)      # its world frozen unrounded
    monkeypatch.setattr(RandomForestRegressor, "fit", rounded)
    res = helpers.run(cell)
    assert not res["correct"], res["checks"]


def test_window_without_answers_is_not_correct():
    data = {"funcs": {}, "forests": [], "log_target": True}
    assert not reference.check(data, [], 1, 10, 2)["correct"]


def test_bfloat16_control_fails(monkeypatch):
    """The reference computed in bfloat16, put in the program's place,
    reads above the limit on the answers and the tables of a run."""
    kept = {}
    orig = reference.check

    def keep(data, answers, seed, n, widest, tables=None):
        kept.update(data=data, answers=answers, tables=tables)
        return orig(data, answers, seed, n, widest, tables)

    monkeypatch.setattr(reference, "check", keep)
    helpers.run(helpers.tiny_cell("refresh"), seconds=2.0)
    entries, missing = reference.distinct_entries(*kept["tables"])
    assert missing == 0
    for distinct in (reference.distinct_answers(kept["answers"]), entries):
        picked = reference.sample(distinct, 5, 2000, 50)
        sound = reference.evaluate(kept["data"], picked)
        control = reference.evaluate(kept["data"], picked, control=True)
        assert sound["gap"] <= reference.CAP_GAP_LIMIT
        assert control["wrong"] > 0
        assert control["gap"] > reference.CAP_GAP_LIMIT


def test_forests_take_turns_in_a_refresh_window(monkeypatch):
    """Every cycle is judged against the forest it installed, and the two
    frozen forests answer differently on the tables a run keeps."""
    kept = {}
    orig = reference.check

    def keep(data, answers, seed, n, widest, tables=None):
        kept.update(data=data, tables=tables)
        return orig(data, answers, seed, n, widest, tables)

    monkeypatch.setattr(reference, "check", keep)
    res = helpers.run(helpers.tiny_cell("refresh"), seconds=2.0)
    assert res["correct"], res["checks"]
    snapshots, colocs, m_max = kept["tables"]
    assert [f for f, _t in snapshots[:4]] == [1, 0, 1, 0]
    entries, _missing = reference.distinct_entries(snapshots, colocs, m_max)
    swapped = {(1 - k[0],) + k[1:]: c for k, c in entries.items()}
    flipped = reference.evaluate(
        kept["data"], [(k, c) for k, cs in swapped.items() for c in cs])
    assert flipped["wrong"] > 0
    assert np.isfinite(flipped["gap"])
