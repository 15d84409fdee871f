"""The readers of the program's phase spans, on traced runs of the tiny
cells, and the rule ``layers.sched_self_ms`` keeps: the phase spans
inside a ``schedule`` span are never counted as its direct children."""
import dataclasses
import json
import math
from types import SimpleNamespace as NS

import pytest

from bench import harness, layers, spans, world

from . import helpers

STORM = ["autoscale_self_ms", "migrate_ms", "measure_ms", "place_drains"]
REFRESH = ["lookup_ms.refresh"]
DRAIN = ["drain_assemble_ms.place", "drain_wait_ms.place",
         "drain_assemble_ms.refresh", "drain_wait_ms.refresh"]
PHASES = {"autoscale", "migrate", "reap", "place", "measure",
          "solve.lookup"}
DRAIN_PHASES = ("drain.assemble", "drain.launch", "drain.readback")


def _cell(traffic, metrics):
    cell = helpers.tiny_cell(traffic)
    units = {m["name"]: m["unit"] for m in json.loads(
        (helpers.BENCH.parent / "BENCHMARK.json").read_text())["per_layer"]}
    cell.per_layer = list(metrics)
    cell.readers = {m: harness.load_reader(helpers.BENCH, m)
                    for m in metrics}
    cell.units.update({m: units[m] for m in metrics})
    return cell


def _traced(traffic, metrics, tmp_path_factory):
    runs = []
    real = harness.Run

    def keep(*a, **kw):
        runs.append(real(*a, **kw))
        return runs[-1]

    harness.Run = keep
    try:
        result = helpers.run(_cell(traffic, metrics), seconds=2.0,
                             traced=True,
                             trace_dir=tmp_path_factory.mktemp(traffic))
    finally:
        harness.Run = real
    return result, runs[0]


@pytest.fixture(scope="module")
def storm(tmp_path_factory):
    return _traced("storm", STORM, tmp_path_factory)


@pytest.fixture(scope="module")
def refresh(tmp_path_factory):
    return _traced("refresh", REFRESH, tmp_path_factory)


def test_traced_storm_run_yields_every_phase(storm):
    result, run = storm
    assert result["correct"]
    names = {s[0] for s in run.spans}
    assert PHASES <= names
    for name, keys in [
            ("autoscale", {"fns", "released", "logical_starts", "evicted"}),
            ("migrate", {"nodes_scanned", "target_scans", "moved"}),
            ("reap", {"reaped"}),
            ("place", {"fn", "count", "placed", "fast", "slow", "drains",
                       "nodes_tried"}),
            ("measure", {"nodes"}),
            ("solve.lookup", {"queries", "unique", "cache_hits",
                              "dupes"})]:
        got = [s for s in run.spans if s[0] == name]
        assert got and all(keys <= set(s[4]) for s in got), name
        assert all(s[3] is None for s in got), name     # no depth


def test_traced_refresh_run_yields_one_lookup_a_cycle(refresh):
    result, run = refresh
    assert result["correct"] and run.cycles > 0
    look = [s for s in run.spans if s[0] == "solve.lookup"]
    assert len(look) == run.cycles
    assert all(s[4]["queries"] > 0 for s in look)


def test_readers_without_a_drain_read_finite_numbers(storm, refresh):
    for (result, _run), metrics in ((storm, STORM), (refresh, REFRESH)):
        for name in metrics:
            value = result["metrics"][name]["value"]
            assert math.isfinite(value) and value >= 0, name
    # the autoscaler's own time lies inside the scheduling phase
    run = storm[1]
    assert 0 < spans.autoscale_self_ms(run) <= layers.sched_self_ms(run) \
        + 1e-9


def test_autoscaler_phases_nest_in_time(storm):
    run = storm[1]
    named = {n: [s for s in run.spans if s[0] == n]
             for n in ("schedule", "autoscale", "migrate", "reap",
                       "place")}
    assert spans.inside(named["autoscale"], named["schedule"]) == \
        named["autoscale"]
    for child in ("migrate", "reap", "place"):
        assert spans.inside(named[child], named["autoscale"]) == \
            named[child], child


def test_readers_read_nothing_from_a_run_without_phases():
    run = NS(spans=[("schedule", 0.0, 5.0, 0, {}),
                    ("device_sweep", 0.001, 1.0, 1, {})],
             fleet_s=10, window_s=1.0)
    for name in STORM + REFRESH + DRAIN:
        assert harness.load_reader(helpers.BENCH, name)(run) is None


# ---------------------------------------------------------------------------
# The device drain on the CPU: decisions' drains and their three phases
# ---------------------------------------------------------------------------


def _layer_parent(span, by_seq):
    """The nearest enclosing layer span (phase spans skipped)."""
    while span.parent is not None:
        span = by_seq[span.parent]
        if span.depth is not None:
            return span
    return None


def _rule_children(tuples):
    """The spans ``layers.sched_self_ms`` subtracts from each schedule
    span: those opened within it at its depth + 1."""
    sched = sorted((s for s in tuples if s[0] == "schedule"),
                   key=lambda s: s[1])
    out = set()
    for sp in tuples:
        for parent in sched:
            if sp is not parent and parent[1] <= sp[1] and \
                    sp[1] + sp[2] / 1e3 <= parent[1] + parent[2] / 1e3 \
                    + 1e-9 and sp[3] == parent[3] + 1:
                out.add(id(sp))
    return out


@pytest.fixture(scope="module")
def device_storm():
    """A tiny storm run whose service drains on the device (the jnp
    sweep on the CPU), switched as ``Platform.build`` does on a TPU."""
    from repro.platform import Platform

    cell = helpers.tiny_cell("storm")
    scenario, manifest = world.build_inputs(cell.config, cell.traffic, 5,
                                            spans=True)
    plat = Platform.build(scenario=scenario, config=manifest)
    svc = plat.service
    svc.set_engine("jax")
    svc.cfg = dataclasses.replace(svc.cfg, drain="device")
    svc.warm_device()
    ticks = 90
    plat.run(ticks)
    tr = plat.telemetry.tracer
    tuples = [(s.name, s.t_start_s, s.dur_ms, s.depth, dict(s.attrs))
              for s in tr.spans]
    return tr.spans, tuples, NS(spans=tuples, fleet_s=ticks, window_s=1.0)


def test_sched_self_ms_subtracts_exactly_the_layer_children(device_storm):
    objs, tuples, run = device_storm
    by_seq = {s.seq: s for s in objs}
    want = set()
    kinds = set()
    for s, t in zip(objs, tuples):
        if s.name in ("capacity_solve", "device_sweep"):
            lp = _layer_parent(s, by_seq)
            if lp is not None and lp.name == "schedule":
                want.add(id(t))
                via = by_seq[s.parent].name
                kinds.add((s.name, via))
    # the flush's solves, and decisions' drains reached through place
    assert ("capacity_solve", "schedule") in kinds
    assert ("device_sweep", "place") in kinds
    assert _rule_children(tuples) == want
    sched = [t for t in tuples if t[0] == "schedule"]
    kids = [t for t in tuples if id(t) in want]
    assert layers.sched_self_ms(run) == pytest.approx(
        (sum(t[2] for t in sched) - sum(t[2] for t in kids)) / run.fleet_s)


def test_drain_phases_account_for_the_device_drains(device_storm):
    objs, _tuples, run = device_storm
    by_seq = {s.seq: s for s in objs}
    sweeps = [s for s in objs if s.name == "device_sweep"]
    phases = [s for s in objs if s.name in DRAIN_PHASES]
    assert sweeps and len(phases) == 3 * len(sweeps)
    for s in phases:
        parent = by_seq[s.parent]
        assert parent.name == "device_sweep"
        assert parent.t_start_s <= s.t_start_s
        assert s.t_start_s + s.dur_ms / 1e3 <= \
            parent.t_start_s + parent.dur_ms / 1e3
    assert sum(s.dur_ms for s in phases) == pytest.approx(
        sum(s.dur_ms for s in sweeps), rel=0.05)
    for name in DRAIN + ["place_drains"]:
        value = harness.load_reader(helpers.BENCH, name)(run)
        assert value is not None and value > 0, name
    assert spans.drain_assemble_ms(run) + spans.drain_wait_ms(run) <= \
        layers.drain_ms(run)
