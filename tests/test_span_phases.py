"""Phase spans inside the control plane's layers: the autoscaler's pass
(``autoscale``, ``migrate``, ``reap``, ``place``), the measurement pass,
``solve_many``'s lookups and a device drain's three phases; their
counters, their nesting, and the profiler annotations a ``SpanTracer``
opens around every span."""
import glob

import pytest

from repro.core.prediction_service import EngineConfig, PredictionService
from repro.core.scenarios import make_scenario, scenario_world
from repro.platform import Platform
from repro.telemetry.spans import ANNOTATION_PREFIX, NULL_TRACER, SpanTracer

MANIFEST = {
    "scenario": {"kind": "burst-storm", "n_functions": 6,
                 "duration_s": 150, "target_nodes": 12, "seed": 3},
    "prediction": {"n_train": 400, "n_trees": 8},
    "telemetry": {"metrics": False, "spans": True},
}

DRAIN_PHASES = ("drain.assemble", "drain.launch", "drain.readback")


def _spans(plat):
    return plat.telemetry.tracer.spans


def _by_seq(spans):
    return {s.seq: s for s in spans}


def _ancestors(span, by_seq):
    while span.parent is not None:
        span = by_seq[span.parent]
        yield span


@pytest.fixture(scope="module")
def storm_spans():
    plat = Platform.build(config=MANIFEST)
    res = plat.run()
    return _spans(plat), res


@pytest.fixture(scope="module")
def drain_service():
    """A bare service on the device drain (the jnp sweep on the CPU),
    warmed so that no drain compiles, and a drain of 32 scenarios."""
    scn = make_scenario("burst-storm", n_functions=8, duration_s=30,
                        target_nodes=8, seed=1)
    w = scenario_world(scn, n_train=200, n_trees=4, max_depth=4)
    svc = PredictionService(w.predictor, w.store, w.qos, scn.specs,
                            EngineConfig(m_max=16, drain="device",
                                         cache=False), engine="jax")
    svc.warm_device()
    names = sorted(scn.specs)
    queries = [({g: (float((k + j) % 4), float(j % 2))
                 for j, g in enumerate(names) if g != fn}, fn, 16)
               for fn in names for k in range(4)]
    return svc, queries


def test_storm_run_records_every_phase_with_its_counters(storm_spans):
    spans, res = storm_spans
    names = {s.name for s in spans}
    assert {"schedule", "capacity_solve", "autoscale", "migrate", "reap",
            "place", "measure", "solve.lookup"} <= names
    auto = [s for s in spans if s.name == "autoscale"]
    assert len(auto) == res.ticks
    assert all(s.attrs["fns"] == 6 for s in auto)
    assert sum(s.attrs["logical_starts"] for s in auto) == \
        res.scaling.logical_cold_starts
    assert sum(s.attrs["released"] for s in auto) == res.scaling.releases
    assert sum(s.attrs["evicted"] for s in auto) == res.scaling.evictions
    mig = [s for s in spans if s.name == "migrate"]
    assert sum(s.attrs["moved"] for s in mig) == res.scaling.migrations
    assert res.scaling.migrations > 0
    assert any(s.attrs["nodes_scanned"] > 0 for s in mig)
    assert any(s.attrs["target_scans"] > 0 for s in mig)
    for s in mig:
        a = s.attrs
        assert a["skipped"] <= a["searches"]
        assert a["index_builds"] <= a["searches"]
    assert any(s.attrs["searches"] > 0 for s in mig)
    place = [s for s in spans if s.name == "place"]
    assert sum(s.attrs["placed"] for s in place) == \
        res.scaling.real_cold_starts
    assert sum(s.attrs["fast"] for s in place) == res.sched.fast
    assert sum(s.attrs["slow"] for s in place) == res.sched.slow
    assert sum(s.attrs["nodes_tried"] for s in place) == \
        res.sched.critical_inference_calls
    assert any(s.attrs["drains"] > 0 for s in place)
    assert all(s.attrs["count"] >= s.attrs["placed"] for s in place)
    assert {"reaped"} <= set(next(s for s in spans
                                  if s.name == "reap").attrs)
    meas = [s for s in spans if s.name == "measure"]
    assert len(meas) == res.ticks and all(s.attrs["nodes"] > 0
                                          for s in meas)
    look = [s for s in spans if s.name == "solve.lookup"]
    for s in look:
        a = s.attrs
        assert a["queries"] == a["cache_hits"] + a["dupes"] + a["unique"]


def test_phase_spans_nest_where_they_run(storm_spans):
    spans, _ = storm_spans
    by_seq = _by_seq(spans)
    for s in spans:
        parent = by_seq.get(s.parent)
        if s.name == "autoscale":
            assert parent.name == "schedule"
        elif s.name in ("migrate", "reap", "place"):
            assert parent.name == "autoscale"
        elif s.name == "measure":
            assert parent is None
        elif s.name == "solve.lookup":
            assert "place" in {a.name for a in _ancestors(s, by_seq)} \
                or parent.name == "capacity_solve"
        if s.depth is None:
            continue
        # a layer span's depth counts only the layer spans around it
        layers = [a for a in _ancestors(s, by_seq) if a.depth is not None]
        assert s.depth == len(layers)


def test_phase_spans_record_parent_and_no_depth():
    tr = SpanTracer()
    with tr.span("schedule"):
        with tr.phase("place"):
            with tr.span("device_sweep"):
                with tr.phase("drain.readback"):
                    pass
    got = {s.name: s for s in tr.spans}
    assert (got["schedule"].depth, got["schedule"].parent) == (0, None)
    assert got["place"].depth is None
    assert got["place"].parent == got["schedule"].seq
    assert got["device_sweep"].depth == 1
    assert got["device_sweep"].parent == got["place"].seq
    assert got["drain.readback"].depth is None
    assert got["drain.readback"].parent == got["device_sweep"].seq
    d = got["place"].to_dict()
    assert d["depth"] is None and d["parent"] == got["schedule"].seq


def _traced(svc, queries):
    """The spans of one drain of ``queries`` on ``svc``."""
    svc.tracer = SpanTracer()
    try:
        svc.solve_many(queries)
        return list(svc.tracer.spans)
    finally:
        svc.tracer = NULL_TRACER


def test_drain_phases_lie_inside_and_account_for_the_drain(drain_service):
    svc, queries = drain_service
    spans = _traced(svc, queries)
    sweep, = [s for s in spans if s.name == "device_sweep"]
    phases = [s for s in spans if s.name in DRAIN_PHASES]
    assert [s.name for s in phases] == list(DRAIN_PHASES)
    for s in phases:
        assert s.parent == sweep.seq
        assert s.t_start_s >= sweep.t_start_s
        assert s.t_start_s + s.dur_ms / 1e3 <= \
            sweep.t_start_s + sweep.dur_ms / 1e3
    total = sum(s.dur_ms for s in phases)
    assert total == pytest.approx(sweep.dur_ms, rel=0.05)
    assert phases[0].attrs["rows"] == sweep.attrs["rows"]
    assert phases[1].attrs["launches"] == sweep.attrs["launches"]


def test_spans_sit_on_the_profiler_clock(drain_service, tmp_path):
    """Under a profiler trace each span opens a prefixed annotation on
    the host plane that nests as the spans do and lasts as long."""
    import jax
    from jax.profiler import ProfileData

    svc, queries = drain_service
    svc.tracer = SpanTracer()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        svc.solve_many(queries)
    finally:
        jax.profiler.stop_trace()
        spans, svc.tracer = svc.tracer.spans, NULL_TRACER
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(ANNOTATION_PREFIX):
                    events.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    want = {ANNOTATION_PREFIX + s.name for s in spans}
    assert {ANNOTATION_PREFIX + "device_sweep",
            ANNOTATION_PREFIX + "drain.readback"} <= want <= set(events)
    (sw0, sw1), = events[ANNOTATION_PREFIX + "device_sweep"]
    (rb0, rb1), = events[ANNOTATION_PREFIX + "drain.readback"]
    assert sw0 <= rb0 and rb1 <= sw1
    for s in spans:
        (a, b), = events[ANNOTATION_PREFIX + s.name]
        if s.dur_ms >= 0.5:
            assert (b - a) / 1e6 == pytest.approx(s.dur_ms, rel=0.10)


def test_null_tracer_never_calls_the_profiler(drain_service, monkeypatch):
    import jax.profiler

    calls = []
    real = jax.profiler.TraceAnnotation

    def counting(name, **kw):
        calls.append(name)
        return real(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    svc, queries = drain_service
    assert svc.tracer is NULL_TRACER
    svc.solve_many(queries)
    plat = Platform.build(config={**MANIFEST, "telemetry": {
        "metrics": False, "spans": False}})
    plat.run(10)
    assert calls == []
    # the patch sees a real tracer's annotations
    svc.tracer = SpanTracer()
    try:
        svc.solve_many(queries[:1])
    finally:
        svc.tracer = NULL_TRACER
    assert ANNOTATION_PREFIX + "solve.lookup" in calls


def test_cell_simulation_records_phases_per_cell():
    plat = Platform.build(config={**MANIFEST, "cells": {"count": 2}})
    res = plat.run(30)
    spans = _spans(plat)
    by_seq = _by_seq(spans)
    auto = [s for s in spans if s.name == "autoscale"]
    assert auto and all(by_seq[s.parent].name == "schedule" for s in auto)
    assert len([s for s in spans if s.name == "measure"]) >= res.ticks
    assert {s.name for s in spans} >= {"migrate", "reap", "place"}
