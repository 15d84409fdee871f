"""repro.telemetry: typed metrics registry, span tracing, RunReport /
BENCH trajectories, the regression gate, the dashboard renderer, and
the platform wiring (``telemetry`` config section)."""
import json
import os

import pytest

from repro.core.events import EventHub, JsonlObserver
from repro.platform import Platform
from repro.telemetry import (NULL_TRACER, MetricsObserver,
                             MetricsRegistry, RunReport, SpanTracer,
                             Telemetry, Tolerances, append_bench,
                             bench_path, compare_reports, gate_study,
                             load_bench, promote_baseline,
                             publish_result)
from repro.telemetry.gate import main as gate_main
from repro.telemetry.report import BENCH_SCHEMA, REPORT_SCHEMA


def _quick_manifest(**telemetry):
    m = {
        "scenario": {"kind": "burst-storm", "n_functions": 4,
                     "duration_s": 20, "target_nodes": 8, "seed": 0},
        "prediction": {"n_train": 300, "n_trees": 8},
    }
    if telemetry:
        m["telemetry"] = telemetry
    return m


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_registry_get_or_create_and_kind_conflict():
    reg = MetricsRegistry()
    c = reg.counter("a.count")
    assert reg.counter("a.count") is c
    c.inc()
    c.inc(2.5)
    assert c.snapshot() == {"kind": "counter", "value": 3.5}
    with pytest.raises(ValueError):
        c.inc(-1.0)
    with pytest.raises(TypeError):
        reg.gauge("a.count")          # one name, one type
    g = reg.gauge("b.level")
    g.set(7)
    h = reg.histogram("c.dist")
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    assert len(reg) == 3 and reg.names() == ["a.count", "b.level",
                                             "c.dist"]
    snap = reg.snapshot(bins=2)
    json.dumps(snap)                  # plain JSON-able
    assert snap["b.level"]["value"] == 7.0
    assert snap["c.dist"]["count"] == 3
    assert sum(c for _, c in snap["c.dist"]["buckets"]) == 3


def test_counter_snapshot_integral_values_stay_ints():
    reg = MetricsRegistry()
    c = reg.counter("n")
    c.inc(4)
    assert c.snapshot()["value"] == 4
    assert isinstance(c.snapshot()["value"], int)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_null_tracer_is_free_and_shared():
    cm1 = NULL_TRACER.span("anything", junk=1)
    cm2 = NULL_TRACER.span("other")
    assert cm1 is cm2 is NULL_TRACER.phase("inner")   # one shared no-op CM
    with cm1 as sp:
        assert sp is None
    assert NULL_TRACER.summary() == []
    assert NULL_TRACER.enabled is False


def test_span_tracer_records_emits_and_aggregates():
    emitted = []
    tr = SpanTracer(emit=emitted.append)
    with tr.span("solve", nodes=3) as sp:
        assert sp.name == "solve" and sp.attrs["nodes"] == 3
        with tr.span("inner") as inner:
            assert inner.depth == 1
    assert [s.name for s in tr.spans] == ["inner", "solve"]  # close order
    assert emitted == tr.spans
    assert tr.spans[1].dur_ms >= tr.spans[0].dur_ms >= 0.0
    rows = tr.summary()
    assert {r["name"] for r in rows} == {"solve", "inner"}
    d = tr.spans[1].to_dict()
    assert d["name"] == "solve" and d["nodes"] == 3 and "ms" in d
    json.dumps(d)


def test_span_counters_are_the_ones_the_site_sets():
    """A span carries the counters its site sets, and nothing copied
    from the service's stats."""
    from repro.core.prediction_service import PredictionService
    from repro.core.scenarios import make_scenario, scenario_world

    scn = make_scenario("burst-storm", n_functions=4, duration_s=10,
                        target_nodes=4, seed=0)
    w = scenario_world(scn, n_train=200, n_trees=4, max_depth=4)
    svc = PredictionService(w.predictor, w.store, w.qos, scn.specs)
    svc.tracer = SpanTracer()
    names = sorted(scn.specs)
    node = {names[1]: (2.0, 0.0), names[0]: (1.0, 0.0)}
    svc.solve_many([(node, names[0], 8), (node, names[0], 8),
                    (node, names[1], 8)])
    svc.solve_many([(node, names[0], 8)])
    first, second = [s for s in svc.tracer.spans
                     if s.name == "solve.lookup"]
    assert first.attrs == {"queries": 3, "unique": 2, "cache_hits": 0,
                           "dupes": 1}
    assert second.attrs == {"queries": 1, "unique": 0, "cache_hits": 1,
                            "dupes": 0}
    assert not any(k.startswith("d_") for s in svc.tracer.spans
                   for k in s.attrs)


def test_span_tracer_bounded():
    tr = SpanTracer(max_spans=2)
    for _ in range(5):
        with tr.span("s"):
            pass
    assert len(tr.spans) == 2 and tr.dropped == 3


# ---------------------------------------------------------------------------
# MetricsObserver + publish_result through a real run
# ---------------------------------------------------------------------------


def test_platform_telemetry_section_explicit_on():
    plat = Platform.build(config=_quick_manifest(metrics=True,
                                                 spans=True,
                                                 histogram_bins=4))
    res = plat.run()
    snap = plat.metrics_snapshot()
    assert snap["sim.ticks"]["value"] == res.ticks
    assert snap["run.density"]["value"] == pytest.approx(res.density)
    assert snap["run.qos_violation_rate"]["value"] == pytest.approx(
        res.qos_violation_rate)
    assert snap["schedule.decisions"]["value"] == res.sched.decisions
    assert snap["schedule.instances_placed"]["value"] == \
        res.sched.instances_placed
    # spans reached both the tracer and the registry
    names = {r["name"] for r in plat.span_summary()}
    assert "schedule" in names and "capacity_solve" in names
    assert snap["span.schedule.ms"]["count"] == res.ticks
    json.dumps(snap)


def test_platform_telemetry_defaults_off_without_observers():
    plat = Platform.build(config=_quick_manifest())
    assert plat.telemetry is None
    assert plat.simulation.tracer is NULL_TRACER
    assert plat.service.tracer is NULL_TRACER
    plat.run()
    assert plat.metrics_snapshot() == {} and plat.span_summary() == []


def test_platform_telemetry_defaults_on_with_observers():
    plat = Platform.build(config=_quick_manifest(),
                          observers=[MetricsObserver()])
    assert plat.telemetry is not None
    assert plat.simulation.tracer is plat.telemetry.tracer


def test_publish_result_engine_stats_gauges():
    plat = Platform.build(config=_quick_manifest(metrics=True))
    plat.run()
    snap = plat.metrics_snapshot()
    assert "run.engine.solves" in snap
    assert snap["run.engine.solves"]["kind"] == "gauge"


def test_telemetry_bundle_shares_one_registry():
    t = Telemetry.create()
    assert t.observer.registry is t.registry   # falsy-when-empty trap


# ---------------------------------------------------------------------------
# RunReport + BENCH trajectory persistence
# ---------------------------------------------------------------------------


def _report(study="s", mode="quick", density=30.0, qos=0.01, **meta):
    return RunReport.build(
        study, mode, manifest={"m": 1},
        metrics={"d": density},
        rows=[{"scenario": "burst-storm", "target_nodes": 8,
               "system": "jiagu", "density": density,
               "qos_violation": qos, "cold_ms_p50": 5.0,
               "cold_ms_p99": 40.0, "sched_ms_p50": 1.0,
               "sched_ms_p99": 3.0}],
        meta=meta)


def test_run_report_round_trip_and_schema_check():
    rep = _report()
    d = rep.to_dict()
    json.dumps(d)
    back = RunReport.from_dict(d)
    assert back == rep
    assert rep.schema == REPORT_SCHEMA
    assert rep.git_sha and rep.config_hash
    with pytest.raises(ValueError):
        RunReport.from_dict({**d, "schema": "bogus@9"})


def test_append_bench_seeds_baseline_and_bounds_runs(tmp_path,
                                                     monkeypatch):
    monkeypatch.setenv("REPRO_BENCH_DIR", str(tmp_path))
    p = append_bench(_report(density=30.0))
    assert p == bench_path("s") == str(tmp_path / "BENCH_s.json")
    data = load_bench("s")
    assert data["schema"] == BENCH_SCHEMA
    assert data["baseline"]["metrics"]["d"] == 30.0   # first run seeds it
    assert len(data["runs"]) == 1
    for i in range(5):
        append_bench(_report(density=31.0 + i), max_runs=3)
    data = load_bench("s")
    assert len(data["runs"]) == 3                     # bounded trajectory
    assert data["baseline"]["metrics"]["d"] == 30.0   # baseline pinned
    promote_baseline("s")
    assert load_bench("s")["baseline"]["metrics"]["d"] == 35.0


def test_load_bench_missing_and_bad_schema(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_BENCH_DIR", str(tmp_path))
    assert load_bench("nope") is None
    (tmp_path / "BENCH_bad.json").write_text('{"schema": "x"}')
    with pytest.raises(ValueError):
        load_bench("bad")


# ---------------------------------------------------------------------------
# the regression gate
# ---------------------------------------------------------------------------


def test_gate_passes_within_tolerance_and_fails_beyond():
    base, fresh = _report(density=30.0), _report(density=29.0)
    deltas = compare_reports(base.to_dict(), fresh.to_dict())
    assert not [d for d in deltas if d.status == "FAIL"]
    worse = _report(density=30.0 * 0.9)   # -10% > 5% floor
    deltas = compare_reports(base.to_dict(), worse.to_dict())
    bad = [d for d in deltas if d.status == "FAIL"]
    assert bad and bad[0].metric == "density"


def test_gate_qos_hard_fails_absolute():
    base = _report(qos=0.01)
    ok = compare_reports(base.to_dict(), _report(qos=0.029).to_dict())
    assert not [d for d in ok if d.status == "FAIL"]
    bad = compare_reports(base.to_dict(), _report(qos=0.05).to_dict())
    assert [d for d in bad
            if d.status == "FAIL" and d.metric == "qos_violation"]


def test_gate_mode_mismatch_and_vanished_row():
    base = _report(mode="full")
    deltas = compare_reports(base.to_dict(), _report(mode="quick").to_dict())
    assert deltas[0].status == "FAIL" and deltas[0].metric == "mode"
    fresh = _report(mode="full")
    fresh.rows = []
    deltas = compare_reports(base.to_dict(), fresh.to_dict())
    assert [d for d in deltas
            if d.status == "FAIL" and d.fresh == "missing"]


def test_gate_tolerances_from_env(monkeypatch):
    monkeypatch.setenv("REPRO_GATE_DENSITY_TOL", "0.5")
    assert Tolerances.from_env().density == 0.5


def test_gate_study_missing_baseline_fails(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_BENCH_DIR", str(tmp_path))
    deltas = gate_study("large_cluster")
    assert deltas[0].status == "FAIL"


def test_gate_main_end_to_end(tmp_path, monkeypatch, capsys):
    monkeypatch.setenv("REPRO_BENCH_DIR", str(tmp_path))
    append_bench(_report(study="large_cluster", density=30.0))
    assert gate_main(["--study", "large_cluster"]) == 0
    append_bench(_report(study="large_cluster", density=20.0))
    assert gate_main(["--study", "large_cluster"]) == 1
    out = capsys.readouterr().out
    assert "density" in out and "FAIL" in out
    # a looser CLI tolerance lets the same delta through
    assert gate_main(["--study", "large_cluster",
                      "--density-tol", "0.5"]) == 0
    # promotion moves the baseline; the gate then passes clean
    assert gate_main(["--promote", "large_cluster"]) == 0
    assert gate_main(["--study", "large_cluster"]) == 0


# ---------------------------------------------------------------------------
# JsonlObserver hardening (satellite)
# ---------------------------------------------------------------------------


def test_jsonl_observer_close_contract(tmp_path):
    path = tmp_path / "deep" / "nested" / "ev.jsonl"   # dirs auto-made
    obs = JsonlObserver(str(path), meta={"manifest": {"x": 1}})
    with obs:
        obs.on_scale(1.0, "fn", "release", 2)
        obs.flush()
        lines = path.read_text().splitlines()
        assert len(lines) == 2                  # durable before close
        assert json.loads(lines[0])["event"] == "meta"
    assert obs.closed
    with pytest.raises(ValueError):
        obs.on_scale(2.0, "fn", "release", 1)   # never truncates
    assert len(path.read_text().splitlines()) == 2
    obs.close()                                  # idempotent


def test_jsonl_observer_persists_spans(tmp_path):
    path = tmp_path / "ev.jsonl"
    with JsonlObserver(str(path)) as obs:
        tr = SpanTracer(emit=obs.on_span)
        with tr.span("retrain", epoch=2):
            pass
    rec = json.loads(path.read_text())
    assert rec["event"] == "span" and rec["name"] == "retrain"
    assert rec["epoch"] == 2 and rec["ms"] >= 0.0


# ---------------------------------------------------------------------------
# dashboard
# ---------------------------------------------------------------------------


def test_dashboard_renders_self_contained_html(tmp_path, monkeypatch):
    from repro.telemetry import dashboard as dash
    monkeypatch.setenv("REPRO_BENCH_DIR", str(tmp_path))
    append_bench(_report(study="large_cluster", density=30.0))
    append_bench(_report(study="large_cluster", density=31.0))
    ev = tmp_path / "benchmarks" / "artifacts" / "events"
    ev.mkdir(parents=True)
    with JsonlObserver(str(ev / "burst-storm_8_jiagu.jsonl"),
                       meta={"manifest": {"scheduler":
                                          {"name": "jiagu"}}}) as obs:
        obs._write({"event": "tick", "now": 0.0, "nodes": 4,
                    "instances": 80, "density": 20.0})
        obs._write({"event": "schedule", "now": 1.0, "fn": "f",
                    "placed": 2,
                    "trace": {"filtered": {"no-capacity": 3}}})
        obs._write({"event": "span", "name": "schedule", "seq": 0,
                    "depth": 0, "ms": 1.5})
    out = tmp_path / "dash.html"
    assert dash.main(["--out", str(out)]) == 0
    html = out.read_text()
    assert html.startswith("<!DOCTYPE html>")
    assert "<svg" in html and "large_cluster" in html
    assert "no-capacity" in html            # reason breakdown rendered
    assert "jiagu" in html
    assert "http" not in html.split("</style>")[1]  # no external assets
    # single self-contained file: nothing else was written next to it
    assert [p.name for p in out.parent.glob("dash*")] == ["dash.html"]


def test_dashboard_renders_empty_state(tmp_path):
    from repro.telemetry.dashboard import render
    html = render(root=str(tmp_path), events_dir=str(tmp_path))
    assert "no BENCH_" in html


# ---------------------------------------------------------------------------
# benchmark drivers persist reports only on the bench path
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_capacity_engine_bench_flag_persists_report(tmp_path,
                                                    monkeypatch):
    from benchmarks import capacity_engine
    monkeypatch.setenv("REPRO_BENCH_DIR", str(tmp_path))
    monkeypatch.setattr(capacity_engine, "save_artifact",
                        lambda *a, **k: None)
    # library call: repo root stays clean
    rows = capacity_engine.run(quick=True, bench=False)
    assert rows and not os.path.exists(
        str(tmp_path / "BENCH_capacity_engine.json"))
    # bench call: report lands in the trajectory and gates clean
    capacity_engine.run(quick=True, bench=True)
    data = load_bench("capacity_engine")
    assert data is not None
    assert data["runs"][-1]["rows"][0]["tables_equal"] is True
    deltas = gate_study("capacity_engine")
    assert deltas and not [d for d in deltas if d.status == "FAIL"]
