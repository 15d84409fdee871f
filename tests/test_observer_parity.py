"""Observer/telemetry parity: instrumentation must observe, never
perturb.

The same seeded scenario runs three ways — bare (zero observers, no
telemetry), through an ``EventHub`` with counting observers +
telemetry (``MetricsObserver`` + ``SpanTracer``), and with a
``JsonlObserver`` persisting every stream — and the simulation outcome
must be bit-identical: placements (density series), QoS accounting,
scheduler decision counters, scaling transitions.  This is the gate
that lets ``Platform.build`` default telemetry on whenever observers
are attached."""
import json
import math

import pytest

from repro.core.events import Observer, JsonlObserver
from repro.platform import Platform

MANIFEST = {
    "scenario": {"kind": "burst-storm", "n_functions": 6,
                 "duration_s": 40, "target_nodes": 12, "seed": 3},
    "prediction": {"n_train": 400, "n_trees": 8},
}


class CountingObserver(Observer):
    def __init__(self):
        self.ticks = 0
        self.schedules = 0
        self.scales = 0
        self.spans = 0

    def on_tick(self, now, sim):
        self.ticks += 1

    def on_schedule(self, now, fn, placements, trace=None):
        self.schedules += 1

    def on_scale(self, now, fn, event, count):
        self.scales += 1

    def on_span(self, span):
        self.spans += 1


def _fingerprint(res):
    """Everything the arms must agree on, bit for bit.  Wall-clock
    latency metrics are deliberately excluded (instrumented runs spend
    different real time); counters and simulated state are not."""
    s, a = res.sched, res.scaling
    return {
        "density": res.density,
        "density_series": list(res.density_series),
        "qos": res.qos_violation_rate,
        "requests": res.requests,
        "violated": res.violated_requests,
        "nodes_peak": res.nodes_peak,
        "node_seconds": res.node_seconds,
        "instance_seconds": res.instance_seconds,
        "decisions": s.decisions,
        "instances_placed": s.instances_placed,
        "fast": s.fast, "slow": s.slow, "failed": s.failed,
        "critical_rows": s.critical_inference_rows,
        "real_cold_starts": a.real_cold_starts,
        "logical_cold_starts": a.logical_cold_starts,
        "releases": a.releases,
        "evictions": a.evictions,
        "migrations": a.migrations,
    }


def _run(observers=()):
    plat = Platform.build(config=MANIFEST, observers=list(observers))
    return plat, _fingerprint(plat.run())


def test_bare_hub_and_jsonl_runs_are_bit_identical(tmp_path):
    bare_plat, bare = _run()
    assert bare_plat.telemetry is None           # nothing attached

    counters = [CountingObserver(), CountingObserver()]
    hub_plat, hub = _run(counters)
    assert hub_plat.telemetry is not None        # auto-on with observers

    jsonl = JsonlObserver(str(tmp_path / "events.jsonl"),
                          meta={"manifest": MANIFEST})
    with jsonl:
        _, persisted = _run([jsonl])

    assert bare == hub == persisted
    assert all(math.isfinite(v) for v in bare["density_series"])

    # the observers actually saw the run (this wasn't a no-op parity)
    for c in counters:
        assert c.ticks == MANIFEST["scenario"]["duration_s"]
        assert c.schedules > 0 and c.scales > 0 and c.spans > 0
    events = [json.loads(l)
              for l in (tmp_path / "events.jsonl").read_text().splitlines()]
    kinds = {e["event"] for e in events}
    assert {"meta", "tick", "schedule", "scale", "span"} <= kinds
    # the autoscaler's and the loop's phase spans were on in every
    # instrumented arm
    spans = {e["name"] for e in events if e["event"] == "span"}
    assert {"schedule", "autoscale", "migrate", "reap", "place",
            "measure", "solve.lookup"} <= spans


def test_telemetry_registry_agrees_with_sim_counters():
    plat = Platform.build(
        config={**MANIFEST, "telemetry": {"metrics": True,
                                          "spans": True}})
    res = plat.run()
    snap = plat.metrics_snapshot()
    assert snap["sim.ticks"]["value"] == res.ticks
    assert snap["schedule.decisions"]["value"] == res.sched.decisions
    assert snap["schedule.instances_placed"]["value"] == \
        res.sched.instances_placed
    scale_total = sum(m["value"] for name, m in snap.items()
                     if name.startswith("scale."))
    a = res.scaling
    # one scale event per transition kind fired with its count
    assert scale_total == a.real_cold_starts + a.logical_cold_starts \
        + a.releases + a.evictions + a.migrations
    assert snap["run.density"]["value"] == pytest.approx(res.density)


def test_explicit_telemetry_does_not_change_results():
    _, bare = _run()
    plat = Platform.build(
        config={**MANIFEST, "telemetry": {"metrics": True,
                                          "spans": True}})
    instrumented = _fingerprint(plat.run())
    assert bare == instrumented
