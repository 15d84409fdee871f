"""The repro.platform control-plane API: config-tree round trip,
build-time validation, name-based registries, pluggable Router /
picker capabilities, and the observer hooks."""
import json
import os

import pytest

from repro.core import (Autoscaler, Cluster, GroundTruth, K8sScheduler,
                        ProfileStore, QoSStore, ScalingConfig,
                        make_scenario, scenario_world,
                        synthetic_functions)
from repro.platform import (CapacityProvider, EqualSplitRouter,
                            LogicalStartPicker, Observer, Platform,
                            PlatformConfig, PlatformConfigError,
                            ReleasePicker, Router, get_router,
                            get_scenario_builder, get_trace,
                            register_router, register_scheduler,
                            registered_routers, registered_scenarios,
                            registered_schedulers, registered_traces,
                            scheduler_entry)

SAMPLE_CSV = os.path.join(os.path.dirname(__file__), "data",
                          "sample_trace.csv")

SMALL = {
    "scenario": {"kind": "burst-storm", "n_functions": 3,
                 "duration_s": 40, "target_nodes": 6, "seed": 0},
    "prediction": {"n_train": 250, "n_trees": 6},
}


@pytest.fixture(scope="module")
def small_world():
    """One trained world shared by the behavioural tests (the scenario
    only varies in scheduler/router/observer wiring)."""
    cfg = PlatformConfig.from_dict(SMALL)
    from repro.platform import scenario_from_config
    scenario = scenario_from_config(cfg)
    world = scenario_world(scenario, n_train=250, n_trees=6)
    return cfg, scenario, world


# ---------------------------------------------------------------------------
# Config tree
# ---------------------------------------------------------------------------


def test_config_roundtrip_defaults():
    cfg = PlatformConfig()
    d = cfg.to_dict()
    json.dumps(d)                      # manifest must be JSON-able
    assert PlatformConfig.from_dict(d) == cfg


def test_config_roundtrip_custom():
    cfg = PlatformConfig.from_dict({
        "cluster": {"node_classes": [
            {"name": "std", "weight": 2},
            {"name": "huge", "cpu_mcores": 96_000.0,
             "mem_mb": 262_144.0, "weight": 1}],
            "max_nodes": 128},
        "scenario": {"kind": "diurnal-shift", "n_functions": 5,
                     "duration_s": 90, "target_nodes": 12, "seed": 3,
                     "spec_seed": 8, "trace_kw": {"n_regions": 2}},
        "scheduler": {"name": "gsight", "max_candidates": 3},
        "scaling": {"dual_staged": True, "release_s": 20.0},
        "prediction": {"schema_version": 2, "n_train": 100},
        "simulation": {"collect_samples": True, "seed": 4},
    })
    d = cfg.to_dict()
    json.dumps(d)
    back = PlatformConfig.from_dict(d)
    assert back == cfg
    assert back.cluster.node_classes[1].cpu_mcores == 96_000.0
    assert back.scenario.trace_kw == {"n_regions": 2}
    # node-class manifests materialize into real NodeClass topology
    classes = back.cluster.to_node_classes()
    assert [c.name for c in classes] == ["std", "huge"]
    assert classes[1].res.mem_mb == 262_144.0


def test_from_dict_rejects_unknown_sections_and_keys():
    with pytest.raises(PlatformConfigError, match="unknown sections"):
        PlatformConfig.from_dict({"schedulerz": {}})
    with pytest.raises(PlatformConfigError, match="unknown keys"):
        PlatformConfig.from_dict({"scheduler": {"nam": "jiagu"}})
    with pytest.raises(PlatformConfigError, match="expected a dict"):
        PlatformConfig.from_dict({"scaling": 7})


# ---------------------------------------------------------------------------
# Build-time validation
# ---------------------------------------------------------------------------


def test_validate_schema_v2_needs_engine_path():
    cfg = PlatformConfig.from_dict({
        "prediction": {"schema_version": 2},
        "simulation": {"use_capacity_engine": False}})
    with pytest.raises(PlatformConfigError, match="v1 feature layout"):
        cfg.validate()


def test_validate_online_retrain_needs_engine_and_samples():
    with pytest.raises(PlatformConfigError, match="on_samples"):
        PlatformConfig.from_dict({
            "prediction": {"online_retrain": True},
            "simulation": {"use_capacity_engine": False,
                           "collect_samples": True}}).validate()
    with pytest.raises(PlatformConfigError, match="collect_samples"):
        PlatformConfig.from_dict({
            "prediction": {"online_retrain": True}}).validate()


def test_validate_predictorless_scheduler_limits():
    with pytest.raises(PlatformConfigError, match="without a predictor"):
        PlatformConfig.from_dict({
            "scheduler": {"name": "k8s"},
            "prediction": {"schema_version": 2}}).validate()


def test_validate_unknown_inference_engine():
    with pytest.raises(PlatformConfigError, match="engine"):
        PlatformConfig.from_dict(
            {"prediction": {"engine": "cuda"}}).validate()


def test_build_mismatched_world_schema(small_world):
    _cfg, scenario, world = small_world   # world speaks schema v1
    cfg = PlatformConfig.from_dict({
        **SMALL, "prediction": {**SMALL["prediction"],
                                "schema_version": 2}})
    with pytest.raises(PlatformConfigError, match="mismatched .*schema"):
        Platform.build(scenario=scenario, config=cfg, world=world)


@pytest.mark.parametrize("backend,engine,path", [
    ("tpu", None, ("pallas", "device")),
    ("tpu", "numpy", ("numpy", "host")),
    ("cpu", None, ("numpy", "host")),
])
def test_build_takes_the_backends_capacity_path(small_world, monkeypatch,
                                                backend, engine, path):
    """On a TPU the capacity solve drains on the chip with the compiled
    Pallas sweep unless the manifest names an engine, and ``numpy``
    always drains on the host; the CPU backend keeps the host numpy
    path.  A device drain is warmed at build.  Only the build runs, and
    the warm-up is recorded instead: the faked backend never compiles."""
    import jax
    from repro.core.prediction_service import PredictionService
    _cfg, scenario, world = small_world
    prediction = dict(SMALL["prediction"])
    if engine is not None:
        prediction["engine"] = engine
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    warmed = []
    monkeypatch.setattr(PredictionService, "warm_device",
                        lambda self: warmed.append(self))
    prev = world.predictor.engine
    try:
        plat = Platform.build(scenario=scenario, world=world, config={
            **SMALL, "scheduler": {"name": "jiagu"},
            "prediction": prediction})
        assert (plat.service.inference_engine,
                plat.service.cfg.drain) == path
        assert warmed == ([plat.service] if path[1] == "device" else [])
    finally:
        world.predictor.engine = prev      # the world is module-shared


# ---------------------------------------------------------------------------
# Registries
# ---------------------------------------------------------------------------


def test_registry_unknown_names_raise():
    with pytest.raises(ValueError, match="unknown scheduler"):
        scheduler_entry("no-such-scheduler")
    with pytest.raises(ValueError, match="unknown scenario kind"):
        get_scenario_builder("no-such-kind")
    with pytest.raises(ValueError, match="unknown trace"):
        get_trace("no-such-trace")
    with pytest.raises(ValueError, match="unknown router"):
        get_router("no-such-router")
    with pytest.raises(ValueError, match="unknown scheduler"):
        PlatformConfig.from_dict(
            {"scheduler": {"name": "no-such-scheduler"}}).validate()


def test_registry_contents_and_duplicate_rejection():
    assert {"jiagu", "gsight", "k8s", "owl"} <= set(
        registered_schedulers())
    assert "replay" in registered_scenarios()
    assert {"timer", "flip", "replay"} <= set(registered_traces())
    assert "equal-split" in registered_routers()
    with pytest.raises(ValueError, match="already registered"):
        register_router("equal-split", EqualSplitRouter)
    assert scheduler_entry("jiagu").dual_staged_default
    assert not scheduler_entry("k8s").dual_staged_default


def test_register_custom_scheduler_and_build_from_manifest(small_world):
    _cfg, scenario, world = small_world
    name = "test-binpack"
    if name not in registered_schedulers():
        register_scheduler(
            name,
            lambda ctx: K8sScheduler(ctx.cluster, ctx.store, ctx.qos))
    plat = Platform.build(scenario=scenario,
                          config={**SMALL, "scheduler": {"name": name}},
                          world=world)
    res = plat.run()
    assert res.ticks == 40
    assert res.requests > 0


# ---------------------------------------------------------------------------
# Capability protocols
# ---------------------------------------------------------------------------


def test_schedulers_satisfy_picker_protocols():
    specs = synthetic_functions(2, seed=0)
    cluster = Cluster(specs)
    store = ProfileStore(seed=0)
    gt = GroundTruth(seed=0)
    qos = QoSStore(store, gt)
    k8s = K8sScheduler(cluster, store, qos)
    assert isinstance(k8s, ReleasePicker)
    assert isinstance(k8s, LogicalStartPicker)
    assert isinstance(EqualSplitRouter(), Router)
    aut = Autoscaler(cluster, k8s, ScalingConfig())
    assert isinstance(aut.capacity, CapacityProvider)


def test_dual_staged_meaningful_for_non_jiagu():
    """The satellite fix: a baseline scheduler that opts into
    dual_staged=True gets release -> logical-cold-start behaviour from
    the greedy default pickers (previously picks were silently [] and
    every rise paid a real cold start)."""
    specs = synthetic_functions(2, seed=5)
    fn = sorted(specs)[0]
    sat = specs[fn].saturated_rps * 0.99
    cluster = Cluster(specs)
    store = ProfileStore(seed=0)
    gt = GroundTruth(seed=0)
    qos = QoSStore(store, gt)
    sched = K8sScheduler(cluster, store, qos)
    aut = Autoscaler(cluster, sched, ScalingConfig(
        release_s=5, keepalive_s=60, dual_staged=True, migrate=False))
    for t in range(3):
        aut.tick(float(t), {fn: sat * 4})
    assert cluster.sat_count(fn) == 4
    for i in range(8):
        aut.tick(3.0 + i, {fn: sat * 2})
    assert cluster.cached_count(fn) == 2      # released, not evicted
    cold_before = aut.metrics.real_cold_starts
    aut.tick(12.0, {fn: sat * 4})
    assert aut.metrics.logical_cold_starts == 2
    assert aut.metrics.real_cold_starts == cold_before
    assert cluster.sat_count(fn) == 4


class _CountingRouter:
    """Delegates to the default equal split; a pluggable policy that
    must observe the exact same requests/violations."""

    name = "counting"

    def __init__(self):
        self.inner = EqualSplitRouter()
        self.calls = 0

    def route(self, spec, fn_rps, node, n_sat, total_sat):
        self.calls += 1
        return self.inner.route(spec, fn_rps, node, n_sat, total_sat)


def _fresh_world(scenario):
    """Per-run world rebuild: ``GroundTruth.measure`` draws measurement
    noise from a stateful RNG, so run-to-run parity needs both arms to
    start from identical world state (same discipline as the
    benchmark's ``ab_parity``)."""
    return scenario_world(scenario, n_train=250, n_trees=6)


def test_custom_router_observes_same_world(small_world):
    _cfg, scenario, _world = small_world
    base = Platform.build(scenario=scenario, config=SMALL,
                          world=_fresh_world(scenario)).run()
    router = _CountingRouter()
    alt = Platform.build(scenario=scenario, config=SMALL,
                         world=_fresh_world(scenario),
                         router=router).run()
    assert router.calls > 0
    assert alt.requests == base.requests
    assert alt.violated_requests == base.violated_requests
    assert alt.density == base.density
    assert alt.per_fn_violations == base.per_fn_violations


# ---------------------------------------------------------------------------
# Observer hooks
# ---------------------------------------------------------------------------


class _Counting(Observer):
    def __init__(self):
        self.ticks = 0
        self.schedules = 0
        self.placed = 0
        self.scales = {}
        self.retrains = 0

    def on_tick(self, now, sim):
        self.ticks += 1

    def on_schedule(self, now, fn, placements, trace=None):
        self.schedules += 1
        self.placed += sum(p.count for p in placements)

    def on_scale(self, now, fn, event, count):
        self.scales[event] = self.scales.get(event, 0) + count

    def on_retrain(self, service):
        self.retrains += 1


def test_observer_hooks_fire(small_world):
    _cfg, scenario, world = small_world
    obs = _Counting()
    plat = Platform.build(scenario=scenario, config=SMALL, world=world,
                          observers=[obs])
    res = plat.run()
    assert obs.ticks == res.ticks == 40
    assert obs.schedules > 0
    assert obs.placed == res.sched.instances_placed
    assert obs.scales.get("real_cold_start", 0) == \
        res.scaling.real_cold_starts
    released = res.scaling.releases
    assert obs.scales.get("release", 0) == released


def test_on_retrain_hook_fires(small_world):
    _cfg, scenario, world = small_world
    obs = _Counting()
    manifest = {
        **SMALL,
        "prediction": {**SMALL["prediction"], "online_retrain": True,
                       "retrain_every": 4},
        "simulation": {"collect_samples": True, "sample_every_s": 2},
    }
    plat = Platform.build(scenario=scenario, config=manifest,
                          world=world, observers=[obs])
    res = plat.run()
    assert res.retrains >= 1
    assert obs.retrains == res.retrains


# ---------------------------------------------------------------------------
# Replay scenario kind (real traces through the scenario suite)
# ---------------------------------------------------------------------------


def test_replay_scenario_runs_in_suite():
    scenario = make_scenario("replay", n_functions=3, duration_s=30,
                             target_nodes=4, seed=0, path=SAMPLE_CSV)
    assert scenario.kind == "replay"
    assert scenario.trace.duration_s == 30
    assert all(len(s) == 30 for s in scenario.trace.rps.values())
    plat = Platform.build(
        scenario=scenario,
        config={"scenario": {"kind": "replay", "n_functions": 3,
                             "duration_s": 30, "target_nodes": 4,
                             "trace_kw": {"path": SAMPLE_CSV}},
                "prediction": {"n_train": 200, "n_trees": 6}})
    res = plat.run()
    assert res.ticks == 30
    assert res.requests > 0


def test_replay_scenario_requires_path():
    with pytest.raises(ValueError, match="path"):
        make_scenario("replay", n_functions=2, duration_s=10,
                      target_nodes=2)


def test_replay_builder_from_config_alone():
    """Pure-manifest path: the replay kind resolves through the
    registry without prebuilding a Scenario."""
    plat = Platform.build(config={
        "scenario": {"kind": "replay", "n_functions": 2,
                     "duration_s": 20, "target_nodes": 3,
                     "trace_kw": {"path": SAMPLE_CSV}},
        "prediction": {"n_train": 200, "n_trees": 6}})
    assert plat.scenario.kind == "replay"
    assert plat.run().ticks == 20


# ---------------------------------------------------------------------------
# Shims stay consistent with the facade
# ---------------------------------------------------------------------------


def test_platform_matches_scenario_simulation_shim(small_world):
    """The facade and the legacy shim assemble the same world -> same
    results (identical seeds)."""
    from repro.core import scenario_simulation
    _cfg, scenario, _world = small_world
    res_shim = scenario_simulation(
        scenario, "jiagu", world=_fresh_world(scenario)).run()
    res_plat = Platform.build(scenario=scenario, config=SMALL,
                              world=_fresh_world(scenario)).run()
    assert res_plat.requests == res_shim.requests
    assert res_plat.density == res_shim.density
    assert res_plat.sched.decisions == res_shim.sched.decisions
    assert res_plat.scaling.real_cold_starts == \
        res_shim.scaling.real_cold_starts
