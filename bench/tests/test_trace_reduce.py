"""The reduction from a device trace to busy time, idle share, kernel
time and idle gaps, and the sweep's work count."""
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import layers, trace_reduce, workcount


def _plane(name, lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=n, start_ns=s, duration_ns=d)
                            for n, s, d in evs]) for ln, evs in lines])


KERNEL = ('%rfr_sweep_op.1 = s32[1,128]{1,0:T(1,128)} custom-call('
          'f32[31,8,32,128]{3,2,1,0:T(8,128)S(1)} %copy_bitcast_fusion, '
          'f32[8,32,128]{2,1,0:T(8,128)S(1)} %copy_bitcast_fusion.1), '
          'custom_call_target="tpu_custom_call"')
KERNEL_SHORT = "%rfr_sweep_op.1 custom-call f32[31,8,32,128]"
COPY = ('%copy-start.2 = (f32[24,256]{1,0:T(8,128)S(1)}, f32[24,256]'
        '{1,0:T(8,128)}, u32[]{:S(2)}) copy-start(f32[24,256]'
        '{1,0:T(8,128)} %leaf.1)')


def _planes():
    dev = _plane("/device:TPU:0", [
        ("XLA Ops", [("fusion.1", 100, 50), (KERNEL, 140, 60),
                     ("fusion.2", 400, 100)]),
        ("XLA Modules", [("jit_rfr_sweep_op", 100, 400)]),
    ])
    host = _plane("/host:CPU", [
        ("python", [("bench.tick", 0, 1000), ("bench.schedule", 210, 150),
                    ("bench.solve_many", 220, 100),
                    ("unrelated", 600, 10)]),
    ])
    return [dev, host, _plane("/device:TPU:1", [("XLA Ops", [("x", 0, 9)])])]


def test_union_and_gaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [(0, 3), (5, 8)]
    assert trace_reduce.gaps([(0, 3), (5, 8)], 0, 10) == [(3, 5), (8, 10)]


def test_reduce_synthetic_trace():
    devices, anns = trace_reduce.collect(_planes(), chips=1)
    assert list(devices) == ["/device:TPU:0"]      # the chips used only
    assert len(anns) == 3                          # bench.* annotations
    got = trace_reduce.reduce(devices, anns, window_s=1e-6)
    # busy: [100, 200) and [400, 500) -> 200 ns of a 1000 ns window
    assert got["busy_s"] == pytest.approx(200e-9)
    assert got["op_s"][KERNEL_SHORT] == pytest.approx(60e-9)
    assert got["op_count"] == {"fusion.1": 1, KERNEL_SHORT: 1,
                               "fusion.2": 1}
    idle = dict(got["breakdown"]["idle_gaps"])
    # gaps [0,100) and [500,1000) lie in the tick only; [200,400) has its
    # midpoint (300) inside solve_many, the innermost annotation open
    assert idle == {"bench.tick": pytest.approx(600e-9),
                    "bench.solve_many": pytest.approx(200e-9)}
    ops = got["breakdown"]["device_ops"]
    assert ops[0][0] == "fusion.2" and len(ops) == 3


def test_short_names():
    assert trace_reduce.short_name(KERNEL) == KERNEL_SHORT
    assert trace_reduce.short_name(COPY) == \
        "%copy-start.2 copy-start f32[24,256]"


def test_labels_follow_nesting():
    anns = [(0, 100, "a"), (10, 20, "b"), (30, 90, "c"), (40, 50, "d")]
    assert trace_reduce.labels_at([5, 15, 25, 45, 60, 95, 150], anns) == \
        ["a", "b", "a", "d", "c", "a", "host"]


def test_device_metrics_from_run():
    devices, anns = trace_reduce.collect(_planes(), chips=1)
    red = trace_reduce.reduce(devices, anns, window_s=1e-6)
    run = NS(device=red, window_s=1e-6, device_kind="TPU v5 lite",
             forest={"trees": 24, "depth": 8, "features": 31},
             spans=[("device_sweep", 0.0, 1.0, 1,
                     {"rows": 240, "scenarios": 1, "launches": 1,
                      "launch_shape": [128, 16, 16, 31]})])
    assert layers.device_idle(run) == pytest.approx(80.0)
    assert layers.kernel_seconds(run) == pytest.approx(60e-9)
    assert layers.lane_fill(run) == pytest.approx(100.0 / 128)
    work = workcount.sweep_work(240, 1, 24, 8, 31)
    least = workcount.least_seconds(work, {"ops_per_s": 197e12,
                                           "hbm_bytes_per_s": 819e9})
    assert layers.sweep_roofline(run) == pytest.approx(100 * least / 60e-9)


def test_reduce_recorded_cpu_trace(tmp_path):
    """A real trace of this backend: the annotations are found and a
    window without device planes reads no busy time."""
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.tick"):
        jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    got = trace_reduce.reduce_dir(tmp_path, window_s=1.0)
    assert got["busy_s"] == 0.0
    assert got["breakdown"]["device_ops"] == []


def test_work_count_ignores_bucket_padding():
    """A drain's ``rows`` attribute counts each asked scenario's own
    m_max x rows_per_m, whatever M and R buckets carry it."""
    from repro.core.prediction_service import EngineConfig, PredictionService
    from repro.core.scenarios import make_scenario, scenario_world
    from repro.telemetry.spans import SpanTracer

    scn = make_scenario("burst-storm", n_functions=12, duration_s=30,
                        target_nodes=8, seed=1)
    w = scenario_world(scn, n_train=200, n_trees=4, max_depth=4)
    svc = PredictionService(w.predictor, w.store, w.qos, scn.specs,
                            EngineConfig(m_max=8, drain="device", cache=False),
                            engine="jax")
    svc.tracer = SpanTracer()
    names = sorted(scn.specs)
    narrow = ({names[1]: (2.0, 0.0)}, names[0], 5)
    wide = ({g: (1.0, 0.0) for g in names[1:]}, names[0], 8)
    svc.solve_many([narrow])
    svc.solve_many([narrow, wide])
    a, b = [s for s in svc.tracer.spans if s.name == "device_sweep"]
    assert a.attrs["launch_shape"] != b.attrs["launch_shape"]
    assert a.attrs["rows"] == 5 * 2
    assert b.attrs["rows"] == 5 * 2 + 8 * 12
    one = workcount.sweep_work(a.attrs["rows"], 1, 4, 4, 31)
    two = workcount.sweep_work(b.attrs["rows"], 2, 4, 4, 31)
    wide_only = workcount.sweep_work(8 * 12, 1, 4, 4, 31)
    assert two["ops"] == one["ops"] + wide_only["ops"]
    assert np.isclose(two["bytes"] - wide_only["bytes"],
                      one["bytes"] - workcount.sweep_work(0, 0, 4, 4,
                                                          31)["bytes"])
