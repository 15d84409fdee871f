#!/usr/bin/env python3
"""Bring-up smoke of Jiagu's capacity solve on one TPU chip.

    python3 chip_smoke.py

Runs in one process, on one chip, from generated data only, in phases
that each raise on failure:

  (a) the device: JAX must find a TPU, or the script exits non-zero
      before any phase — there is no CPU path;
  (b) the RFR kernels compiled at drain widths (24-32 trees of depth 8,
      schema v1/v2 widths, 1024+ scenarios of 16 concurrencies and a
      ragged 3), against the ``kernels/ref.py`` oracles and the numpy
      engine's semantics, with ``tpu_custom_call`` in their programs;
  (c) the capacity drain of a 4096-node fleet on the device (compiled
      Pallas sweep) against the host numpy oracle: equal tables;
  (d) ``Platform.build`` end to end: the first run of a 1024-node
      ``jiagu`` burst storm on the chip path the platform selects, which
      must compile nothing its build did not warm, against the same
      world run on the host numpy drain: identical density, QoS and
      counters;
  (e) the learned scorer trained on traced ``jiagu-pipeline`` decisions,
      served in one platform run: no stale-epoch serves.

Each phase prints one JSON line of what it measured; the last line is
``{"ok": true, "device": {...}}``.  Host-clock times include compiles
unless labelled otherwise.
"""
from __future__ import annotations

import copy
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))
sys.path.insert(0, HERE)

DEPTH = 8
M_SWEEP = 16
#: a differing capacity is admitted only when its deciding prediction
#: lies this close to its QoS bound (numpy's f32 exp is not monotone at
#: a few thousand inputs; expect none)
BOUNDARY_ULPS = 4


def log(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=float), flush=True)


class CompileCounter:
    """Counts XLA backend compilations (Pallas kernels included)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


# ---------------------------------------------------------------------------
# (b) kernels
# ---------------------------------------------------------------------------


def _forest(rng, T: int, F: int):
    NN = (1 << DEPTH) - 1
    feat = rng.integers(0, F, (T, NN)).astype(np.int32)
    thr = rng.standard_normal((T, NN)).astype(np.float32)
    thr[rng.random((T, NN)) < 0.1] = np.inf      # unsplit: always left
    # leaves are log-latencies, as the predictor trains them
    leaf = rng.uniform(0.5, 4.0, (T, 1 << DEPTH)).astype(np.float32)
    return feat, thr, leaf


def _host_predict(feat, thr, leaf, x):
    """The numpy engine's predictions (tree mean), the host oracle."""
    from repro.core.predictor import ForestArrays, RandomForestRegressor
    m = RandomForestRegressor(n_trees=feat.shape[0], max_depth=DEPTH)
    m.arrays = ForestArrays(feat, thr, leaf)
    return m.predict(x, engine="numpy")


def _first_fail(ok):
    """Longest passing prefix over m of all-rows-pass: (S, M, R) -> (S,)."""
    m_ok = ok.all(axis=2)
    M = m_ok.shape[1]
    return np.where(m_ok, M, np.arange(M)[None, :]).min(axis=1)


def _boundary(caps, want, preds, bounds):
    """Split differing scenarios into those whose deciding prediction
    (at the first m past the smaller capacity) is within BOUNDARY_ULPS
    of its bound, and the rest."""
    near = far = 0
    for s in np.nonzero(caps != want)[0]:
        m = min(int(caps[s]), int(want[s]))
        p = preds[s, m].astype(np.float64)
        ulps = np.abs(p - bounds[s, m]) / np.spacing(preds[s, m])
        if np.isfinite(ulps).any() and np.nanmin(ulps) <= BOUNDARY_ULPS:
            near += 1
        else:
            far += 1
    return near, far


def phase_kernels(cases, compiled: bool = True) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    from repro.kernels.rfr_inference import sweep_limits

    if compiled and ops.pallas_interpret():
        raise RuntimeError("Pallas would run interpreted on this TPU")
    rng = np.random.default_rng(0)
    for T, F, S, R, with_ref in cases:
        feat, thr, leaf = _forest(rng, T, F)
        dev = [jnp.asarray(a) for a in (feat, thr, leaf)]
        # forest predictions
        N = 4096
        xf = rng.standard_normal((N, F)).astype(np.float32)
        xf_d = jnp.asarray(xf)
        hlo_apply = ops.rfr_op.lower(xf_d, *dev).compile().as_text()
        t0 = time.perf_counter()
        sums = jax.block_until_ready(ops.rfr_op(xf_d, *dev))
        apply_ms = (time.perf_counter() - t0) * 1e3
        pred = np.asarray(sums) / np.float32(T)
        host = _host_predict(feat, thr, leaf, xf)
        want = np.asarray(ref.rfr_forest_ref(xf, feat, thr, leaf))
        np.testing.assert_allclose(pred, want, rtol=1e-6, atol=0)
        # the engines' contract: pallas predictions are the numpy engine's
        not_equal = int((pred != host).sum())
        if not_equal:
            raise AssertionError(f"forest T={T} F={F}: {not_equal} rows "
                                 "differ from the numpy engine's")
        # the fused sweep: bounds straddle the host's own predictions
        x = rng.standard_normal((S, M_SWEEP, R, F)).astype(np.float32)
        p = np.exp(_host_predict(feat, thr, leaf, x.reshape(-1, F)))
        p = p.reshape(S, M_SWEEP, R)
        factor = rng.uniform(0.9, 2.0, p.shape)
        factor[np.abs(factor - 1.0) < 1e-4] += 2e-4
        bounds = (p * factor).astype(np.float32)
        for s in range(S):
            bounds[s, :, int(rng.integers(1, R + 1)):] = np.inf
            bounds[s, int(rng.integers(0, M_SWEEP + 1)):, :] = -np.inf
        lim = jnp.asarray(sweep_limits(bounds, T, log_target=True))
        x_d = jnp.asarray(x)
        hlo_sweep = ops.rfr_sweep_op.lower(x_d, lim, *dev).compile().as_text()
        t0 = time.perf_counter()
        caps = np.asarray(jax.block_until_ready(
            ops.rfr_sweep_op(x_d, lim, *dev)))
        sweep_ms = (time.perf_counter() - t0) * 1e3
        host_caps = _first_fail(p.astype(np.float64) <= bounds)
        if not np.array_equal(caps, host_caps):
            raise AssertionError(
                f"sweep T={T} F={F} S={S}: "
                f"{int((caps != host_caps).sum())} capacities differ from "
                "the numpy engine's")
        near = far = 0
        if with_ref:
            ref_caps = np.asarray(ref.rfr_capacity_sweep_ref(
                x, bounds, feat, thr, leaf, log_target=True))
            near, far = _boundary(caps, ref_caps, p, bounds)
            if far:
                raise AssertionError(
                    f"sweep T={T} F={F} S={S}: {far} capacities differ "
                    "from ref.rfr_capacity_sweep_ref away from a bound")
        if compiled and not ("tpu_custom_call" in hlo_apply
                             and "tpu_custom_call" in hlo_sweep):
            raise AssertionError("a compiled RFR program holds no kernel")
        log("kernels", trees=T, features=F, scenarios=S, rows_per_m=R,
            apply_rows=N, apply_ms=apply_ms, sweep_ms=sweep_ms,
            forest_max_rel_err=float(np.max(np.abs(pred - want) / want)),
            forest_rows_not_bit_equal_numpy=not_equal,
            capacity_hist=np.bincount(caps, minlength=M_SWEEP + 1).tolist(),
            ref_checked=with_ref, ref_boundary_cases=near,
            tpu_custom_call=("tpu_custom_call" in hlo_sweep))


# ---------------------------------------------------------------------------
# (c) the 4096-node drain
# ---------------------------------------------------------------------------


def phase_drain(n_nodes: int, counter: CompileCounter) -> None:
    from benchmarks.capacity_engine import (M_MAX, _build_nodes, _clear,
                                            _tables)
    from benchmarks.common import build_world
    from repro.engine import CapacityEngine, EngineConfig

    t0 = time.perf_counter()
    world = build_world(n_synthetic=6)
    pred = world.predictor
    nodes = _build_nodes(world.specs, n_nodes, seed=n_nodes)
    setup_s = time.perf_counter() - t0

    pred.engine = "numpy"
    host = CapacityEngine(pred, world.store, world.qos, world.specs,
                          EngineConfig(m_max=M_MAX))
    t0 = time.perf_counter()
    host.update_nodes(nodes, m_max=M_MAX)
    host_ms = (time.perf_counter() - t0) * 1e3
    want = _tables(nodes)
    _clear(nodes)

    pred.engine = "pallas"
    dev = CapacityEngine(pred, world.store, world.qos, world.specs,
                         EngineConfig(m_max=M_MAX, drain="device"))
    c0 = counter.n
    t0 = time.perf_counter()
    dev.update_nodes(nodes, m_max=M_MAX)
    first_ms = (time.perf_counter() - t0) * 1e3
    first = _tables(nodes)
    compiles_first = counter.n - c0
    _clear(nodes)
    dev.invalidate()
    c0 = counter.n
    t0 = time.perf_counter()
    dev.update_nodes(nodes, m_max=M_MAX)
    again_ms = (time.perf_counter() - t0) * 1e3
    again = _tables(nodes)
    compiles_again = counter.n - c0
    pred.engine = "numpy"
    differing = sum(a != b for a, b in zip(first, want)) + \
        sum(a != b for a, b in zip(again, want))
    log("drain", nodes=n_nodes, solves=host.stats.solves,
        unique_solves=host.stats.unique_solves, setup_s=setup_s,
        host_numpy_ms=host_ms, device_first_ms=first_ms,
        device_first_compiles=compiles_first, device_again_ms=again_ms,
        device_again_compiles=compiles_again,
        tables_equal=(differing == 0), differing_nodes=differing)
    if differing:
        raise AssertionError(f"{differing} node tables differ from the "
                             "host numpy oracle")


# ---------------------------------------------------------------------------
# (d) the platform end to end
# ---------------------------------------------------------------------------


def _fingerprint(res) -> dict:
    """Behaviour the two paths must share exactly.  Wall-clock latencies
    are left out, and so is inference billing: the host drain's early
    exit and the device drain's full sweep build different row counts
    for the same capacities."""
    s, a = res.sched, res.scaling
    return {
        "ticks": res.ticks, "density": res.density,
        "qos_violation_rate": res.qos_violation_rate,
        "requests": res.requests, "violated": res.violated_requests,
        "nodes_peak": res.nodes_peak, "node_seconds": res.node_seconds,
        "instance_seconds": res.instance_seconds,
        "density_series": list(res.density_series),
        "per_fn_violations": res.per_fn_violations,
        "decisions": s.decisions, "instances_placed": s.instances_placed,
        "fast": s.fast, "slow": s.slow, "failed": s.failed,
        "real_cold_starts": a.real_cold_starts,
        "logical_cold_starts": a.logical_cold_starts,
        "blocked_logical": a.blocked_logical, "migrations": a.migrations,
        "releases": a.releases, "evictions": a.evictions,
    }


def phase_platform(target_nodes: int, duration_s: int,
                   counter: CompileCounter, compiled: bool = True) -> None:
    from repro.platform import Platform

    manifest = {
        "scenario": {"kind": "burst-storm", "n_functions": 24,
                     "duration_s": duration_s,
                     "target_nodes": target_nodes, "seed": 0},
        "scheduler": {"name": "jiagu"},
        "cells": {"count": 1},
        "telemetry": {"spans": True},
    }
    # the user's first build and run in this process: the build warms
    # the device drain's launch shapes, and the run must compile nothing
    # (a decision slowed by a compile delays its table update)
    c0 = counter.n
    t0 = time.perf_counter()
    chip = Platform.build(config=manifest)
    build_s = time.perf_counter() - t0
    build_compiles = counter.n - c0
    svc = chip.service
    path = (svc.inference_engine, svc.cfg.drain)
    if compiled and path != ("pallas", "device"):
        raise AssertionError(f"the platform chose {path} on a TPU")
    chip.world.gt.reseed()
    c0 = counter.n
    t0 = time.perf_counter()
    res_chip = chip.run()
    chip_s = time.perf_counter() - t0
    compiles = counter.n - c0
    spans = {r["name"]: r for r in chip.span_summary()}

    oracle = copy.deepcopy(manifest)
    oracle["prediction"] = {"engine": "numpy"}
    host = Platform.build(scenario=chip.scenario, config=oracle,
                          world=chip.world)
    if (host.service.inference_engine, host.service.cfg.drain) != \
            ("numpy", "host"):
        raise AssertionError("prediction.engine=numpy must drain on the host")
    host.world.gt.reseed()
    t0 = time.perf_counter()
    res_host = host.run()
    host_s = time.perf_counter() - t0

    fc, fh = _fingerprint(res_chip), _fingerprint(res_host)
    diff = sorted(k for k in fc if fc[k] != fh[k])
    sweep = spans.get("device_sweep", {})
    host_spans = {r["name"]: r for r in host.span_summary()}
    log("platform", nodes=target_nodes, duration_s=duration_s,
        path=list(path), build_s=build_s, build_compiles=build_compiles,
        chip_run_s=chip_s, run_compiles=compiles,
        host_run_s=host_s, drains=svc.stats.predict_calls,
        unique_solves=svc.stats.unique_solves,
        device_sweep_count=sweep.get("count", 0),
        device_sweep_total_ms=sweep.get("total_ms", 0.0),
        capacity_solve_total_ms=spans.get("capacity_solve", {}).get(
            "total_ms", 0.0),
        host_capacity_solve_total_ms=host_spans.get(
            "capacity_solve", {}).get("total_ms", 0.0),
        density=res_chip.density,
        qos_violation_rate=res_chip.qos_violation_rate,
        decisions=res_chip.sched.decisions,
        chip_decision_ms_p50=res_chip.sched.p50_latency_ms,
        host_decision_ms_p50=res_host.sched.p50_latency_ms,
        chip_decision_ms_p99=res_chip.sched.p99_latency_ms,
        host_decision_ms_p99=res_host.sched.p99_latency_ms,
        chip_inference_rows=res_chip.inference_rows,
        host_inference_rows=res_host.inference_rows,
        identical=not diff, differing=diff)
    if compiles:
        raise AssertionError(f"the chip run compiled {compiles} programs "
                             "that its build did not warm")
    if diff:
        raise AssertionError(f"chip and host runs differ in {diff}")


# ---------------------------------------------------------------------------
# (e) the learned stack
# ---------------------------------------------------------------------------


def phase_learned() -> None:
    import benchmarks.policy as bp
    from repro.platform import Platform
    from repro.policy import load_traces, merge
    from repro.policy.train import np_scores

    spec = bp.study_spec(smoke=True)
    out_dir = os.path.join(bp.ARTIFACTS, "chip_smoke_policy")
    store_dir = os.path.join(out_dir, "store")
    t0 = time.perf_counter()
    paths = bp.collect(spec, out_dir)
    metrics = bp.fit(spec, paths, store_dir, smoke=True)
    train_s = time.perf_counter() - t0

    manifest = copy.deepcopy(spec["evaluate"])
    manifest["scheduler"] = {"name": "learned"}
    manifest["policy"] = {"store": store_dir, "epoch": 0}
    plat = Platform.build(config=manifest)
    plat.world.gt.reseed()
    res = plat.run()
    scorer = plat.scheduler.learned_scorer
    stats = scorer.stats
    if stats.batches == 0 or stats.stale_serves != 0:
        raise AssertionError(f"learned scorer served {stats.snapshot()}")
    ds = merge(load_traces(p) for p in paths)
    rows = np.concatenate([d.features for d in ds.decisions]).astype(
        np.float32)
    served = np.asarray(scorer._fwd(rows), np.float64)    # the jitted forward
    exact = np_scores({k: np.asarray(v, np.float64)
                       for k, v in scorer.policy.items()},
                      rows.astype(np.float64))
    if not np.isfinite(served).all():
        raise AssertionError("learned scores are not finite")
    log("learned", decisions_traced=len(ds), train_s=train_s,
        imitation_agreement=metrics["imitation_agreement"],
        density=res.density, qos_violation_rate=res.qos_violation_rate,
        scored_batches=stats.batches, stale_serves=stats.stale_serves,
        scored_rows=len(rows),
        max_abs_diff_vs_f64=float(np.max(np.abs(served - exact))))


# ---------------------------------------------------------------------------


def main() -> int:
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    counter = CompileCounter()
    log("device", platform=dev.platform, kind=dev.device_kind,
        count=len(jax.devices()), compile_cache=cache)
    for name, run in [
        ("kernels", lambda: phase_kernels(
            [(24, 31, 1024, 6, True), (32, 33, 1025, 8, True),
             (24, 33, 3, 5, True), (32, 31, 3, 6, True)])),
        ("drain", lambda: phase_drain(4096, counter)),
        ("platform", lambda: phase_platform(1024, 120, counter)),
        ("learned", phase_learned),
    ]:
        c0 = counter.n
        t0 = time.perf_counter()
        run()
        log(f"{name}-done", wall_s=time.perf_counter() - t0,
            compiles=counter.n - c0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
