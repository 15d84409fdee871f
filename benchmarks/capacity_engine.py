"""Cluster-scale capacity-solve scaling study: legacy per-node path vs
the CapacityEngine host drain vs the device-resident fused drain,
24 -> 4096 nodes.

Each cluster size is populated with nodes drawn from a fixed pool of
colocation patterns — the regime a real fleet is in, where most nodes
look like a few dozen archetypes.  For each size we drain the whole
cluster's capacity tables per path:

  * legacy  — ``update_capacity_table`` node by node (one predictor call
              per (node, function), Python row assembly, full m-sweep)
  * engine  — ``CapacityEngine.update_nodes`` (one coalesced drain:
              a handful of batched predictor calls, signature cache,
              vectorized assembly, chunked early-exit m-sweep)
  * device  — ``EngineConfig(drain="device")``: the drain's new
              scenarios packed into (128, M, R, F) blocks, each block's
              full m-sweep fused into a single forest pass
              (``rfr_sweep_op``; Pallas on TPU, the jnp gather sweep on
              CPU), launch shapes compiled before the timing.  A second
              (warm) drain shows the steady-state cost once the
              signature cache is populated.

and assert all resulting capacity tables are identical (the solver's
bit-compatibility contract).  The legacy O(nodes) path is only run up
to 512 nodes; the extended sizes (1024, 4096) compare the device drain
against the host-engine oracle.  Acceptance targets: >= 5x wall-time
AND predictor-call reduction at 256 nodes; device per-solve latency
flat in cluster size (log-log slope < 0.5 across the >= 128-node rows,
recorded as ``device_per_solve_slope`` and gated).
"""
from __future__ import annotations

import time

import numpy as np

from .common import build_world, emit, save_artifact

from repro.core import update_capacity_table
from repro.core.cluster import Node
from repro.core.interference import NodeResources
from repro.engine import CapacityEngine, EngineConfig
from repro.telemetry import RunReport, append_bench

M_MAX = 16
N_PATTERNS = 24
#: legacy per-node solving is O(nodes) with Python row assembly — above
#: this it only burns benchmark time proving the same linearity
LEGACY_MAX_NODES = 512
#: device-drain-only extension (vs the host-engine oracle)
EXTENDED_SIZES = [1024, 4096]
#: device per-solve latency must stay flat: log-log slope of
#: us-per-solve vs nodes over the >= SLOPE_MIN_NODES rows
SLOPE_MAX = 0.5
SLOPE_MIN_NODES = 100


def _pattern_pool(specs, rng, n_patterns: int):
    names = sorted(specs)
    pool = []
    for _ in range(n_patterns):
        k = int(rng.integers(1, 4))
        pat = {}
        for g in rng.choice(names, size=k, replace=False):
            pat[g] = (int(rng.integers(1, 6)), int(rng.integers(0, 3)))
        pool.append(pat)
    return pool


def _build_nodes(specs, n_nodes: int, seed: int):
    rng = np.random.default_rng(seed)
    pool = _pattern_pool(specs, rng, N_PATTERNS)
    nodes = []
    for _ in range(n_nodes):
        node = Node(NodeResources())
        for g, (ns, nc) in pool[rng.integers(len(pool))].items():
            node.state(g).n_sat = ns
            node.state(g).n_cached = nc
        nodes.append(node)
    return nodes


def _tables(nodes):
    return [sorted((fn, e.capacity) for fn, e in n.table.items())
            for n in nodes]


def _clear(nodes):
    for n in nodes:
        n.table.clear()


def _device_engine() -> str:
    """Pallas kernel on TPU; the jnp gather sweep on CPU (interpret-mode
    Pallas would benchmark the emulator, not the drain)."""
    import jax
    return "pallas" if jax.default_backend() == "tpu" else "jax"


def run(quick: bool = False, bench: bool = False):
    """``bench=True`` (the driver/__main__ path) persists a
    ``RunReport`` into ``BENCH_capacity_engine.json`` for the
    regression gate; tests calling this directly leave the repo root
    untouched."""
    world = build_world(n_synthetic=6)
    pred = world.predictor
    sizes = [24, 128, 256] if quick else [24, 64, 128, 256, 512]
    all_sizes = sizes + EXTENDED_SIZES
    dev_engine = _device_engine()
    rows = []
    for n_nodes in all_sizes:
        nodes = _build_nodes(world.specs, n_nodes, seed=n_nodes)
        run_legacy = n_nodes <= LEGACY_MAX_NODES

        legacy_s = legacy_calls = legacy_rows = None
        if run_legacy:
            # -- legacy: per-node, per-function solves -----------------
            calls0, rows0 = pred.inference_calls, pred.inference_count
            t0 = time.perf_counter()
            for node in nodes:
                update_capacity_table(pred, world.store, world.qos,
                                      world.specs, node, m_max=M_MAX)
            legacy_s = time.perf_counter() - t0
            legacy_calls = pred.inference_calls - calls0
            legacy_rows = pred.inference_count - rows0
            ref = _tables(nodes)
            _clear(nodes)

        # -- engine: one coalesced drain, cold cache -------------------
        engine = CapacityEngine(pred, world.store, world.qos, world.specs,
                                EngineConfig(m_max=M_MAX))
        calls0, rows0 = pred.inference_calls, pred.inference_count
        t0 = time.perf_counter()
        engine.update_nodes(nodes, m_max=M_MAX)
        engine_s = time.perf_counter() - t0
        engine_calls = pred.inference_calls - calls0
        engine_rows = pred.inference_count - rows0
        got = _tables(nodes)
        if run_legacy:
            assert got == ref, f"capacity tables diverged at {n_nodes} nodes"
        else:
            ref = got               # host engine is the oracle out here
        _clear(nodes)

        # -- engine again: warm signature cache ------------------------
        t0 = time.perf_counter()
        engine.update_nodes(nodes, m_max=M_MAX)
        warm_s = time.perf_counter() - t0
        assert _tables(nodes) == ref, "warm-cache tables diverged"
        _clear(nodes)

        # -- device: fused single-pass m-sweep -------------------------
        device = CapacityEngine(pred, world.store, world.qos, world.specs,
                                EngineConfig(m_max=M_MAX, drain="device"))
        prev_engine = pred.engine
        pred.engine = dev_engine
        try:
            device.warm_device()        # compiles stay out of the timing
            t0 = time.perf_counter()
            device.update_nodes(nodes, m_max=M_MAX)
            device_s = time.perf_counter() - t0
            assert _tables(nodes) == ref, \
                f"device capacity tables diverged at {n_nodes} nodes"
            device_calls = device.stats.predict_calls
            _clear(nodes)
            t0 = time.perf_counter()
            device.update_nodes(nodes, m_max=M_MAX)
            device_warm_s = time.perf_counter() - t0
            assert _tables(nodes) == ref, "warm device tables diverged"
        finally:
            pred.engine = prev_engine

        scenarios = sum(len(t) for t in ref)
        rows.append({
            "nodes": n_nodes,
            "scenarios": scenarios,
            "legacy_ms": round(legacy_s * 1e3, 2) if run_legacy else None,
            "engine_ms": round(engine_s * 1e3, 2),
            "warm_ms": round(warm_s * 1e3, 2),
            "device_ms": round(device_s * 1e3, 2),
            "device_warm_ms": round(device_warm_s * 1e3, 2),
            "device_us_per_solve": round(device_s * 1e6 / scenarios, 2),
            "speedup": round(legacy_s / max(engine_s, 1e-9), 2)
            if run_legacy else None,
            "warm_speedup": round(legacy_s / max(warm_s, 1e-9), 2)
            if run_legacy else None,
            "legacy_calls": legacy_calls,
            "engine_calls": engine_calls,
            "device_calls": device_calls,
            "call_reduction": round(legacy_calls / max(engine_calls, 1), 1)
            if run_legacy else None,
            "legacy_rows": legacy_rows,
            "engine_rows": engine_rows,
            "unique_solves": engine.stats.unique_solves,
            "cache_hits": engine.stats.cache_hits,
            "coalesced_dupes": engine.stats.coalesced_dupes,
            "tables_equal": True,
        })
        emit(rows[-1:])

    # device scaling law: per-solve latency vs cluster size (log-log).
    # <= 0 means flat-or-amortizing; SLOPE_MAX bounds regressions.
    fit = [(r["nodes"], r["device_us_per_solve"]) for r in rows
           if r["nodes"] >= SLOPE_MIN_NODES]
    slope = float(np.polyfit(np.log([n for n, _ in fit]),
                             np.log([u for _, u in fit]), 1)[0]) \
        if len(fit) >= 2 else 0.0
    assert slope < SLOPE_MAX, \
        f"device per-solve latency grows with cluster size " \
        f"(log-log slope {slope:.3f} >= {SLOPE_MAX})"
    print(f"# device per-solve slope ({len(fit)} sizes >= "
          f"{SLOPE_MIN_NODES} nodes, engine={dev_engine}): {slope:.3f} "
          f"=> {'PASS' if slope < SLOPE_MAX else 'FAIL'}")

    save_artifact("capacity_engine_scaling", {"m_max": M_MAX,
                                              "n_patterns": N_PATTERNS,
                                              "device_engine": dev_engine,
                                              "rows": rows})
    at256 = [r for r in rows if r["nodes"] == 256]
    if at256:
        r = at256[0]
        ok = r["speedup"] >= 5.0 and r["call_reduction"] >= 5.0
        print(f"# 256-node acceptance: speedup={r['speedup']}x "
              f"calls {r['legacy_calls']}->{r['engine_calls']} "
              f"({r['call_reduction']}x) tables_equal={r['tables_equal']} "
              f"=> {'PASS' if ok else 'FAIL'}")
    if bench:
        top = [r for r in rows if r["speedup"] is not None][-1]
        report = RunReport.build(
            "capacity_engine", mode="quick" if quick else "full",
            manifest={"m_max": M_MAX, "n_patterns": N_PATTERNS,
                      "sizes": all_sizes, "device_engine": dev_engine},
            metrics={"speedup_max_size": top["speedup"],
                     "warm_speedup_max_size": top["warm_speedup"],
                     "call_reduction_max_size": top["call_reduction"],
                     "device_per_solve_slope": round(slope, 4),
                     "device_us_per_solve_max_size":
                         rows[-1]["device_us_per_solve"],
                     "tables_equal_all": all(r["tables_equal"]
                                             for r in rows)},
            rows=rows)
        path = append_bench(report)
        print(f"# bench: appended {report.mode} run "
              f"({len(rows)} rows, git {report.git_sha}) -> {path}")
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    run(quick=args.quick, bench=True)
