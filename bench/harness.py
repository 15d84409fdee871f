"""One run of one benchmark cell: set-up, a measured window, per-layer
readings and the check against the plain reference.

``BENCHMARK.json`` names each cell's configuration, traffic mix and
metrics.  Everything that belongs to one of them is found by its name:

  * a configuration: the file its ``configs`` entry names;
  * a traffic mix: ``<bench>/traffic/<name>.json``;
  * a per-layer metric: ``<bench>/metrics/<name>.py``, whose
    ``read(run)`` returns the number or None when the run holds nothing
    for it to read.

A traffic file's ``"window"`` says how a run is driven:

  * ``"ticks"``: ``Platform.run`` plays the trace; the first
    ``warmup_s`` fleet seconds fill the fleet as set-up, and the window
    is every tick that starts before ``--seconds`` of wall time have
    passed since the window opened.  The harness times each
    ``schedule`` call itself.
  * ``"refresh"``: set-up plays the ``warmup_s`` fill; the window then
    repeats whole refresh cycles until ``--seconds`` have passed.  A
    cycle is what an online retrain does: the service's forest is
    replaced (the configuration's two frozen forests take turns) and
    its epoch bumped, ``PredictionService.invalidate`` drops the cache,
    and ``refresh_tables`` re-solves every node's table.

Every capacity the prediction service answers in the window is kept,
with the forest it was asked of; in a refresh window so are the tables
of a seeded sample of nodes after every cycle.  After the window a
sample of them, drawn from the seed, is compared with ``reference.py``
on the configuration's frozen world.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import random
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from . import reference, world

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

#: answers compared with the reference per run (plus the widest ones)
SAMPLE_ANSWERS = 1500
SAMPLE_WIDEST = 50
#: nodes whose tables a refresh window keeps after every cycle
SAMPLE_NODES = 64


class BenchError(RuntimeError):
    """The benchmark cannot run this cell here."""


# ---------------------------------------------------------------------------
# Finding a cell
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[str]
    per_layer: List[str]
    readers: Dict[str, Callable]
    units: Dict[str, str]


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_reader(bench_dir: Path, name: str) -> Callable:
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None or not path.is_file():
        raise BenchError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def find_cell(name: str, root: Path = ROOT,
              bench_dir: Optional[Path] = None) -> Cell:
    bench_dir = bench_dir or root / "bench"
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} (have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    config["world_file"] = str(root / config["world_file"])
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m["name"] for m in spec["end_to_end"] if _applies(m, name)]
    layer = [m["name"] for m in spec["per_layer"] if _applies(m, name)]
    units = {m["name"]: m["unit"]
             for m in spec["end_to_end"] + spec["per_layer"]}
    return Cell(name, int(w["chips"]), config, traffic, e2e, layer,
                {m: load_reader(bench_dir, m) for m in layer}, units)


# ---------------------------------------------------------------------------
# The device
# ---------------------------------------------------------------------------


def check_device(chips: int) -> Dict[str, Any]:
    """The accelerator JAX found; raises without a TPU or with fewer
    chips than the cell asks for.  There is no CPU fallback."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchError(f"needs {chips} chips; JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` when that
    is set, else at the fixed ``<checkout>/.jax_cache``; every program
    is cached, so a second run of a cell compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


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
# The run record the metric readers see
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Run:
    cell: Cell
    seed: int
    seconds: float
    traced: bool
    window_s: float = 0.0
    fleet_s: int = 0             # fleet seconds the window played
    cycles: int = 0              # refresh cycles the window completed
    place_ms: List[float] = dataclasses.field(default_factory=list)
    #: closed program spans of the window: (name, start_s, ms, depth,
    #: attrs), start on the host's perf_counter
    spans: List[tuple] = dataclasses.field(default_factory=list)
    #: (queries, results, forest) of every ``solve_many`` call
    answers: List[tuple] = dataclasses.field(default_factory=list)
    forest_idx: int = 0          # the frozen forest the service holds
    #: (forest, [table of each sampled node]) after every refresh cycle
    tables: List[tuple] = dataclasses.field(default_factory=list)
    window_compiles: int = 0
    failed: int = 0              # instances no decision could place
    nodes: List[int] = dataclasses.field(default_factory=list)
    #: the reduced device trace (``trace_reduce.reduce``), traced runs
    device: Optional[Dict[str, Any]] = None
    forest: Optional[Dict[str, int]] = None
    device_kind: str = ""
    in_window: bool = False
    t0: float = 0.0


class WindowClosed(Exception):
    """Raised from the tick observer to end ``Platform.run`` at a tick
    boundary once the window has lasted ``--seconds``."""


def _annotation(run: Run, name: str):
    if not run.traced:
        return None
    import jax
    return jax.profiler.TraceAnnotation(name)


def _schedulers(plat) -> list:
    sim = plat.simulation
    return sim.schedulers() if hasattr(sim, "schedulers") \
        else [sim.scheduler]


def _instrument(plat, run: Run) -> None:
    """Wrap the calls into the layers the metrics read: each scheduler's
    ``schedule`` (timed by the host clock) and each prediction
    service's ``solve_many`` (its answers are kept for the check)."""
    services = [s.prediction_service for s in _schedulers(plat)]
    for sched in _schedulers(plat):
        def timed(fn, count, now, _orig=sched.schedule):
            ann = _annotation(run, "bench.schedule")
            if ann is not None:
                ann.__enter__()
            t = time.perf_counter()
            out = _orig(fn, count, now)
            if run.in_window:
                run.place_ms.append((time.perf_counter() - t) * 1e3)
            if ann is not None:
                ann.__exit__(None, None, None)
            return out
        sched.schedule = timed
    for svc in services:
        if svc is None:
            raise BenchError("the cell's scheduler has no prediction "
                             "service to check")
        def kept(queries, _orig=svc.solve_many):
            ann = _annotation(run, "bench.solve_many")
            if ann is not None:
                ann.__enter__()
            out = _orig(queries)
            if run.in_window:
                run.answers.append((queries, out, run.forest_idx))
            if ann is not None:
                ann.__exit__(None, None, None)
            return out
        svc.solve_many = kept


def _observer(run: Run, warmup_s: int, on_open: Callable,
              stop_after: Optional[float]):
    """A tick observer: opens the window after the warm-up prefix, keeps
    the spans that close inside it and, for a placement window, ends the
    run once the window has lasted ``stop_after`` seconds."""
    from repro.core.events import Observer

    class _Clock(Observer):
        tick_ann = None

        def on_tick(self, now, sim):
            t = int(now)
            if self.tick_ann is not None:
                self.tick_ann.__exit__(None, None, None)
                self.tick_ann = None
            if run.in_window:
                run.fleet_s += 1
                cells = getattr(sim, "cells", None)
                run.nodes.append(sum(len(c.cluster.nodes) for c in cells)
                                 if cells else len(sim.cluster.nodes))
                elapsed = time.perf_counter() - run.t0
                if stop_after is not None and elapsed >= stop_after:
                    run.window_s = elapsed
                    run.in_window = False
                    raise WindowClosed()
            elif t == warmup_s - 1:
                on_open()
            if run.in_window:
                self.tick_ann = _annotation(run, "bench.tick")
                if self.tick_ann is not None:
                    self.tick_ann.__enter__()

        def on_span(self, span):
            if run.in_window:
                run.spans.append((span.name, span.t_start_s, span.dur_ms,
                                  span.depth, dict(span.attrs)))

    return _Clock()


def _frozen_model(model, forest):
    """A forest of the program's class holding a frozen forest's arrays,
    as a refit leaves them (its device copy is made on first use)."""
    fresh = type(model)(model.n_trees, model.max_depth,
                        model.min_samples_leaf)
    fresh.arrays = type(model.arrays)(*(np.array(a) for a in forest))
    return fresh


def _retrain(svc, model) -> None:
    """What an online retrain does to the service, with the refit done
    ahead of time: the new forest in place, the epoch bumped
    (``PerfPredictor.retrain``) and the cache dropped."""
    svc.predictor.model = model
    svc.predictor.retrain_count += 1
    svc.invalidate()


def _coloc(node) -> Dict[str, tuple]:
    return {g: (float(s.n_sat), float(s.n_cached))
            for g, s in node.funcs.items() if s.total > 0}


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             t_start: float, device: Dict[str, Any],
             trace_dir: Optional[Path] = None,
             compile_counter: Optional[CompileCounter] = None
             ) -> Dict[str, Any]:
    """Set up, measure and check one run; returns the result line."""
    from repro.platform import Platform

    if traced and trace_dir is None:
        raise BenchError("a traced run needs a directory for its trace")
    run = Run(cell, seed, seconds, traced, device_kind=device["kind"])
    traffic, config = cell.traffic, cell.config
    warmup = int(traffic["warmup_s"])
    scenario, manifest = world.build_inputs(config, traffic, seed,
                                            spans=traced)
    data = reference.load_world(config["world_file"])
    plat = Platform.build(scenario=scenario, config=manifest)
    _instrument(plat, run)
    pred = config["prediction"]
    run.forest = {"trees": int(pred["n_trees"]),
                  "depth": int(pred["max_depth"]),
                  "features": reference.N_FEATURES}
    compiles0 = [0]
    setup = {}
    scheds = _schedulers(plat)
    failed0 = [0]

    def open_window():
        if traced:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # annotations only
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        if compile_counter is not None:
            compiles0[0] = compile_counter.n
        failed0[0] = sum(x.metrics.failed for x in scheds)
        run.t0 = time.perf_counter()
        setup["s"] = run.t0 - t_start
        run.in_window = True

    mode = traffic["window"]
    if mode == "ticks":
        plat.add_observer(_observer(run, warmup, open_window, seconds))
        try:
            plat.run()
        except WindowClosed:
            pass
        else:
            run.window_s = time.perf_counter() - run.t0
            run.in_window = False
    elif mode == "refresh":
        plat.add_observer(_observer(run, warmup, lambda: None, None))
        plat.run(warmup)
        svc = plat.service
        nodes = list(plat.cluster.nodes.values())
        models = [svc.predictor.model] + [
            _frozen_model(svc.predictor.model, f)
            for f in data["forests"][1:]]
        for k in (1, 0):                   # two cycles of set-up
            _retrain(svc, models[k])
            svc.refresh_tables(nodes)
        picked = sorted(random.Random(seed).sample(
            range(len(nodes)), min(SAMPLE_NODES, len(nodes))))
        picked = [nodes[i] for i in picked]
        open_window()
        while True:
            ann = _annotation(run, "bench.refresh")
            if ann is not None:
                ann.__enter__()
            run.forest_idx = (run.cycles + 1) % len(models)
            _retrain(svc, models[run.forest_idx])
            svc.refresh_tables(nodes)
            if ann is not None:
                ann.__exit__(None, None, None)
            run.tables.append((run.forest_idx, [
                {fn: e.capacity for fn, e in n.table.items()}
                for n in picked]))
            run.cycles += 1
            if time.perf_counter() - run.t0 >= seconds:
                break
        run.window_s = time.perf_counter() - run.t0
        run.in_window = False
        run.nodes = [len(nodes)]
    else:
        raise BenchError(f"unknown window kind {mode!r}")
    if "s" not in setup:
        raise BenchError("the run ended before its window opened")
    if compile_counter is not None:
        run.window_compiles = compile_counter.n - compiles0[0]
    run.failed = sum(x.metrics.failed for x in scheds) - failed0[0]
    if traced:
        import jax
        from . import trace_reduce
        jax.profiler.stop_trace()
        run.device = trace_reduce.reduce_dir(trace_dir, run.window_s,
                                             cell.chips)

    device = dict(device)
    device["memory_peak_bytes"] = _memory_peak()
    if traced:
        device["busy_s"] = run.device["busy_s"]
        device["window_s"] = run.device["window_s"]

    # the check, once the program's state is dropped
    tables = None
    if mode == "refresh":
        tables = (run.tables, [_coloc(n) for n in picked],
                  int(config["m_max"]))
        del nodes, picked, models, svc
    answers, run.answers, run.tables = run.answers, [], []
    del plat
    check = reference.check(data, answers, seed, SAMPLE_ANSWERS,
                            SAMPLE_WIDEST, tables)

    metrics: Dict[str, Dict[str, Any]] = {}
    if traced:
        for name in cell.per_layer:
            value = cell.readers[name](run)
            if value is not None:
                metrics[name] = {"value": value, "unit": cell.units[name]}
    else:
        for name, value in end_to_end(run, setup["s"]).items():
            if name in cell.end_to_end:
                metrics[name] = {"value": value, "unit": cell.units[name]}
    attempted = len(run.place_ms) if mode == "ticks" else run.cycles
    result = {
        "correct": bool(check["correct"]),
        "attempted": attempted,
        "failed": run.failed,
        "metrics": metrics,
        "device": device,
    }
    if traced:
        result["breakdown"] = run.device["breakdown"]
    result["run"] = {
        "window_s": run.window_s, "fleet_s": run.fleet_s,
        "cycles": run.cycles, "decisions": len(run.place_ms),
        "place_ms_max": max(run.place_ms) if run.place_ms else None,
        "window_compiles": run.window_compiles,
        "nodes_mean": float(np.mean(run.nodes)) if run.nodes else 0.0,
        "nodes_peak": max(run.nodes) if run.nodes else 0,
        "answers_checked": check["checked"],
    }
    result["checks"] = check["numbers"]
    return result


def end_to_end(run: Run, setup_s: float) -> Dict[str, float]:
    """The end-to-end metrics a window supports, from the host clock."""
    out = {"setup_s": setup_s}
    if run.place_ms:
        out["place_ms_p50"] = float(np.percentile(run.place_ms, 50))
        out["place_ms_p90"] = float(np.percentile(run.place_ms, 90))
    if run.fleet_s:
        out["tick_ms"] = run.window_s * 1e3 / run.fleet_s
    if run.cycles:
        out["refresh_ms"] = run.window_s * 1e3 / run.cycles
    return out


def _memory_peak() -> int:
    import jax
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


# ---------------------------------------------------------------------------
# Command line
# ---------------------------------------------------------------------------


def main(argv: Optional[List[str]] = None, t_start: Optional[float] = None
         ) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = find_cell(args.workload)
        device = check_device(cell.chips)
    except (BenchError, OSError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    enable_compile_cache(ROOT)
    counter = CompileCounter()
    trace_dir = ROOT / ".bench_trace" / args.workload if args.trace else None
    if trace_dir is not None:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start, device, trace_dir, counter)
    if trace_dir is not None:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
    for name, (value, limit) in result["checks"].items():
        print(f"check {name}: {value!r} limit {limit!r}", file=sys.stderr)
    print(json.dumps(result, default=float), flush=True)
    return 0
