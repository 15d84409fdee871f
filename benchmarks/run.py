"""Benchmark driver: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]

| paper artifact                          | module            |
|-----------------------------------------|-------------------|
| Fig 11/12 scheduling cost + cold start  | scheduling_cost   |
| Table 2 overhead vs container systems   | scheduling_cost   |
| Fig 13 normalized density               | density           |
| Fig 14 QoS violations + reduced starts  | qos_coldstart     |
| Fig 15/16/17 prediction + model zoo     | prediction        |
| capacity-engine scaling (24->512 nodes) | capacity_engine   |
| large-cluster scenario study + A/B gate | large_cluster     |
| kernel/arch microbench                  | model_perf        |
| §Roofline table (reads dry-run JSONs)   | roofline_report   |
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="shorter traces / fewer repetitions")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (capacity_engine, density, large_cluster, model_perf,
                   prediction, qos_coldstart, roofline_report,
                   scheduling_cost)
    suites = [
        ("scheduling_cost", lambda: scheduling_cost.run(
            duration=300 if args.quick else 600, quick=args.quick)),
        ("density", lambda: density.run(
            duration=300 if args.quick else 600, quick=args.quick)),
        ("qos_coldstart", lambda: qos_coldstart.run(
            duration=300 if args.quick else 600, quick=args.quick)),
        ("prediction", lambda: prediction.run(quick=args.quick)),
        ("capacity_engine", lambda: capacity_engine.run(
            quick=args.quick, bench=True)),
        # the large-cluster study is driven through repro.platform
        # manifests: one PlatformConfig.from_dict-validated dict per
        # (scenario, size, system) run, derived from this spec; each
        # run's observer streams (ticks / schedule decisions with
        # DecisionTrace summaries / scaling / retrains) land in
        # artifacts/events/*.jsonl for cross-run dashboards
        # both studies persist RunReports into the repo-root
        # BENCH_*.json trajectories (repro.telemetry.report) — the
        # regression gate and the dashboard read them
        ("large_cluster", lambda: large_cluster.run(
            quick=args.quick,
            spec=large_cluster.study_spec(quick=args.quick),
            bench=True)),
        ("model_perf", lambda: model_perf.run(quick=args.quick)),
        ("roofline_report", lambda: roofline_report.run()),
    ]
    for name, fn in suites:
        if args.only and name != args.only:
            continue
        print(f"\n{'=' * 70}\n# benchmark: {name}\n{'=' * 70}")
        t0 = time.time()
        fn()
        print(f"# {name} done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
