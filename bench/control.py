#!/usr/bin/env python3
"""The readings the ``cap_gap`` limit is set from, on the chip:

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed it runs the cell once as the benchmark does, then reads
the widest gap twice on the same sampled answers (and, in a refresh
window, tables): the program's (the lower reading) and the control's,
the reference computed in bfloat16 and put in the program's place (the
upper reading).  One JSON line per seed.
The benchmark's own runs never run the control.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from bench import harness, reference  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(prog="bench/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    cell = harness.find_cell(args.workload)
    device = harness.check_device(cell.chips)
    harness.enable_compile_cache(harness.ROOT)
    kept = {}
    check = reference.check

    def keep(data, answers, seed, n, widest, tables=None):
        kept.update(data=data, answers=answers, tables=tables)
        return check(data, answers, seed, n, widest, tables)

    reference.check = keep
    n, widest = harness.SAMPLE_ANSWERS, harness.SAMPLE_WIDEST
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        res = harness.run_cell(cell, seed, args.seconds, False, t0, device)
        data = kept["data"]
        picked = {"answers": reference.sample(reference.distinct_answers(
            kept["answers"]), seed, n, widest)}
        if kept["tables"] is not None:
            entries, _missing = reference.distinct_entries(*kept["tables"])
            picked["tables"] = reference.sample(entries, seed, n, widest)
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "program": {k: reference.evaluate(data, v)
                        for k, v in picked.items()},
            "control": {k: reference.evaluate(data, v, control=True)
                        for k, v in picked.items()},
            "correct": res["correct"], "checks": res["checks"],
            "run": res["run"]}), flush=True)
        kept.clear()
    return 0


if __name__ == "__main__":
    sys.exit(main())
