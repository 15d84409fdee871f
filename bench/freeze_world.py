#!/usr/bin/env python3
"""Write a configuration's frozen world: the data the reference runs on.

    python3 bench/freeze_world.py bench/configs/<name>.json

The reference (``reference.py``) takes nothing that the program makes at
run time.  Its data are frozen once, here, into the ``.npz`` file that
the configuration's ``world_file`` names:

  * ``forests``: two forests of the configuration's trees and depth,
    fitted on the configuration's training rows.  Forest 0 is the one
    the program fits at ``Platform.build`` from ``world_seed``; forest 1
    is a refit of the same rows with the bootstrap seed
    ``world_seed + 1``, what an online retrain hands the service.  A
    refresh window swaps between the two on every cycle;
  * per function, its solo-run profile, solo latency and QoS target.

A run whose program fits another forest, or builds other profiles or
targets, answers differently from the reference and reads not correct.
"""
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def world_arrays(config) -> dict:
    """The frozen world of ``config`` as named arrays."""
    from repro.core.predictor import RandomForestRegressor
    from repro.core.scenarios import scenario_world

    from bench import world

    p = config["prediction"]
    scenario = world.population_scenario(config)
    w = scenario_world(scenario, n_train=p["n_train"],
                       n_trees=p["n_trees"], max_depth=p["max_depth"],
                       schema_version=p["schema_version"])
    pred = w.predictor
    X, y = pred.dataset()
    if pred.log_target:
        y = np.log(np.maximum(y, 1e-6))
    refit = RandomForestRegressor(p["n_trees"], p["max_depth"],
                                  seed=int(config["world_seed"]) + 1)
    refit.fit(X, y)
    forests = [pred.model.arrays, refit.arrays]
    names = sorted(scenario.specs)
    specs = [scenario.specs[n] for n in names]
    return {
        "feat": np.stack([f.feat for f in forests]).astype(np.int32),
        "thr": np.stack([f.thr for f in forests]).astype(np.float32),
        "leaf": np.stack([f.leaf for f in forests]).astype(np.float32),
        "log_target": np.array(bool(pred.log_target)),
        "names": np.array(names),
        "profile": np.stack([np.asarray(w.store.profile(s), np.float64)
                             for s in specs]),
        "solo": np.array([float(w.qos.solo(s)) for s in specs]),
        "qos": np.array([float(w.qos.qos(s)) for s in specs]),
    }


def freeze(config, out: str) -> str:
    np.savez_compressed(out, **world_arrays(config))
    return out


if __name__ == "__main__":
    cfg = json.loads(open(sys.argv[1]).read())
    print(freeze(cfg, os.path.join(ROOT, cfg["world_file"])))
