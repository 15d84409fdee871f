"""From a configuration file, a traffic file and a seed to the program's
inputs: the ``Scenario`` that ``Platform.build`` takes and the manifest
it is built from.

A configuration (``bench/configs/<name>.json``) fixes the deployment:
fleet size, node classes, population, cells, scheduler and forest.  A
traffic mix (``bench/traffic/<name>.json``) names one generator of
``bench/generators.py`` with its parameters and says how the window is
driven.  The configuration's ``world_seed`` draws the population, the
popularity ranks and, through the scenario's seed, the forest's training
data and the profiles.  The run's seed draws the trace, unless the
traffic file pins it with ``trace_seed``: then every run offers the
same load, and the seed draws only the answers the check samples.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

from . import generators

#: fleet seconds a placement trace holds after its warm-up prefix: far
#: more than any window plays, so that a faster program never runs out
HORIZON_S = 3600


def node_classes(config: Dict[str, Any]):
    from repro.core.interference import NodeResources
    from repro.core.scenarios import NodeClass

    return [NodeClass(c["name"], NodeResources(
        cpu_mcores=c["cpu_mcores"], mem_mb=c["mem_mb"],
        mem_bw_gbps=c["mem_bw_gbps"], llc_mb=c["llc_mb"]),
        weight=c["weight"]) for c in config["node_classes"]]


def _population(config: Dict[str, Any]):
    """The functions of the configuration, drawn from ``world_seed``."""
    from repro.core.profiles import FunctionSpec

    functions = generators.scenario_functions(config["n_functions"],
                                              seed=int(config["world_seed"]))
    return functions, {fn: FunctionSpec(**functions[fn])
                       for fn in sorted(functions)}


def _scenario(config, specs, kind: str, rps, duration: int, name: str,
              trace_name: str):
    from repro.core.scenarios import Scenario
    from repro.core.traces import Trace

    return Scenario(name, kind, specs, Trace(trace_name, rps, duration),
                    node_classes(config), config["target_nodes"],
                    int(config["world_seed"]))


def population_scenario(config: Dict[str, Any]):
    """A scenario of the configuration's population with an idle trace:
    everything the world (forest, profiles, QoS targets) is built
    from."""
    _functions, specs = _population(config)
    return _scenario(config, specs, "population",
                     {fn: np.zeros(1) for fn in specs}, 1,
                     f"{config['name']}-population", "population")


def build_inputs(config: Dict[str, Any], traffic: Dict[str, Any],
                 seed: int, spans: bool = False) -> Tuple[Any, Dict]:
    """The scenario and the platform manifest of one run."""
    duration = int(traffic["warmup_s"]) + HORIZON_S
    wseed = int(config["world_seed"])
    functions, specs = _population(config)
    names = sorted(specs)
    gen = generators.GENERATORS[traffic["generator"]]
    rps = gen(names, duration_s=duration,
              seed=int(traffic.get("trace_seed", seed)),
              scale_rps=generators.popularity(names, config["zipf_s"],
                                              wseed),
              **traffic.get("params", {}))
    rps = generators.scale_to_nodes(rps, functions, config["target_nodes"],
                                    config["node_classes"],
                                    config["utilization"])
    scenario = _scenario(config, specs, traffic["generator"], rps, duration,
                         f"{config['name']}-{traffic['generator']}-"
                         f"seed{seed}", f"{traffic['generator']}-seed{seed}")
    manifest = {
        "scenario": {"kind": traffic["generator"],
                     "n_functions": config["n_functions"],
                     "duration_s": duration,
                     "target_nodes": config["target_nodes"], "seed": seed},
        "scheduler": {"name": config["scheduler"],
                      "m_max": config["m_max"]},
        "prediction": {k: config["prediction"][k]
                       for k in ("schema_version", "n_train", "n_trees",
                                 "max_depth")},
        "cells": {"count": config["cells"]},
        "telemetry": {"metrics": False, "spans": spans},
    }
    return scenario, manifest
