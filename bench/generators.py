"""The benchmark's own copies of the traffic and population generators.

Copied from ``repro.core.traces`` (``burst_storm_trace``,
``azure_sparse_trace``) and ``repro.core.scenarios`` (``zipf_weights``,
``scenario_functions``, ``expected_mean_nodes``,
``scale_trace_to_nodes`` and the population half of ``make_scenario``)
so that a later change to the program cannot change what the benchmark
offers it.  ``bench/tests/test_generators.py`` holds them equal to the
program's for the same seed and parameters, but for the departures
below.

Everything here is plain numpy: series are ``{name: (T,) float64}`` and
functions are ``{name: {field: value}}`` with the fields of
``repro.core.profiles.FunctionSpec``.  The departures:

  * ``burst_storm(period_s=..., width_s=..., amp=...)`` starts one
    storm of a fixed width and height every period instead of at
    Poisson times, and otherwise draws exactly what the original draws;
  * ``azure_sparse`` gives its head a period of a day
    (``_head_period``), where the original draws one, and given
    ``scale_rps`` makes the most popular functions its head, where the
    original takes the first names; over the names in that order it
    draws what the original draws.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import numpy as np

Series = Dict[str, np.ndarray]
Functions = Dict[str, Dict[str, float]]


# ---------------------------------------------------------------------------
# Population
# ---------------------------------------------------------------------------


def zipf_weights(n: int, s: float = 1.2, seed: int = 0) -> np.ndarray:
    """Normalised Zipf popularity over a seed-shuffled rank assignment."""
    ranks = np.arange(1, n + 1, dtype=np.float64)
    w = ranks ** -s
    w /= w.sum()
    rng = np.random.default_rng(seed)
    return w[rng.permutation(n)]


def scenario_functions(n_functions: int, seed: int = 0) -> Functions:
    """The large-cluster population: requested sizes, rates and the
    hidden resource behaviour of each function (FunctionSpec fields)."""
    rng = np.random.default_rng(seed + 17)
    out: Functions = {}
    for i in range(n_functions):
        name = f"sfn{i:03d}"
        cpu_req = float(rng.choice([1000.0, 2000.0, 2000.0, 4000.0]))
        slots = cpu_req / 1000.0
        out[name] = dict(
            name=name,
            cpu_req=cpu_req,
            mem_req=float(rng.choice([512.0, 1024.0, 2048.0])),
            saturated_rps=float(rng.uniform(8, 60)),
            exec_ms=float(rng.uniform(10, 80)),
            cpu_work=float(rng.uniform(0.22, 0.5)),
            mem_work=float(rng.uniform(0.3, 0.7)),
            bw_demand=slots * float(rng.uniform(0.2, 0.75)),
            cache_mb=slots * float(rng.uniform(0.3, 1.1)),
            cpu_sens=float(rng.uniform(0.7, 1.5)),
            bw_sens=float(rng.uniform(0.7, 1.5)),
            cache_sens=float(rng.uniform(0.7, 1.5)),
        )
    return out


def popularity(names: Sequence[str], zipf_s: float, seed: int
               ) -> Dict[str, float]:
    """Per-function peak-rate shares with mean 1, as ``make_scenario``
    hands them to its trace builder."""
    w = zipf_weights(len(names), s=zipf_s, seed=seed + 1)
    return {fn: float(len(names) * wi) for fn, wi in zip(names, w)}


# ---------------------------------------------------------------------------
# Trace programs
# ---------------------------------------------------------------------------


def burst_storm(fn_names: List[str], duration_s: int = 3600, seed: int = 0,
                scale_rps: Optional[Dict[str, float]] = None,
                storms_per_hour: float = 10.0, coherence: float = 0.6,
                period_s: Optional[int] = None, offset_s: int = 0,
                width_s: Optional[int] = None, amp: Optional[float] = None
                ) -> Series:
    """Correlated cross-function spike storms over a quiet base load.

    With ``period_s`` set, storm k starts at ``offset_s + k * period_s``
    (every start inside the trace) instead of at a Poisson time, and
    ``width_s`` and ``amp`` fix every storm's width and height; each
    storm still consumes the draws of the Poisson form, so the functions
    it recruits are drawn as there."""
    rng = np.random.default_rng(seed)
    t = np.arange(duration_s, dtype=np.float64)
    base = {}
    for fn in fn_names:
        level = rng.uniform(0.15, 0.45)
        period = rng.uniform(1200, 3000)
        phase = rng.uniform(0, 2 * math.pi)
        base[fn] = level * (0.8 + 0.2 * np.sin(2 * math.pi * t / period
                                               + phase))
    n_storms = max(1, int(rng.poisson(storms_per_hour * duration_s / 3600)))
    starts = None
    if period_s is not None:
        starts = list(range(offset_s, duration_s, period_s))
        n_storms = len(starts)
    storm = {fn: np.zeros(duration_s) for fn in fn_names}
    for k in range(n_storms):
        s = int(rng.integers(0, duration_s))
        if starts is not None:
            s = starts[k]
        w = int(rng.uniform(20, 90))
        if width_s is not None:
            w = width_s
        e = min(s + w, duration_s)
        a = rng.uniform(3.0, 8.0)
        if amp is not None:
            a = amp
        envelope = a * np.linspace(1, 0, e - s) ** 0.7
        hit = rng.random(len(fn_names)) < coherence
        if not hit.any():
            hit[rng.integers(len(fn_names))] = True
        for fn, h in zip(fn_names, hit):
            if h:
                storm[fn][s:e] = np.maximum(storm[fn][s:e], envelope)
    out = {}
    for fn in fn_names:
        shape = base[fn] * (1 + storm[fn])
        shape = shape * rng.lognormal(0, 0.2, duration_s)
        peak = (scale_rps or {}).get(fn, rng.uniform(40, 400))
        out[fn] = np.clip(shape * peak, 0.0, None)
    return out


#: the head's diurnal period: a day, as in the Azure Functions trace
#: (Shahrad et al., "Serverless in the Wild", USENIX ATC 2020)
DAY_S = 86400.0


def _head_period(drawn: float) -> float:
    """A head function's period: a day, in place of the original's draw
    from 1,500-3,600 s, which is still made so that no later draw
    moves."""
    return DAY_S


def azure_sparse(fn_names: List[str], duration_s: int = 3600, seed: int = 0,
                 scale_rps: Optional[Dict[str, float]] = None,
                 hot_frac: float = 0.1, zipf_s: float = 1.5) -> Series:
    """A hot head with diurnal load and a Zipf long tail of sparse,
    few-second invocation episodes at Poisson times.

    Shaped on the Azure Functions trace (Shahrad et al., "Serverless in
    the Wild", USENIX ATC 2020): invocations concentrate in the most
    popular functions, and their load follows a daily cycle: the head's
    period is ``DAY_S``.  With ``scale_rps`` the head is the
    ``hot_frac`` of functions with the largest shares (ties broken by
    name) and tail rank 1 is the largest share left, so the tail's
    episode rate follows popularity; without it the head is the first
    names."""
    rng = np.random.default_rng(seed)
    t = np.arange(duration_s, dtype=np.float64)
    n_hot = max(1, int(round(hot_frac * len(fn_names))))
    order = list(fn_names)
    if scale_rps is not None:
        order.sort(key=lambda fn: (-scale_rps.get(fn, 0.0), fn))
    out = {}
    for i, fn in enumerate(order):
        if i < n_hot:
            period = _head_period(rng.uniform(1500, 3600))
            phase = rng.uniform(0, 2 * math.pi)
            shape = (0.45 + 0.35 * np.sin(2 * math.pi * t / period + phase)
                     ) * rng.lognormal(0, 0.2, duration_s)
            peak = (scale_rps or {}).get(fn, rng.uniform(80, 400))
            out[fn] = np.clip(shape * peak, 0.0, None)
            continue
        rank = i - n_hot + 1
        rate_per_hour = 30.0 / rank ** zipf_s + 0.2
        series = np.zeros(duration_s)
        n_events = rng.poisson(rate_per_hour * duration_s / 3600)
        peak = (scale_rps or {}).get(fn, rng.uniform(3, 15))
        for _ in range(n_events):
            s = int(rng.integers(0, duration_s))
            e = min(s + int(rng.uniform(2, 8)), duration_s)
            series[s:e] = peak * rng.uniform(0.5, 1.0)
        out[fn] = series
    return {fn: out[fn] for fn in fn_names}


#: trace programs a traffic file may name under ``"generator"``
GENERATORS = {"burst-storm": burst_storm, "azure-sparse": azure_sparse}


# ---------------------------------------------------------------------------
# Scaling to a fleet size
# ---------------------------------------------------------------------------


def expected_mean_nodes(rps: Series, functions: Functions,
                        node_cpu_mcores: float) -> float:
    """Mean requested-CPU demand of the series, in nodes."""
    mcores = 0.0
    for fn, series in rps.items():
        f = functions[fn]
        mean_inst = float(np.mean(series)) / f["saturated_rps"]
        mcores += mean_inst * f["cpu_req"]
    return mcores / max(node_cpu_mcores, 1e-9)


def scale_to_nodes(rps: Series, functions: Functions, target_nodes: int,
                   node_classes: Sequence[Dict[str, float]],
                   utilization: float = 0.8) -> Series:
    """Rescale every series so that mean requested CPU fills
    ``utilization`` of ``target_nodes`` mean-shaped nodes; node classes
    are ``{"cpu_mcores": ..., "weight": ...}``."""
    tot_w = sum(max(int(c["weight"]), 1) for c in node_classes)
    mean_cpu = sum(c["cpu_mcores"] * max(int(c["weight"]), 1)
                   for c in node_classes) / max(tot_w, 1)
    demand = expected_mean_nodes(rps, functions, mean_cpu)
    factor = target_nodes * utilization / max(demand, 1e-9)
    return {fn: series * factor for fn, series in rps.items()}
