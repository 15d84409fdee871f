"""The plain reference for the capacities the control plane answers, and
the comparison that decides ``correct``.

Jiagu's capacity of function f among a colocation is the largest m in
1..m_max such that, with m saturated instances of f beside the node's
other functions, every colocated function's predicted latency meets its
QoS target.  The reference computes it in the plainest way: it builds
each feature row of the schema-v1 layout (the paper's 31-vector) in
float64, casts it to float32 as the program's schema states, descends
every tree of the forest one level at a time, averages the leaves in
float64, undoes the log target and compares with the QoS bound.  It
imports nothing of the program and takes nothing that the program makes
at run time: it reads the configuration's frozen world
(``freeze_world.py``): two forests, and per function its solo profile,
solo latency and QoS target.

Two numbers are compared, each against the forest that was current
when the answer was made:

  * ``cap_gap``, over a seeded sample of the capacities the prediction
    service answered in the window: the widest relative margin by which
    the reference's predictions contradict an answered capacity c: a
    row at some m <= c predicted above its bound (c too high), or every
    row at m = c + 1 predicted within its bounds (c too low).  It is 0
    where the answers equal the reference's capacities, and of the
    order of float32 rounding where a prediction lies on its bound;
  * ``table_gap``, in a refresh window: the same margin over the
    capacity tables of a seeded sample of nodes as every cycle left
    them.  An entry missing for a function the node hosts reads
    infinite.
"""
from __future__ import annotations

import random
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

#: the limit on ``cap_gap`` and ``table_gap``: see PERF.md for the
#: readings it was set from
CAP_GAP_LIMIT = 1e-3

#: the schema-v1 row: solo latency, 13 profile metrics, the target's own
#: saturated and cached counts, 13 summed metrics and the node's totals
N_FEATURES = 31


def load_world(path) -> Dict[str, Any]:
    """The frozen world of a configuration (``freeze_world.py``)."""
    with np.load(path) as z:
        names = [str(n) for n in z["names"]]
        funcs = {n: (z["profile"][i].astype(np.float64), float(z["solo"][i]),
                     float(z["qos"][i])) for i, n in enumerate(names)}
        forests = [(z["feat"][k], z["thr"][k], z["leaf"][k])
                   for k in range(len(z["feat"]))]
        return {"forests": forests, "log_target": bool(z["log_target"]),
                "funcs": funcs}


# ---------------------------------------------------------------------------
# Rows and predictions
# ---------------------------------------------------------------------------


def scenario_rows(funcs, fn: str, neigh: Sequence[Tuple[str, float, float]],
                  m_max: int) -> Tuple[np.ndarray, np.ndarray]:
    """Feature rows (m_max, R, 31) float64 and bounds (m_max, R) of one
    scenario: per m, the target's row then one row per neighbour."""
    prof_f, solo_f, qos_f = funcs[fn]
    ms = np.arange(1, m_max + 1, dtype=np.float64)
    others = np.zeros(prof_f.shape)
    sat = cached = 0.0
    for g, ns, nc in neigh:
        others = others + funcs[g][0] * ns
        sat += ns
        cached += nc
    R = 1 + len(neigh)
    X = np.zeros((m_max, R, N_FEATURES))
    B = np.zeros((m_max, R))
    X[:, 0, 0] = solo_f
    X[:, 0, 1:14] = prof_f
    X[:, 0, 14] = ms
    X[:, 0, 15] = 0.0
    X[:, 0, 16:29] = ms[:, None] * prof_f + others
    X[:, 0, 29] = ms + sat
    X[:, 0, 30] = cached
    B[:, 0] = qos_f
    for j, (g, ns, nc) in enumerate(neigh, start=1):
        prof_g, solo_g, qos_g = funcs[g]
        X[:, j, 0] = solo_g
        X[:, j, 1:14] = prof_g
        X[:, j, 14] = ns
        X[:, j, 15] = nc
        X[:, j, 16:29] = others + ms[:, None] * prof_f
        X[:, j, 29] = sat + ms
        X[:, j, 30] = cached
        B[:, j] = qos_g
    return X, B


def predict(data, X: np.ndarray, dtype=np.float32, forest: int = 0
            ) -> np.ndarray:
    """Predictions of forest ``forest`` for float64 rows X (N, 31)
    computed in ``dtype``: rows and thresholds are cast to it, the
    descent compares in it and the leaves are averaged from it (in
    float64 for float32, in float32 for a lower precision)."""
    feat, thr, leaf = data["forests"][forest]
    thr, leaf = thr.astype(dtype), leaf.astype(dtype)
    Xd = X.astype(np.float32).astype(dtype)
    T, NN = feat.shape
    n = len(Xd)
    rows = np.arange(n)
    acc_t = np.float64 if dtype == np.float32 else np.float32
    total = np.zeros(n, acc_t)
    for t in range(T):
        node = np.zeros(n, np.int64)
        for _ in range(int(np.log2(NN + 1))):
            right = Xd[rows, feat[t, node]] >= thr[t, node]
            node = 2 * node + 1 + right
        total = total + leaf[t, node - NN].astype(acc_t)
    mean = total.astype(np.float64) / T
    return np.exp(mean) if data["log_target"] else mean


def capacity(P: np.ndarray, B: np.ndarray) -> int:
    """Longest prefix of m whose rows all meet their bounds."""
    ok = (P <= B).all(axis=1)
    return int(np.argmin(ok)) if not ok.all() else len(ok)


def gap(P: np.ndarray, B: np.ndarray, cap: int) -> float:
    """How far the predictions P (m_max, R) contradict capacity cap."""
    rel = (P - B) / B
    g = 0.0
    if cap > 0:
        g = max(g, float(rel[:cap].max()))
    if cap < len(P):
        g = max(g, float((-rel[cap]).min()))
    return g


# ---------------------------------------------------------------------------
# The comparison
# ---------------------------------------------------------------------------

#: a scenario judged against one forest: (forest, target, m_max,
#: ((neighbour, saturated, cached), ...))
Key = Tuple[int, str, int, tuple]


def scenario_key(forest: int, coloc: Dict[str, Tuple[float, float]],
                 fn: str, m_max: int) -> Key:
    neigh = tuple(sorted((g, float(ns), float(nc))
                         for g, (ns, nc) in coloc.items()
                         if g != fn and ns + nc > 0))
    return (int(forest), fn, int(m_max), neigh)


def distinct_answers(answers) -> Dict[Key, set]:
    """Every distinct (scenario, capacity) pair answered; ``answers``
    holds (queries, results, forest) per ``solve_many`` call."""
    out: Dict[Key, set] = {}
    for queries, results, forest in answers:
        for q, (cap, _rows) in zip(queries, results):
            key = scenario_key(forest, q[0], q[1], q[2])
            out.setdefault(key, set()).add(int(cap))
    return out


def distinct_entries(snapshots, colocs, m_max: int
                     ) -> Tuple[Dict[Key, set], int]:
    """The distinct (scenario, capacity) pairs of the sampled nodes'
    tables over every cycle, and the count of entries missing for a
    function a node hosts.  ``snapshots`` holds (forest, [table per
    node]) per cycle; ``colocs`` each sampled node's colocation."""
    out: Dict[Key, set] = {}
    missing = 0
    for forest, tables in snapshots:
        for coloc, table in zip(colocs, tables):
            for fn in coloc:
                if fn not in table:
                    missing += 1
                    continue
                out.setdefault(scenario_key(forest, coloc, fn, m_max),
                               set()).add(int(table[fn]))
    return out, missing


def sample(distinct: Dict[Key, set], seed: int, n: int, widest: int
           ) -> List[Tuple[Key, int]]:
    """A seeded sample of the answered pairs, with the widest scenarios
    (most rows) always in it."""
    pairs = sorted((k, c) for k, caps in distinct.items() for c in caps)
    by_width = sorted(pairs, key=lambda p: -p[0][2] * (1 + len(p[0][3])))
    keep = set(by_width[:widest])
    rest = [p for p in pairs if p not in keep]
    rng = random.Random(seed)
    keep.update(rng.sample(rest, min(n, len(rest))))
    return sorted(keep)


def evaluate(data, picked: Sequence[Tuple[Key, int]],
             control: bool = False) -> Dict[str, float]:
    """The widest gap of the picked answers; with ``control`` the
    answers are replaced by the reference's own capacities computed in
    bfloat16, the precision below the float32 that the schema states."""
    if not picked:
        return {"gap": float("inf"), "wrong": 0, "checked": 0}
    worst, wrong = 0.0, 0
    for forest in sorted({k[0] for k, _c in picked}):
        mine = [(k, c) for k, c in picked if k[0] == forest]
        blocks, shapes = [], []
        for (_f, fn, m_max, neigh), _cap in mine:
            X, B = scenario_rows(data["funcs"], fn, neigh, m_max)
            blocks.append(X.reshape(-1, N_FEATURES))
            shapes.append(B)
        X = np.concatenate(blocks)
        P = predict(data, X, forest=forest)
        Pc = None
        if control:
            import ml_dtypes
            Pc = predict(data, X, ml_dtypes.bfloat16, forest=forest)
        off = 0
        for (_key, cap), B in zip(mine, shapes):
            n = B.size
            p = P[off:off + n].reshape(B.shape)
            if Pc is not None:
                cap = capacity(Pc[off:off + n].reshape(B.shape), B)
            off += n
            wrong += cap != capacity(p, B)
            worst = max(worst, gap(p, B, cap))
    return {"gap": worst, "wrong": wrong, "checked": len(picked)}


def check(data, answers, seed: int, n: int, widest: int,
          tables=None) -> Dict[str, Any]:
    """The verdict of one run: its sampled answers, and in a refresh
    window its sampled tables (``(snapshots, colocs, m_max)``), against
    the reference.  A window with nothing to compare is not correct."""
    got = evaluate(data, sample(distinct_answers(answers), seed, n, widest))
    numbers = {"cap_gap": (got["gap"], CAP_GAP_LIMIT)}
    checked, wrong = got["checked"], got["wrong"]
    if tables is not None:
        entries, missing = distinct_entries(*tables)
        tab = evaluate(data, sample(entries, seed, n, widest))
        numbers["table_gap"] = (float("inf") if missing else tab["gap"],
                                CAP_GAP_LIMIT)
        checked, wrong = checked + tab["checked"], wrong + tab["wrong"]
    ok = got["checked"] > 0 and all(v <= lim for v, lim in numbers.values())
    return {"correct": ok, "checked": checked, "wrong": wrong,
            "numbers": numbers}
