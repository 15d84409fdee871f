"""Dual-staged scaling state machine: release timing, logical cold
starts, keep-alive eviction, on-demand migration (paper §5, Fig 10)."""
import copy
import random
import zlib

import pytest

from repro.core import (Autoscaler, Cluster, GroundTruth, JiaguScheduler,
                        PerfPredictor, ProfileStore, QoSStore,
                        ScalingConfig, generate_dataset,
                        synthetic_functions)
from repro.core.cluster import CapEntry
from repro.core.events import EventHub, Observer
from repro.core.interference import NodeResources
from repro.telemetry.spans import SpanTracer


@pytest.fixture(scope="module")
def world():
    specs = synthetic_functions(3, seed=5)
    gt = GroundTruth(seed=0)
    store = ProfileStore(seed=0)
    qos = QoSStore(store, gt)
    pred = PerfPredictor(n_trees=12, max_depth=7, seed=0)
    X, y = generate_dataset(specs, gt, store, qos, 500, seed=1)
    pred.add_dataset(X, y)
    return specs, gt, store, qos, pred


def _mk(world, release_s=45.0, keepalive_s=60.0, dual=True, migrate=True):
    specs, gt, store, qos, pred = world
    cluster = Cluster(specs)
    sched = JiaguScheduler(cluster, store, qos, pred, m_max=12)
    aut = Autoscaler(cluster, sched, ScalingConfig(
        release_s=release_s, keepalive_s=keepalive_s, dual_staged=dual,
        migrate=migrate))
    return cluster, sched, aut


def _fn(world):
    return sorted(world[0])[0]


def _sat_rps(world, fn, n):
    return world[0][fn].saturated_rps * n * 0.99


def test_dual_staged_timeline(world):
    """Fig 10: load drop -> release after release_s (instances cached, not
    evicted) -> eviction only after keepalive_s."""
    cluster, sched, aut = _mk(world, release_s=10, keepalive_s=30)
    fn = _fn(world)
    for t in range(5):
        aut.tick(float(t), {fn: _sat_rps(world, fn, 4)})
        sched.on_tick(float(t) + 0.5)
    assert cluster.sat_count(fn) == 4
    # drop to 2-instances load
    t_drop = 5.0
    for i in range(9):
        aut.tick(t_drop + i, {fn: _sat_rps(world, fn, 2)})
    assert cluster.sat_count(fn) == 4          # release_s not reached
    assert cluster.cached_count(fn) == 0
    aut.tick(t_drop + 10.0, {fn: _sat_rps(world, fn, 2)})
    assert cluster.sat_count(fn) == 2          # released, not evicted
    assert cluster.cached_count(fn) == 2
    assert aut.metrics.releases == 2
    assert aut.metrics.evictions == 0
    # keep-alive expiry: ttl = keepalive - release = 20 s after release
    for i in range(25):
        aut.tick(t_drop + 11 + i, {fn: _sat_rps(world, fn, 2)})
    assert cluster.cached_count(fn) == 0       # finally evicted
    assert aut.metrics.evictions == 2


def test_logical_cold_start_on_load_rise(world):
    """A rise while instances are cached re-routes (<1 ms) instead of
    creating instances."""
    cluster, sched, aut = _mk(world, release_s=5, keepalive_s=120)
    fn = _fn(world)
    aut.tick(0.0, {fn: _sat_rps(world, fn, 4)})
    sched.on_tick(0.5)
    for i in range(7):
        aut.tick(1.0 + i, {fn: _sat_rps(world, fn, 2)})
    assert cluster.cached_count(fn) == 2
    real_before = aut.metrics.real_cold_starts
    aut.tick(10.0, {fn: _sat_rps(world, fn, 4)})
    assert cluster.sat_count(fn) == 4
    assert aut.metrics.logical_cold_starts >= 2
    assert aut.metrics.real_cold_starts == real_before
    # logical cold start cost is the re-route constant, not init_ms
    assert min(aut.metrics.cold_start_ms[-2:]) < 1.0


def test_traditional_keepalive_evicts_directly(world):
    cluster, sched, aut = _mk(world, keepalive_s=10, dual=False)
    fn = _fn(world)
    aut.tick(0.0, {fn: _sat_rps(world, fn, 3)})
    sched.on_tick(0.5)
    for i in range(12):
        aut.tick(1.0 + i, {fn: _sat_rps(world, fn, 1)})
    assert cluster.cached_count(fn) == 0       # never cached
    assert cluster.sat_count(fn) == 1
    assert aut.metrics.evictions == 2
    assert aut.metrics.releases == 0


def test_scale_up_from_zero_and_down_to_zero(world):
    cluster, sched, aut = _mk(world, release_s=3, keepalive_s=8)
    fn = _fn(world)
    aut.tick(0.0, {fn: 0.0})
    assert cluster.sat_count(fn) == 0
    aut.tick(1.0, {fn: _sat_rps(world, fn, 2)})
    assert cluster.sat_count(fn) == 2
    for i in range(15):
        aut.tick(2.0 + i, {fn: 0.0})
    assert cluster.sat_count(fn) == 0
    assert cluster.cached_count(fn) == 0
    assert len(cluster.nodes) == 0             # empty servers returned


def test_migration_frees_blocked_cached_instances(world):
    """When a node fills up so cached instances can't re-saturate, they
    migrate to a node with capacity headroom (paper Fig 14-b)."""
    specs, gt, store, qos, pred = world
    cluster, sched, aut = _mk(world, release_s=2, keepalive_s=500)
    fns = sorted(specs)
    fn, other = fns[0], fns[1]
    # two nodes running fn
    aut.tick(0.0, {fn: _sat_rps(world, fn, 6)})
    sched.on_tick(0.5)
    # drop fn so some instances get cached
    for i in range(5):
        aut.tick(1.0 + i, {fn: _sat_rps(world, fn, 2)})
    assert cluster.cached_count(fn) >= 1
    # squeeze capacity on the cached node by filling it with `other`
    cached_nodes = [n for n in cluster.nodes.values()
                    if fn in n.funcs and n.funcs[fn].n_cached > 0]
    assert cached_nodes
    node = cached_nodes[0]
    node.deploy(other, 6)
    from repro.core.capacity import update_capacity_table
    update_capacity_table(pred, store, qos, specs, node, m_max=12)
    # force a small capacity so n_sat + n_cached > capacity
    node.table[fn].capacity = max(node.funcs[fn].n_sat, 1)
    migrated_before = aut.metrics.migrations
    aut.tick(10.0, {fn: _sat_rps(world, fn, 2)})
    # either migrated away, or no target existed (then blocked counted)
    assert (aut.metrics.migrations > migrated_before
            or node.funcs[fn].n_cached == 0
            or aut.metrics.blocked_logical >= 0)


# -- migration targets: the per-pass index against the full scan -----------


class _ColocCapacity:
    """A node's table entry where it has one, else an answer drawn from
    the node's shape and counts, None for about a third of them: an
    answer that changes only with the node's counts, as
    ``CapacityProvider`` asks."""

    def __init__(self, salt: int):
        self.salt = salt

    def node_capacity(self, node, fn):
        entry = node.table.get(fn)
        if entry is not None:
            return entry.capacity
        counts = sorted((g, s.n_sat, s.n_cached)
                        for g, s in node.funcs.items() if s.total > 0)
        h = zlib.crc32(repr((self.salt, fn, node.res.mem_mb,
                             counts)).encode())
        return None if h % 3 == 0 else h % 14


class _NoCapacity:
    """A table-free scheduler's provider."""

    def node_capacity(self, node, fn):
        return None


class _Touched:
    """Stands in for the scheduler: a migration pass only reports the
    nodes it changed, source then target."""

    def __init__(self):
        self.ids = []

    def notify_change(self, node, now):
        self.ids.append(node.id)


class _Migrations(Observer):
    def __init__(self):
        self.moves = []

    def on_scale(self, now, fn, event, count):
        if event == "migrate":
            self.moves.append((fn, count))


def _fleet(seed):
    """A fleet of mixed node shapes with saturated and cached instances,
    some all-cached nodes, and capacity tables on most (node, fn)."""
    rng = random.Random(seed)
    specs = synthetic_functions(4, seed=seed)
    shapes = [NodeResources(cpu_mcores=48_000.0, mem_mb=8192.0),
              NodeResources(cpu_mcores=96_000.0, mem_mb=16384.0),
              NodeResources(cpu_mcores=32_000.0, mem_mb=6144.0)]
    cluster = Cluster(specs, res_pool=shapes)
    for _ in range(rng.randint(6, 30)):
        node = cluster.add_node()
        all_cached = rng.random() < 0.25
        for fn in rng.sample(sorted(specs), rng.randint(1, 3)):
            sat = 0 if all_cached else rng.randint(0, 4)
            cached = rng.randint(0 if sat else 1, 3)
            if sat:
                node.deploy(fn, sat)
            if cached:
                node.add_cached(fn, cached)
            if rng.random() < 0.7:
                node.table[fn] = CapEntry(
                    capacity=rng.randint(max(sat - 1, 0), sat + cached + 8))
    return cluster


def _counts(cluster):
    return {nid: {fn: (s.n_sat, s.n_cached) for fn, s in n.funcs.items()}
            for nid, n in cluster.nodes.items()}


def _room(cluster, capacity, node, fn):
    cap = capacity.node_capacity(node, fn)
    if cap is None:
        return -1
    st = node.funcs[fn]
    return min(cap - st.n_sat - st.n_cached, cluster.mem_headroom(node, fn))


def _full_scan_moves(cluster, capacity, seen):
    """The migration pass with the search the index replaced: each search
    sorts every node hosting fn by descending n_sat and reads each
    candidate's capacity until one fits.  Returns the moves and the
    searches whose k exceeds every candidate's room; ``seen`` counts the
    cases the fleets must cover."""
    moves, filled, gone, beyond = [], {}, set(), 0
    for node in cluster.nodes_with_cached():
        all_cached = all(s.n_sat == 0 for s in node.funcs.values()) \
            and node.n_instances() > 0
        seen["all_cached"] += all_cached
        for fn, st in list(node.funcs.items()):
            if st.n_cached == 0:
                continue
            cap = capacity.node_capacity(node, fn)
            if all_cached:
                k = st.n_cached
            elif cap is not None:
                if st.n_sat + st.n_cached - cap <= 0:
                    continue
                k = min(st.n_sat + st.n_cached - cap, st.n_cached)
            else:
                continue
            seen["searched_after_leaving"] += fn in gone
            cands = sorted(cluster.nodes_with(fn),
                           key=lambda n: -n.funcs[fn].n_sat)
            beyond += k > max(
                (_room(cluster, capacity, c, fn) for c in cands),
                default=-1)
            target = None
            for cand in cands:
                if cand.id == node.id:
                    continue
                c = capacity.node_capacity(cand, fn)
                if c is None:
                    continue
                s = cand.funcs[fn]
                room = c - s.n_sat - s.n_cached
                if room < k:
                    seen["filled_earlier"] += filled.get((fn, cand.id),
                                                         0) >= k
                    continue
                if cluster.mem_headroom(cand, fn) < k:
                    seen["memory_bound"] += 1
                    continue
                target = cand
                filled[(fn, cand.id)] = room
                break
            if target is None:
                continue
            node.evict_cached(fn, k)
            target.add_cached(fn, k)
            if fn not in node.funcs:
                gone.add(fn)
            moves.append((fn, node.id, target.id, k))
    return moves, beyond


def test_migration_index_matches_the_full_scan():
    seen = dict.fromkeys(("all_cached", "searched_after_leaving",
                          "filled_earlier", "memory_bound"), 0)
    searched = moved = 0
    for seed in range(50):
        table_free = seed % 5 == 0
        capacity = _NoCapacity() if table_free else _ColocCapacity(seed)
        fleet = _fleet(seed)
        oracle = copy.deepcopy(fleet)
        want, beyond = _full_scan_moves(oracle, capacity, seen)

        touched, log = _Touched(), _Migrations()
        aut = Autoscaler(fleet, touched, ScalingConfig(), capacity=capacity,
                         events=EventHub([log]))
        aut.tracer = SpanTracer()
        aut._migrate(0.0)
        got = [(fn, touched.ids[2 * i], touched.ids[2 * i + 1], k)
               for i, (fn, k) in enumerate(log.moves)]
        assert got == want, seed
        assert _counts(fleet) == _counts(oracle), seed
        span, = aut.tracer.spans
        a = span.attrs
        assert a["moved"] == sum(k for *_, k in want)
        assert a["skipped"] == beyond
        assert a["skipped"] <= a["searches"]
        assert a["index_builds"] <= a["searches"]
        if table_free:
            assert want == [] and a["skipped"] == a["searches"]
        searched += a["searches"]
        moved += len(want)
    assert searched > 0 and moved > 0
    assert all(seen.values()), seen
