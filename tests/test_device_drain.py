"""Device-resident capacity drain: the fused single-pass m-sweep
(``EngineConfig(drain="device")``) must be an exact drop-in for the
chunked host drain — bit-identical capacity tables AND matching
EngineStats accounting — for both device engines (jnp gather sweep and
the Pallas kernel), cold and warm, homogeneous and heterogeneous
(schema-v2 node shapes, per-query m_max)."""
import numpy as np
import pytest

from repro.core import (CapacityEngine, EngineConfig, GroundTruth,
                        NodeResources, PerfPredictor, ProfileStore,
                        QoSStore, generate_dataset, synthetic_functions)
from repro.core.cluster import Node

STAT_KEYS = ("solves", "unique_solves", "cache_hits", "coalesced_dupes",
             "rows_built", "predict_calls")


@pytest.fixture(scope="module")
def world():
    specs = synthetic_functions(5, seed=2)
    gt = GroundTruth(seed=0)
    store = ProfileStore(seed=0)
    qos = QoSStore(store, gt)
    pred = PerfPredictor(n_trees=12, max_depth=7, seed=0)
    X, y = generate_dataset(specs, gt, store, qos, 700, seed=1)
    pred.add_dataset(X, y)
    return specs, gt, store, qos, pred


SHAPES = [NodeResources(),
          NodeResources(cpu_mcores=96_000.0, mem_mb=262_144.0),
          NodeResources(cpu_mcores=24_000.0, mem_mb=65_536.0)]


def _hetero_nodes(specs, rng, n_nodes, n_patterns=6):
    """Nodes drawn from a pattern pool (so signature sharing occurs)
    across three node shapes (so schema-v2 signatures diverge)."""
    names = sorted(specs)
    patterns = []
    for _ in range(n_patterns):
        pat = {}
        for g in rng.choice(names, size=int(rng.integers(1, 4)),
                            replace=False):
            pat[g] = (int(rng.integers(1, 5)), int(rng.integers(0, 3)))
        patterns.append(pat)
    nodes = []
    for i in range(n_nodes):
        node = Node(SHAPES[i % len(SHAPES)])
        for g, (ns, nc) in patterns[rng.integers(n_patterns)].items():
            node.state(g).n_sat = ns
            node.state(g).n_cached = nc
        nodes.append(node)
    return nodes


def _tables(nodes):
    return [sorted((fn, e.capacity) for fn, e in node.table.items())
            for node in nodes]


def _clear(nodes):
    for node in nodes:
        node.table.clear()


@pytest.fixture()
def restore_engine(world):
    pred = world[4]
    prev = pred.engine
    yield pred
    pred.engine = prev


def _three_way(world, schema):
    """Host numpy oracle (full sweep, for stats parity) vs device drains."""
    specs, gt, store, qos, pred = world
    rng = np.random.default_rng(17)
    nodes = _hetero_nodes(specs, rng, n_nodes=64)
    m_max = 12

    host = CapacityEngine(pred, store, qos, specs,
                          EngineConfig(m_max=m_max, early_exit=False),
                          schema=schema)
    host.update_nodes(nodes, m_max=m_max)
    ref_tables = _tables(nodes)
    ref_stats = host.stats.snapshot()
    _clear(nodes)

    for engine in ("jax", "pallas"):
        dev = CapacityEngine(pred, store, qos, specs,
                             EngineConfig(m_max=m_max, drain="device"),
                             schema=schema)
        pred.engine = engine
        dev.update_nodes(nodes, m_max=m_max)
        assert _tables(nodes) == ref_tables, f"capacity mismatch ({engine})"
        dev_stats = dev.stats.snapshot()
        for k in STAT_KEYS:
            assert dev_stats[k] == ref_stats[k], \
                f"{k}: device={dev_stats[k]} host={ref_stats[k]} ({engine})"
        # warm drain: every signature resolves from the host cache
        rows_before = dev.stats.rows_built
        _clear(nodes)
        warm_rows = dev.update_nodes(nodes, m_max=m_max)
        assert _tables(nodes) == ref_tables
        assert warm_rows == 0
        assert dev.stats.rows_built == rows_before
        assert dev.stats.cache_hits == ref_stats["cache_hits"] \
            + ref_stats["solves"]
        _clear(nodes)
    return ref_tables


def test_three_way_drain_parity_v1(world, restore_engine):
    """numpy host oracle vs engine="jax" vs fused Pallas sweep: identical
    capacity tables and identical EngineStats on a seeded 64-node run."""
    _three_way(world, schema=1)


def test_three_way_drain_parity_v2_hetero_shapes(world, restore_engine):
    """Same, node-shape-aware: schema-v2 rows, margins, and shape-keyed
    signatures must survive the device packing unchanged."""
    _three_way(world, schema=2)


def test_device_drain_heterogeneous_m_max(world, restore_engine):
    """Per-query m_max exercises the -inf padding (m beyond a scenario's
    own sweep must fail) inside one packed tensor."""
    specs, gt, store, qos, pred = world
    names = sorted(specs)
    rng = np.random.default_rng(23)
    queries = []
    for i in range(20):
        coloc = {}
        for g in rng.choice(names, size=int(rng.integers(0, 4)),
                            replace=False):
            coloc[g] = (float(rng.integers(1, 5)), float(rng.integers(0, 3)))
        fn = names[int(rng.integers(len(names)))]
        queries.append((coloc, fn, int(rng.integers(1, 17)), None))

    host = CapacityEngine(pred, store, qos, specs,
                          EngineConfig(cache=False, early_exit=False))
    want = [c for c, _r in host.solve_many(list(queries))]
    pred.engine = "pallas"
    dev = CapacityEngine(pred, store, qos, specs,
                         EngineConfig(cache=False, drain="device"))
    got = [c for c, _r in dev.solve_many(list(queries))]
    assert got == want


def test_device_drain_rows_billed_to_first_occurrence(world, restore_engine):
    """Same contract as the host drain: duplicate signatures inside one
    batch bill rows once, cache hits bill zero."""
    specs, gt, store, qos, pred = world
    names = sorted(specs)
    pred.engine = "jax"
    dev = CapacityEngine(pred, store, qos, specs,
                         EngineConfig(m_max=8, drain="device"))
    coloc = {names[1]: (2.0, 1.0)}
    q = (dict(coloc), names[0], 8, None)
    (c1, r1), (c2, r2) = dev.solve_many([q, q])
    assert c1 == c2
    assert r1 > 0 and r2 == 0          # dupe coalesced, billed once
    (c3, r3), = dev.solve_many([q])
    assert c3 == c1 and r3 == 0        # warm: cache hit, zero rows
    assert dev.stats.coalesced_dupes == 1
    assert dev.stats.cache_hits == 1


def test_device_drain_runs_only_warmed_launch_shapes(world, restore_engine):
    """``warm_device`` compiles every launch shape a drain can take:
    afterwards a drain wider than one 128-scenario launch, with mixed
    m_max and neighbour counts, compiles nothing and equals the host
    drain."""
    from repro.kernels import ops
    specs, gt, store, qos, pred = world
    names = sorted(specs)
    rng = np.random.default_rng(5)
    queries = []
    for _ in range(300):
        coloc = {g: (float(rng.integers(1, 9)), float(rng.integers(0, 4)))
                 for g in rng.choice(names, size=int(rng.integers(0, 5)),
                                     replace=False)}
        queries.append((coloc, names[int(rng.integers(len(names)))],
                        int(rng.integers(1, 13)), None))
    host = CapacityEngine(pred, store, qos, specs,
                          EngineConfig(m_max=12, cache=False))
    want = [c for c, _r in host.solve_many(queries)]
    pred.engine = "jax"
    dev = CapacityEngine(pred, store, qos, specs,
                         EngineConfig(m_max=12, cache=False, drain="device"))
    assert dev.warm_device() == 3      # M buckets 4, 8, 16 x R bucket 8
    compiled = ops.rfr_sweep_op._cache_size()
    got = [c for c, _r in dev.solve_many(queries)]
    assert dev.stats.unique_solves > 128
    assert got == want
    assert ops.rfr_sweep_op._cache_size() == compiled


def test_device_drain_empty_and_trivial_batches(world, restore_engine):
    specs, gt, store, qos, pred = world
    pred.engine = "jax"
    dev = CapacityEngine(pred, store, qos, specs,
                         EngineConfig(drain="device"))
    assert dev.solve_many([]) == []
    names = sorted(specs)
    (cap, rows), = dev.solve_many([({}, names[0], 0, None)])
    assert cap == 0 and rows == 0      # m_max=0: nothing admissible


def test_device_drain_refuses_numpy_engine(world, restore_engine):
    """The device drain runs the jax or pallas engine; it never runs a
    jnp sweep under the name ``numpy``."""
    specs, gt, store, qos, pred = world
    pred.engine = "numpy"
    dev = CapacityEngine(pred, store, qos, specs,
                         EngineConfig(drain="device"))
    with pytest.raises(ValueError, match="numpy engine drains on the host"):
        dev.solve_many([({}, sorted(specs)[0], 4, None)])


def test_device_cache_eviction_compacts_slots(world, restore_engine):
    """The device drain's signature cache is bounded like the host
    drain's: oldest entries evicted, survivors still resolve warm."""
    specs, gt, store, qos, pred = world
    names = sorted(specs)
    pred.engine = "jax"
    dev = CapacityEngine(pred, store, qos, specs,
                         EngineConfig(m_max=6, drain="device",
                                      max_cache_entries=3))
    colocs = [{names[j]: (float(i + 1), 0.0)}
              for i in range(2) for j in range(1, 4)]
    expect = {}
    for i, coloc in enumerate(colocs):
        (cap, _r), = dev.solve_many([(dict(coloc), names[0], 6, None)])
        expect[i] = cap
    assert len(dev._cache) == 3
    # survivors (the 3 newest) still resolve warm with the right values
    for i in (3, 4, 5):
        (cap, rows), = dev.solve_many([(dict(colocs[i]), names[0], 6, None)])
        assert cap == expect[i] and rows == 0
    # evicted entries re-solve to the same capacity
    (cap, rows), = dev.solve_many([(dict(colocs[0]), names[0], 6, None)])
    assert cap == expect[0] and rows > 0


def test_device_drain_retrain_invalidates(world, restore_engine):
    """Epoch bump must clear the device drain's cache too — a
    post-retrain solve can never serve a pre-retrain capacity."""
    specs, gt, store, qos, pred = world
    p2 = PerfPredictor(n_trees=6, max_depth=6, seed=3)
    X, y = generate_dataset(specs, gt, store, qos, 300, seed=9)
    p2.add_dataset(X, y)
    p2.engine = "jax"
    dev = CapacityEngine(p2, store, qos, specs,
                         EngineConfig(m_max=8, drain="device"))
    names = sorted(specs)
    q = ({names[1]: (2.0, 0.0)}, names[0], 8, None)
    dev.solve_many([q])
    assert dev._cache
    p2.add_sample(X[0], float(y[0]), retrain=False)
    p2.retrain()
    (cap, rows), = dev.solve_many([q])
    assert rows > 0                    # re-solved, not served stale
    assert dev.stats.stale_epoch_hits == 0
    cap_ref, _ = CapacityEngine(p2, store, qos, specs,
                                EngineConfig(m_max=8)).capacity(
        {names[1]: (2.0, 0.0)}, names[0], 8)
    assert cap == cap_ref
