"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py
oracles, plus ops.py wrappers vs the model layer's expectations.  The
compiled kernels are checked against a described TPU in
``test_tpu_compile.py``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rfr_inference import (rfr_forest_apply, sweep_limits,
                                         tree_sum)
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.ssd_scan import ssd_scan


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind,window", [("global", 0), ("local", 32),
                                         ("chunked", 32)])
@pytest.mark.parametrize("S", [64, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(kind, window, S, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    BH, D = 4, 32
    q = _rand(k1, (BH, S, D), dtype)
    k = _rand(k2, (BH, S, D), dtype)
    v = _rand(k3, (BH, S, D), dtype)
    out = flash_attention(q, k, v, causal=True, kind=kind, window=window,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, kind=kind,
                                   window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("softcap", [0.0, 20.0])
def test_flash_attention_softcap(softcap):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(k1, (2, 64, 16), jnp.float32) * 4
    k = _rand(k2, (2, 64, 16), jnp.float32) * 4
    v = _rand(k3, (2, 64, 16), jnp.float32)
    out = flash_attention(q, k, v, causal=True, softcap=softcap,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


def test_flash_attention_noncausal():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (_rand(kk, (2, 96, 16), jnp.float32) for kk in (k1, k2, k3))
    out = flash_attention(q, k, v, causal=False, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("backend,interpret", [("cpu", True),
                                               ("tpu", False),
                                               ("gpu", None)])
def test_pallas_interpret_follows_backend(monkeypatch, backend, interpret):
    """One decision: compiled on a TPU, interpreted on the CPU backend,
    and no silent interpreter anywhere else."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="no Pallas path"):
            ops.pallas_interpret()
    else:
        assert ops.pallas_interpret() is interpret


def test_attention_op_gqa_expansion():
    """ops.attention_op accepts (B, S, H, D) GQA layouts."""
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 3)
    B, S, Hq, Hkv, D = 2, 64, 4, 2, 16
    q = _rand(ks[0], (B, S, Hq, D), jnp.float32)
    k = _rand(ks[1], (B, S, Hkv, D), jnp.float32)
    v = _rand(ks[2], (B, S, Hkv, D), jnp.float32)
    out_pl = ops.attention_op(q, k, v, use_pallas=True)
    out_ref = ops.attention_op(q, k, v, use_pallas=False)
    assert out_pl.shape == (B, S, Hq, D)
    np.testing.assert_allclose(np.asarray(out_pl), np.asarray(out_ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# RG-LRU scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("S,W", [(32, 64), (128, 128), (100, 96)])
def test_rglru_scan_matches_ref(S, W):
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    B = 2
    a = jax.nn.sigmoid(_rand(ks[0], (B, S, W), jnp.float32))  # decay in (0,1)
    b = _rand(ks[1], (B, S, W), jnp.float32)
    got = rglru_scan(a, b, interpret=True)
    want = ref.rglru_scan_ref(a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_rglru_scan_with_initial_state():
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    B, S, W = 2, 48, 64
    a = jax.nn.sigmoid(_rand(ks[0], (B, S, W), jnp.float32))
    b = _rand(ks[1], (B, S, W), jnp.float32)
    h0 = _rand(ks[2], (B, W), jnp.float32)
    got = rglru_scan(a, b, h0, interpret=True)
    want = ref.rglru_scan_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_rglru_scan_is_associative_consistent():
    """Splitting a sequence and chaining states == one long scan."""
    ks = jax.random.split(jax.random.PRNGKey(6), 2)
    B, S, W = 1, 64, 32
    a = jax.nn.sigmoid(_rand(ks[0], (B, S, W), jnp.float32))
    b = _rand(ks[1], (B, S, W), jnp.float32)
    full = ref.rglru_scan_ref(a, b)
    h_mid = full[:, S // 2 - 1]
    second = ref.rglru_scan_ref(a[:, S // 2:], b[:, S // 2:], h_mid)
    np.testing.assert_allclose(np.asarray(second),
                               np.asarray(full[:, S // 2:]),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# Mamba-2 SSD scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("S,chunk", [(64, 16), (128, 32), (96, 32)])
def test_ssd_scan_matches_ref(S, chunk):
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    B, H, P, N = 2, 3, 8, 16
    x = _rand(ks[0], (B, H, S, P), jnp.float32)
    dA = -jax.nn.softplus(_rand(ks[1], (B, H, S), jnp.float32))  # negative
    dt = jax.nn.softplus(_rand(ks[2], (B, H, S), jnp.float32))
    Bm = _rand(ks[3], (B, H, S, N), jnp.float32)
    Cm = _rand(ks[4], (B, H, S, N), jnp.float32)
    y, h = ssd_scan(x, dA, dt, Bm, Cm, chunk=chunk, interpret=True)
    y_ref, h_ref = ref.ssd_scan_ref(x, dA, dt, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               atol=2e-4, rtol=2e-4)


def test_ssd_scan_state_chaining():
    ks = jax.random.split(jax.random.PRNGKey(8), 5)
    B, H, S, P, N = 1, 2, 64, 4, 8
    x = _rand(ks[0], (B, H, S, P), jnp.float32)
    dA = -jax.nn.softplus(_rand(ks[1], (B, H, S), jnp.float32))
    dt = jax.nn.softplus(_rand(ks[2], (B, H, S), jnp.float32))
    Bm = _rand(ks[3], (B, H, S, N), jnp.float32)
    Cm = _rand(ks[4], (B, H, S, N), jnp.float32)
    y_full, h_full = ref.ssd_scan_ref(x, dA, dt, Bm, Cm)
    half = S // 2
    y1, h1 = ssd_scan(x[:, :, :half], dA[:, :, :half], dt[:, :, :half],
                      Bm[:, :, :half], Cm[:, :, :half], chunk=16,
                      interpret=True)
    y2, h2 = ssd_scan(x[:, :, half:], dA[:, :, half:], dt[:, :, half:],
                      Bm[:, :, half:], Cm[:, :, half:], h0=h1, chunk=16,
                      interpret=True)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y_full[:, :, half:]),
                               atol=3e-4, rtol=3e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full),
                               atol=3e-4, rtol=3e-4)


# ---------------------------------------------------------------------------
# RFR forest inference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("N,T,depth,F", [(32, 4, 3, 8), (100, 16, 6, 31)])
def test_rfr_forest_matches_ref(N, T, depth, F):
    rng = np.random.default_rng(0)
    NN = (1 << depth) - 1
    x = rng.standard_normal((N, F)).astype(np.float32)
    feat = rng.integers(0, F, (T, NN)).astype(np.int32)
    thr = rng.standard_normal((T, NN)).astype(np.float32)
    leaf = rng.standard_normal((T, 1 << depth)).astype(np.float32)
    got = rfr_forest_apply(jnp.asarray(x), jnp.asarray(feat),
                           jnp.asarray(thr), jnp.asarray(leaf),
                           interpret=True)
    want = ref.rfr_forest_ref(x, feat, thr, leaf)
    np.testing.assert_allclose(np.asarray(got) / np.float32(T),
                               np.asarray(want), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("T", [1, 5, 8, 24, 32, 100, 200])
def test_tree_sum_is_numpy_mean_order(T):
    """The kernels' tree sums follow numpy's pairwise f32 order, so sum
    / T is bit-identical to the numpy engine's ``vals.mean(axis=1)``
    (a sequential sum differs in about half of all rows)."""
    rng = np.random.default_rng(T)
    vals = (rng.standard_normal((4000, T))
            * rng.uniform(0.1, 10.0, (4000, 1))).astype(np.float32)
    got = tree_sum(
        lambda t: jax.lax.dynamic_index_in_dim(jnp.asarray(vals), t, 1,
                                               keepdims=False),
        T, jnp.zeros((4000,), jnp.float32))
    np.testing.assert_array_equal(np.asarray(got) / np.float32(T),
                                  vals.mean(axis=1))


def test_rfr_forest_apply_bit_matches_numpy_engine():
    """Compare-and-select descent plus pairwise tree sums: the Pallas
    engine's predictions equal the numpy engine's exactly, across a
    multi-block grid and +inf (always-left) thresholds."""
    from repro.core.predictor import ForestArrays, RandomForestRegressor
    rng = np.random.default_rng(11)
    T, depth, F, N = 24, 6, 31, 2500
    NN = (1 << depth) - 1
    m = RandomForestRegressor(n_trees=T, max_depth=depth)
    thr = rng.standard_normal((T, NN)).astype(np.float32)
    thr[:, NN // 2:] = np.inf
    m.arrays = ForestArrays(rng.integers(0, F, (T, NN)).astype(np.int32),
                            thr, rng.standard_normal(
                                (T, 1 << depth)).astype(np.float32))
    x = rng.standard_normal((N, F)).astype(np.float32)
    np.testing.assert_array_equal(m.predict(x, engine="pallas"),
                                  m.predict(x, engine="numpy"))
    np.testing.assert_array_equal(m.predict(x, engine="jax"),
                                  m.predict(x, engine="numpy"))


def test_rfr_forest_apply_empty_batch():
    """N == 0 (a drain with nothing to solve) used to divide by zero:
    bn = min(block_n, 0) = 0 and grid = (N // bn,)."""
    rng = np.random.default_rng(3)
    T, depth, F = 4, 3, 8
    NN = (1 << depth) - 1
    feat = rng.integers(0, F, (T, NN)).astype(np.int32)
    thr = rng.standard_normal((T, NN)).astype(np.float32)
    leaf = rng.standard_normal((T, 1 << depth)).astype(np.float32)
    out = rfr_forest_apply(jnp.zeros((0, F), jnp.float32),
                           jnp.asarray(feat), jnp.asarray(thr),
                           jnp.asarray(leaf), interpret=True)
    assert out.shape == (0,)
    assert out.dtype == jnp.float32


@pytest.mark.parametrize("N,block_n", [(3, 256), (100, 32), (64, 64)])
def test_rfr_forest_apply_partial_blocks(N, block_n):
    """Batches smaller than block_n and non-multiples of it (padded
    grid) must match the oracle on the real rows."""
    rng = np.random.default_rng(4)
    T, depth, F = 6, 4, 10
    NN = (1 << depth) - 1
    x = rng.standard_normal((N, F)).astype(np.float32)
    feat = rng.integers(0, F, (T, NN)).astype(np.int32)
    thr = rng.standard_normal((T, NN)).astype(np.float32)
    leaf = rng.standard_normal((T, 1 << depth)).astype(np.float32)
    got = rfr_forest_apply(jnp.asarray(x), jnp.asarray(feat),
                           jnp.asarray(thr), jnp.asarray(leaf),
                           block_n=block_n, interpret=True)
    want = ref.rfr_forest_ref(x, feat, thr, leaf)
    assert got.shape == (N,)
    np.testing.assert_allclose(np.asarray(got) / np.float32(T),
                               np.asarray(want), atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# RFR fused capacity m-sweep
# ---------------------------------------------------------------------------


def _sweep_case(seed, S=7, M=6, R=3, T=8, depth=4, F=9):
    """A padded scenario tensor exercising both padding encodings:
    +inf bounds (R padding rows, always pass) and -inf bounds (m beyond
    a scenario's own m_max, always fail)."""
    rng = np.random.default_rng(seed)
    NN = (1 << depth) - 1
    x = rng.standard_normal((S, M, R, F)).astype(np.float32)
    feat = rng.integers(0, F, (T, NN)).astype(np.int32)
    thr = rng.standard_normal((T, NN)).astype(np.float32)
    leaf = rng.standard_normal((T, 1 << depth)).astype(np.float32)
    # finite bounds in the prediction range so pass/fail actually varies
    bounds = rng.uniform(-0.6, 0.6, (S, M, R)).astype(np.float32)
    for s in range(S):
        r_real = int(rng.integers(1, R + 1))
        m_real = int(rng.integers(0, M + 1))
        bounds[s, :, r_real:] = np.inf      # padded rows pass
        bounds[s, m_real:, :] = -np.inf     # past this scenario's m_max
    return x, bounds, feat, thr, leaf


@pytest.mark.parametrize("use_pallas", [True, False])
@pytest.mark.parametrize("log_target", [False, True])
def test_rfr_capacity_sweep_matches_ref(use_pallas, log_target):
    x, bounds, feat, thr, leaf = _sweep_case(5)
    limits = sweep_limits(bounds, feat.shape[0], log_target)
    got = ops.rfr_sweep_op(jnp.asarray(x), jnp.asarray(limits),
                           jnp.asarray(feat), jnp.asarray(thr),
                           jnp.asarray(leaf), use_pallas=use_pallas)
    want = ref.rfr_capacity_sweep_ref(x, bounds, feat, thr, leaf,
                                      log_target=log_target)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_rfr_capacity_sweep_block_partitioning():
    """Scenario-block size must not change results (padded scenarios
    pass trivially and are sliced off).  Blocks are whole 128-lane
    columns: 300 scenarios run as grids of 3, 2, 1 and 1 steps."""
    from repro.kernels.rfr_inference import rfr_capacity_sweep
    x, bounds, feat, thr, leaf = _sweep_case(6, S=300)
    want = ref.rfr_capacity_sweep_ref(x, bounds, feat, thr, leaf)
    limits = jnp.asarray(sweep_limits(bounds, feat.shape[0], False))
    for bs in (128, 256, 384, 1024):
        got = rfr_capacity_sweep(jnp.asarray(x), limits,
                                 jnp.asarray(feat), jnp.asarray(thr),
                                 jnp.asarray(leaf), block_s=bs,
                                 interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("log_target", [False, True])
def test_sweep_limits_reproduce_host_verdicts(log_target):
    """A tree sum passes its limit exactly when the host's prediction
    (numpy's f32 division, then exp) meets the float64 bound — checked
    at the limit, one ulp above it, and on random sums."""
    rng = np.random.default_rng(3)
    T = 24
    bounds = np.concatenate([rng.uniform(0.05, 40.0, 200),
                             [np.inf, -np.inf]])
    if not log_target:
        bounds[:200] = rng.uniform(-3.0, 3.0, 200)
    lim = sweep_limits(bounds, T, log_target)
    assert lim[-2] == np.inf and lim[-1] == -np.inf
    fin = lim[:-2]
    above = np.nextafter(fin, np.float32(np.inf))
    s = np.concatenate([fin, above,
                        rng.uniform(-80.0, 80.0, 200).astype(np.float32)])
    b = np.concatenate([bounds[:-2]] * 3)
    p = s / np.float32(T)
    if log_target:
        p = np.exp(p)
    host = p.astype(np.float64) <= b
    np.testing.assert_array_equal(s <= np.concatenate([fin] * 3), host)
    assert host[:200].all() and not host[200:400].any()


@pytest.mark.parametrize("use_pallas", [True, False])
def test_rfr_capacity_sweep_degenerate_shapes(use_pallas):
    rng = np.random.default_rng(7)
    T, depth, F = 4, 3, 6
    NN = (1 << depth) - 1
    feat = jnp.asarray(rng.integers(0, F, (T, NN)).astype(np.int32))
    thr = jnp.asarray(rng.standard_normal((T, NN)).astype(np.float32))
    leaf = jnp.asarray(rng.standard_normal((T, 1 << depth)).astype(
        np.float32))
    for S, M, R in [(0, 4, 2), (3, 0, 2), (3, 4, 0)]:
        out = ops.rfr_sweep_op(jnp.zeros((S, M, R, F), jnp.float32),
                               jnp.zeros((S, M, R), jnp.float32),
                               feat, thr, leaf, use_pallas=use_pallas)
        assert out.shape == (S,)
        assert out.dtype == jnp.int32
        assert not np.asarray(out).any()


def test_rfr_op_consistent_with_trained_model():
    """The Pallas engine and the numpy engine of the actual predictor
    agree on real trained trees."""
    from repro.core.predictor import RandomForestRegressor
    rng = np.random.default_rng(1)
    X = rng.standard_normal((400, 10)).astype(np.float32)
    y = (X[:, 0] * 2 + np.sin(X[:, 1]) + 0.1 *
         rng.standard_normal(400)).astype(np.float64)
    m = RandomForestRegressor(n_trees=8, max_depth=5, seed=1)
    m.fit(X, y)
    p_np = m.predict(X[:64], engine="numpy")
    p_pl = m.predict(X[:64], engine="pallas")
    np.testing.assert_allclose(p_np, p_pl, atol=1e-4, rtol=1e-4)
