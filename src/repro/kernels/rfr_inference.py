"""Random-Forest-Regression batched inference — Pallas TPU kernels.

This is the paper's scheduling-latency hot spot (Table 2: model inference
~20 ms dominates cold starts once container init is <10 ms; Jiagu needs
~1 ms).  The forest is flattened to dense complete-tree arrays that sit
in SMEM for the whole launch (32 trees x depth 8 ~= 100 KB), so a
capacity-solve batch of inputs is scored in one kernel launch with zero
HBM re-reads of the model:

    feat (T, 2^D - 1) int32   split feature per internal node
    thr  (T, 2^D - 1) f32     split threshold
    leaf (T, 2^D)     f32     leaf values

Rows sit on the lanes: the inputs arrive transposed, feature-major, so
each feature of a block is one lane-dense tile.  A tree is descended
level by level with compare/select only — at level l each of the 2^l
nodes reads its split feature (a dynamic leading-axis load of that
feature's tile) and threshold (SMEM scalars), and the rows whose current
node it is take its branch bit.  Mosaic has no general vector gather;
selects are exact, so the branch taken is the numpy engine's.

Tree values are summed in numpy's pairwise order (``tree_sum``), which is
the order of the numpy engine's ``vals.mean(axis=1)``: with IEEE f32
adds the device's tree sums are bit-identical to the host's.

Two kernels share the descent:

  * ``rfr_forest_apply`` — plain batched inference, (N, F) -> (N,)
    tree sums (the prediction is the sum over T).
  * ``rfr_capacity_sweep`` — the fused capacity m-sweep.  Input is the
    padded scenario tensor (S, M, R, F): S capacity scenarios, M swept
    concurrencies, R feature rows per concurrency (target + colocated
    neighbors).  One pass descends every row, compares each row's tree
    sum against its limit (``sweep_limits``), reduces (all rows pass)
    over R and (longest passing prefix) over M, and returns the max
    admissible m per scenario as (S,) int32 — no host round-trip per
    chunk.  Padding is encoded in the limits: +inf rows always pass
    (R padding), -inf rows always fail (m beyond a scenario's own m_max,
    capping its capacity there).

The un-jitted numpy training half lives in ``repro.core.predictor``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: f32 sublanes per vreg: a forest-apply block holds whole (8, 128) tiles
SUBLANES = 8
#: scoped-VMEM ceiling for the sweep (v5e has 128 MiB per core)
_VMEM_CAP = 100 * 1024 * 1024


def _depth(feat) -> int:
    nn = feat.shape[1]
    depth = (nn + 1).bit_length() - 1
    if (1 << depth) - 1 != nn:
        raise ValueError(f"complete tree layout required, got {nn} "
                         "internal nodes")
    return depth


def tree_sum(value_of, n_trees: int, zeros):
    """Sum ``value_of(t)`` over trees t < n_trees in numpy's pairwise
    order for f32 reductions: fewer than 8 terms add in sequence; up to
    128 terms add into 8 interleaved accumulators combined as
    ((r0+r1)+(r2+r3))+((r4+r5)+(r6+r7)), then the n % 8 tail in
    sequence; longer runs split in halves rounded down to a multiple of
    8.  ``value_of`` takes a traced int32 tree index; ``zeros`` fixes the
    result's shape and dtype."""

    def seq(start, count, acc):
        return jax.lax.fori_loop(start, start + count,
                                 lambda t, a: a + value_of(t), acc)

    def block(start, n):
        if n < 8:
            return seq(start, n, zeros)
        if n <= 128:
            rounds = n // 8

            def one_round(q, acc):
                return tuple(a + value_of(start + 8 * q + j)
                             for j, a in enumerate(acc))

            r = jax.lax.fori_loop(0, rounds, one_round, (zeros,) * 8)
            res = ((r[0] + r[1]) + (r[2] + r[3])) + \
                ((r[4] + r[5]) + (r[6] + r[7]))
            return seq(start + 8 * rounds, n % 8, res)
        half = n // 2
        half -= half % 8
        return block(start, half) + block(start + half, n - half)

    return block(0, n_trees)


def _forest_sums(x_ref, feat_ref, thr_ref, leaf_ref, *, shape,
                 n_trees: int, depth: int):
    """Tree sums over one block: ``x_ref`` is feature-major (F, *shape);
    feat/thr/leaf are the SMEM forest tables."""

    def descend(t):
        idx = jnp.zeros(shape, jnp.int32)       # node within the level
        for level in range(depth):
            first = (1 << level) - 1

            def node(k, go):
                n = first + k
                right = (x_ref[feat_ref[t, n]] >= thr_ref[t, n])
                return jnp.where(idx == k, right.astype(jnp.int32), go)

            go = jax.lax.fori_loop(0, 1 << level, node,
                                   jnp.zeros(shape, jnp.int32))
            idx = 2 * idx + go

        def pick(k, val):
            return jnp.where(idx == k, leaf_ref[t, k], val)

        return jax.lax.fori_loop(0, 1 << depth, pick,
                                 jnp.zeros(shape, jnp.float32))

    return tree_sum(descend, n_trees, jnp.zeros(shape, jnp.float32))


def _smem():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _apply_kernel(x_ref, feat_ref, thr_ref, leaf_ref, out_ref, *,
                  n_trees: int, depth: int):
    out_ref[...] = _forest_sums(x_ref, feat_ref, thr_ref, leaf_ref,
                                shape=out_ref.shape, n_trees=n_trees,
                                depth=depth)


def rfr_forest_apply(x, feat, thr, leaf, *, block_n: int = 1024,
                     interpret: bool = False):
    """x: (N, F) f32; feat/thr: (T, 2^D-1); leaf: (T, 2^D).
    Returns the tree sums (N,) f32; the prediction is the sum over T,
    divided on the host (XLA turns a division by a constant into a
    reciprocal multiply, which rounds differently from numpy's mean).
    ``block_n`` rows per grid step are rounded up to whole (8, 128)
    tiles; a batch that fits one block runs as one.  Handles N == 0
    (empty drain) and ragged N (zero-padded rows, sliced off)."""
    N, F = x.shape
    T = feat.shape[0]
    depth = _depth(feat)
    if N == 0:
        return jnp.zeros((0,), jnp.float32)
    cols = -(-N // LANES)                       # 128-row lane columns
    tile = SUBLANES * LANES
    rb = -(-max(block_n, 1) // tile) * SUBLANES
    if cols <= rb:
        rb = cols                               # one block: full dims
    cols_p = -(-cols // rb) * rb
    xt = jnp.pad(x.astype(jnp.float32), [(0, cols_p * LANES - N), (0, 0)])
    xt = xt.T.reshape(F, cols_p, LANES)

    kernel = functools.partial(_apply_kernel, n_trees=T, depth=depth)
    out = pl.pallas_call(
        kernel,
        grid=(cols_p // rb,),
        in_specs=[pl.BlockSpec((F, rb, LANES), lambda i: (0, i, 0)),
                  _smem(), _smem(), _smem()],
        out_specs=pl.BlockSpec((rb, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((cols_p, LANES), jnp.float32),
        interpret=interpret,
    )(xt, feat, thr, leaf)
    return out.reshape(-1)[:N]


def _sweep_kernel(x_ref, lim_ref, feat_ref, thr_ref, leaf_ref, out_ref, *,
                  n_trees: int, depth: int):
    R, M, L = lim_ref.shape
    sums = _forest_sums(x_ref, feat_ref, thr_ref, leaf_ref, shape=(R, M, L),
                        n_trees=n_trees, depth=depth)
    ok = (sums <= lim_ref[...]).astype(jnp.int32)
    # all R rows of a concurrency must meet QoS; capacity is the longest
    # passing prefix of m = 1..M, i.e. the index of the first failing m
    # (a failing m caps every later m: the host sweep's early exit)
    m_ok = jnp.min(ok, axis=0)                              # (M, L)
    m = jax.lax.broadcasted_iota(jnp.int32, (M, L), 0)
    out_ref[...] = jnp.min(jnp.where(m_ok == 1, M, m), axis=0,
                           keepdims=True)


def rfr_capacity_sweep(x, limits, feat, thr, leaf, *, block_s: int = LANES,
                       interpret: bool = False):
    """Fused capacity m-sweep: one Pallas pass over the whole padded
    scenario tensor.

    x: (S, M, R, F) f32 feature rows; limits: (S, M, R) f32 tree-sum
    limits from ``sweep_limits`` (+inf = padded row, always passes;
    -inf = m beyond the scenario's m_max, always fails); feat/thr/leaf:
    the flattened forest.  ``block_s`` scenarios per grid step are
    rounded up to whole 128-lane columns.  Returns (S,) int32 — the max
    admissible concurrency per scenario.
    """
    S, M, R, F = x.shape
    T = feat.shape[0]
    depth = _depth(feat)
    if S == 0 or M == 0 or R == 0:
        return jnp.zeros((S,), jnp.int32)
    bs = -(-max(block_s, 1) // LANES) * LANES
    Sp = -(-S // bs) * bs
    # scenario-minor layout: every (feature, row, m) is a lane-dense run
    # of scenarios; padded scenarios pass trivially and are sliced off
    xt = jnp.pad(x.astype(jnp.float32),
                 [(0, Sp - S), (0, 0), (0, 0), (0, 0)]).transpose(3, 2, 1, 0)
    lt = jnp.pad(limits.astype(jnp.float32), [(0, Sp - S), (0, 0), (0, 0)],
                 constant_values=jnp.inf).transpose(2, 1, 0)
    block_bytes = 4 * bs * R * M * (F + 1)
    vmem = min(_VMEM_CAP, 2 * block_bytes + 32 * 1024 * 1024)

    kernel = functools.partial(_sweep_kernel, n_trees=T, depth=depth)
    out = pl.pallas_call(
        kernel,
        grid=(Sp // bs,),
        in_specs=[pl.BlockSpec((F, R, M, bs), lambda i: (0, 0, 0, i)),
                  pl.BlockSpec((R, M, bs), lambda i: (0, 0, i)),
                  _smem(), _smem(), _smem()],
        out_specs=pl.BlockSpec((1, bs), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Sp), jnp.int32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        interpret=interpret,
    )(xt, lt, feat, thr, leaf)
    return out[0, :S]


# ---------------------------------------------------------------------------
# Host side: QoS bounds -> tree-sum limits
# ---------------------------------------------------------------------------

_F32_MAX_KEY = int(np.array(np.finfo(np.float32).max,
                            np.float32).view(np.int32))


def _from_key(k):
    """Ordered int64 key -> f32 (keys grow with the float's value)."""
    k = np.asarray(k, np.int64)
    bits = np.where(k < 0, (-k) | 0x80000000, k).astype(np.uint32)
    return bits.view(np.float32)


def sweep_limits(bounds, n_trees: int, log_target: bool):
    """Per-row tree-sum limits for ``rfr_capacity_sweep``.

    The host drain predicts p = f32(s / T) from the pairwise tree sum s
    (then f32 ``np.exp`` with ``log_target``) and passes a row iff
    p <= bound, in float64.  The limit is the largest f32 s that passes,
    found by bisection over the ordered f32 values with numpy's own
    division and exp, so the kernel compares sums and needs neither.
    Its verdicts equal the host's wherever the prediction grows with s;
    numpy's f32 exp drops by one ulp at a few thousand adjacent inputs,
    where a row within one ulp of its bound can differ.  +inf bounds give
    +inf limits (always pass), -inf bounds -inf (always fail).
    """
    b = np.asarray(bounds, np.float64)
    u, inv = np.unique(b.reshape(-1), return_inverse=True)
    lo = np.full(u.shape, -_F32_MAX_KEY - 1, np.int64)   # passes
    hi = np.full(u.shape, _F32_MAX_KEY + 1, np.int64)    # fails
    T = np.float32(n_trees)
    with np.errstate(over="ignore"):
        while True:
            open_ = hi - lo > 1
            if not open_.any():
                break
            mid = np.clip((lo + hi) // 2, -_F32_MAX_KEY, _F32_MAX_KEY)
            p = _from_key(mid) / T
            if log_target:
                p = np.exp(p)
            ok = p.astype(np.float64) <= u
            lo = np.where(open_ & ok, mid, lo)
            hi = np.where(open_ & ~ok, mid, hi)
    lim = np.where(lo < -_F32_MAX_KEY, -np.inf,
                   np.where(lo >= _F32_MAX_KEY, np.inf,
                            _from_key(np.clip(lo, -_F32_MAX_KEY,
                                              _F32_MAX_KEY))))
    return lim[inv].reshape(b.shape).astype(np.float32)
