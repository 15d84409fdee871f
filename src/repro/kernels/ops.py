"""Jit'd public wrappers around the Pallas kernels.

Each op accepts model-native layouts, handles GQA head expansion /
transposes, and dispatches to the Pallas kernel (``use_pallas=True``) or
the jnp oracle.  Whether a kernel runs compiled or interpreted is decided
in one place, ``pallas_interpret``: compiled on a TPU, interpreted on the
CPU backend (where the test suite validates the kernels).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention
from .rfr_inference import rfr_capacity_sweep, rfr_forest_apply, tree_sum
from .rglru_scan import rglru_scan
from .ssd_scan import ssd_scan


def pallas_interpret() -> bool:
    """Pallas kernels run compiled on a TPU and interpreted on the CPU
    backend; no other backend has a Pallas path here.  Asked when an op
    is traced, never at import."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"no Pallas path for the {backend!r} backend")
    return backend == "cpu"


@partial(jax.jit, static_argnames=("causal", "kind", "window", "softcap",
                                   "use_pallas"))
def attention_op(q, k, v, *, causal=True, kind="global", window=0,
                 softcap=0.0, use_pallas=True):
    """q: (B, S, Hq, D); k, v: (B, S, Hkv, D) -> (B, S, Hq, D)."""
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    if Hkv != Hq:
        k = jnp.repeat(k, Hq // Hkv, axis=2)
        v = jnp.repeat(v, Hq // Hkv, axis=2)
    qm = q.transpose(0, 2, 1, 3).reshape(B * Hq, S, D)
    km = k.transpose(0, 2, 1, 3).reshape(B * Hq, S, D)
    vm = v.transpose(0, 2, 1, 3).reshape(B * Hq, S, D)
    fn = (partial(flash_attention, interpret=pallas_interpret())
          if use_pallas
          else ref.flash_attention_ref)
    out = fn(qm, km, vm, causal=causal, kind=kind, window=window,
             softcap=softcap)
    return out.reshape(B, Hq, S, D).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("use_pallas",))
def rglru_op(a, b, h0=None, *, use_pallas=True):
    """a, b: (B, S, W) fp32 -> h (B, S, W)."""
    if use_pallas:
        return rglru_scan(a, b, h0, interpret=pallas_interpret())
    return ref.rglru_scan_ref(a, b, h0)


@partial(jax.jit, static_argnames=("chunk", "use_pallas"))
def ssd_op(x, dt, A, Bm, Cm, h0=None, *, chunk=256, use_pallas=True):
    """Model layout: x (B,S,H,P); dt (B,S,H) post-softplus; A (H,) negative;
    Bm, Cm: (B,S,H,N).  Returns (y (B,S,H,P), h_final (B,H,P,N))."""
    xt = x.transpose(0, 2, 1, 3)
    dtt = dt.transpose(0, 2, 1)
    dA = dtt * A[None, :, None]
    Bt = Bm.transpose(0, 2, 1, 3)
    Ct = Cm.transpose(0, 2, 1, 3)
    if use_pallas:
        y, h = ssd_scan(xt, dA, dtt, Bt, Ct, h0, chunk=chunk,
                        interpret=pallas_interpret())
    else:
        y, h = ref.ssd_scan_ref(xt, dA, dtt, Bt, Ct, h0)
    return y.transpose(0, 2, 1, 3), h


def _forest_gather(x, feat, thr, leaf):
    """Pure-jnp level-synchronous forest descent (the predictor's
    ``engine="jax"``): vectorized gathers, traceable under jit — the
    numpy ``ref.rfr_forest_ref`` oracle cannot run inside a traced
    function.  x (N, F) -> (N,) f32 tree sums, added in the kernels'
    (numpy's) pairwise order."""
    N = x.shape[0]
    T, NN = feat.shape
    depth = (NN + 1).bit_length() - 1
    t_ids = jnp.arange(T)[None, :]                       # (1, T)
    idx = jnp.zeros((N, T), jnp.int32)
    rows = jnp.arange(N)[:, None]                        # (N, 1)
    for _ in range(depth):
        f = feat[t_ids, idx]                             # (N, T)
        t = thr[t_ids, idx]
        go_right = (x[rows, f] >= t).astype(jnp.int32)
        idx = 2 * idx + 1 + go_right
    vals = leaf[t_ids, idx - NN].astype(jnp.float32)
    return tree_sum(
        lambda i: jax.lax.dynamic_index_in_dim(vals, i, 1, keepdims=False),
        T, jnp.zeros((N,), jnp.float32))


@partial(jax.jit, static_argnames=("use_pallas",))
def rfr_op(x, feat, thr, leaf, *, use_pallas=True):
    """Forest inference: x (N, F) -> (N,) tree sums (the prediction is
    the sum over T, divided on the host)."""
    if use_pallas:
        return rfr_forest_apply(x, feat, thr, leaf,
                                interpret=pallas_interpret())
    return _forest_gather(x, feat, thr, leaf)


@partial(jax.jit, static_argnames=("use_pallas",))
def rfr_sweep_op(x, limits, feat, thr, leaf, *, use_pallas=True):
    """Fused capacity m-sweep: the device-resident drain's one pass.

    x (S, M, R, F) padded scenario feature rows; limits (S, M, R)
    tree-sum limits (``rfr_inference.sweep_limits``) with +inf = padded
    row (always passes) and -inf = m beyond a scenario's m_max (always
    fails).  Returns (S,) int32 max-admissible m.  ``use_pallas=False``
    runs the same sweep as jnp gathers + reductions (the
    ``engine="jax"`` device path and the kernel's traced oracle)."""
    if use_pallas:
        return rfr_capacity_sweep(x, limits, feat, thr, leaf,
                                  interpret=pallas_interpret())
    S, M, R, F = x.shape
    if S == 0 or M == 0 or R == 0:
        return jnp.zeros((S,), jnp.int32)
    sums = _forest_gather(x.reshape(S * M * R, F), feat, thr, leaf)
    ok = (sums <= limits.reshape(-1)).reshape(S, M, R)
    m_ok = jnp.all(ok, axis=2)                           # (S, M)
    first_fail = jnp.where(m_ok, M, jnp.arange(M, dtype=jnp.int32))
    return jnp.min(first_fail, axis=1).astype(jnp.int32)

