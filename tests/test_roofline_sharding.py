"""Roofline HLO parsing and sharding-rule units (no multi-device state
needed — specs are computed against a duck-typed mesh)."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.roofline import (collective_bytes, hlo_stats,
                                   model_flops, normalize_cost_analysis,
                                   roofline_terms)
from repro.distributed.sharding import (batch_pspecs, cache_pspec_for,
                                        dp_axes, pspec_for_param)
from repro.configs.base import SHAPE_BY_NAME, get_config


# ---------------------------------------------------------------------------
# HLO parsing
# ---------------------------------------------------------------------------


def test_hlo_stats_scales_loop_bodies():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y
    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    c = jax.jit(f).lower(x, w).compile()
    st = hlo_stats(c.as_text())
    one = 2 * 64 * 128 * 128
    assert abs(st["flops"] / one - 7.0) < 0.01
    # XLA's own cost_analysis counts the body once — our reason to parse
    # (list in older JAX, dict in newer — normalize either way)
    xla = normalize_cost_analysis(c.cost_analysis())
    assert abs(xla["flops"] / one - 1.0) < 0.01


def test_hlo_stats_counts_dot_contraction():
    def f(a, b):
        return a @ b
    a = jax.ShapeDtypeStruct((32, 100), jnp.float32)
    b = jax.ShapeDtypeStruct((100, 16), jnp.float32)
    c = jax.jit(f).lower(a, b).compile()
    st = hlo_stats(c.as_text())
    assert st["flops"] == 2 * 32 * 100 * 16


def test_collective_bytes_synthetic_hlo():
    hlo = """HloModule m

ENTRY %main.1 (p: f32[16,16]) -> f32[16,16] {
  %p = f32[16,16]{1,0} parameter(0)
  %ar = f32[16,16]{1,0} all-reduce(%p), replica_groups={}, to_apply=%add.1
  ROOT %ag = f32[32,16]{1,0} all-gather(%ar), dimensions={0}
}
"""
    coll = collective_bytes(hlo)
    assert coll["all-reduce"] == 2 * 16 * 16 * 4   # 2x convention
    assert coll["all-gather"] == 32 * 16 * 4


def test_roofline_terms_and_bottleneck():
    cost = {"flops": 197e12, "bytes accessed": 819e9 * 2}
    coll = {"all-reduce": 0.0, "all-gather": 0.0, "reduce-scatter": 0.0,
            "all-to-all": 0.0, "collective-permute": 0.0}
    t = roofline_terms(cost, coll, 256, device_kind="TPU v5 lite")
    assert abs(t["compute_s"] - 1.0) < 1e-6
    assert abs(t["memory_s"] - 2.0) < 1e-6
    assert t["bottleneck"] == "memory"


def test_roofline_terms_refuse_unknown_device():
    """A chip without published peaks is an error, not a v5e default."""
    with pytest.raises(ValueError, match="no published peaks"):
        roofline_terms({"flops": 1.0}, {}, 1, device_kind="cpu")


def test_model_flops_conventions():
    cfg = get_config("gemma2-2b")
    tr = model_flops(cfg, SHAPE_BY_NAME["train_4k"])
    pf = model_flops(cfg, SHAPE_BY_NAME["prefill_32k"])
    dc = model_flops(cfg, SHAPE_BY_NAME["decode_32k"])
    n = cfg.param_count()
    assert tr == 6.0 * n * 4096 * 256
    assert pf == 2.0 * n * 32768 * 32
    assert dc == 2.0 * n * 128
    moe = get_config("deepseek-v2-236b")
    assert model_flops(moe, SHAPE_BY_NAME["train_4k"]) < \
        6.0 * moe.param_count() * 4096 * 256  # active < total


# ---------------------------------------------------------------------------
# Sharding rules (duck-typed mesh: only .axis_names / .shape used)
# ---------------------------------------------------------------------------


def _mesh(shape_map):
    m = types.SimpleNamespace()
    m.axis_names = tuple(shape_map)
    m.shape = dict(shape_map)
    return m


MESH = _mesh({"data": 16, "model": 16})
MESH3 = _mesh({"pod": 2, "data": 16, "model": 16})


class _Leaf:
    def __init__(self, *shape):
        self.shape = shape


def _spec(path_str, *shape, mesh=MESH):
    path = tuple(types.SimpleNamespace(key=k) for k in path_str.split("/"))
    return pspec_for_param(path, _Leaf(*shape), mesh)


def test_param_rules_basic():
    assert _spec("embed", 256000, 2304) == P("model")
    assert _spec("head/0/attn/w_q", 8192, 64, 128) == \
        P("data", "model")                       # qwen: 64 heads divisible
    assert _spec("head/0/mlp/w_gate", 8192, 49152) == P("data", "model")
    assert _spec("head/0/mlp/w_down", 49152, 8192) == P("model", "data")
    assert _spec("head/0/pre_norm/scale", 8192) == P()


def test_param_rules_divisibility_fallback():
    # gemma2: 8 q heads / 4 kv heads on a 16-way model axis -> replicated
    assert _spec("head/0/attn/w_q", 2304, 8, 256) == P("data")
    assert _spec("head/0/attn/w_k", 2304, 4, 256) == P("data")
    # but its FFN still gets TP
    assert _spec("head/0/mlp/w_gate", 2304, 9216) == P("data", "model")


def test_param_rules_body_stacking():
    # body params carry a leading period axis that must stay unsharded
    assert _spec("body/p0/mlp/w_gate", 13, 2304, 9216) == \
        P(None, "data", "model")


def test_param_rules_moe_expert_parallel():
    assert _spec("body/p0/moe/w_gate", 30, 160, 5120, 1536) == \
        P(None, "model", "data")
    assert _spec("body/p0/moe/w_down", 30, 160, 1536, 5120) == \
        P(None, "model", None, "data")
    # trailing-None normalization: P(None) == replicated
    assert tuple(_spec("body/p0/moe/w_router", 30, 5120, 160)) in (
        (), (None,))


def test_param_rules_vocab_fallback():
    # hubert vocab=504 does not divide 16 -> replicated embedding
    assert _spec("embed", 504, 1280) == P()


def test_batch_pspecs_dp_and_decode():
    cfg = get_config("gemma2-2b")
    tr = batch_pspecs(cfg, SHAPE_BY_NAME["train_4k"], MESH3)
    assert tr["tokens"] == P(("pod", "data"), None)
    dec = batch_pspecs(cfg, SHAPE_BY_NAME["decode_32k"], MESH)
    assert dec["tokens"] == P("data")
    # long_500k: batch=1 unshardable
    lng = batch_pspecs(cfg, SHAPE_BY_NAME["long_500k"], MESH)
    assert lng["tokens"] == P(None)


def test_cache_pspec_sequence_parallel_fallback():
    cfg = get_config("gemma3-12b")
    path = (types.SimpleNamespace(key="head"), types.SimpleNamespace(key="0"),
            types.SimpleNamespace(key="k"))
    # decode_32k: batch 128 shards on data
    spec = cache_pspec_for(path, _Leaf(128, 32768, 8, 256), cfg, MESH, 128)
    assert spec[0] == "data"
    # long_500k: batch 1 -> shard the sequence dim instead
    spec = cache_pspec_for(path, _Leaf(1, 524288, 8, 256), cfg, MESH, 1)
    # kv heads (8) don't divide 16 -> replicated; trailing Nones trimmed
    assert tuple(spec)[:2] == (None, "data")
    assert all(x is None for x in tuple(spec)[2:])


def test_dp_axes():
    assert dp_axes(MESH) == ("data",)
    assert dp_axes(MESH3) == ("pod", "data")
