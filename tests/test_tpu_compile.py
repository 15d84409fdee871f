"""The RFR kernels compiled by the TPU compiler for a described v5e chip.

Nothing runs: the topology is described, not attached, so these tests
show that Mosaic accepts both kernels at drain widths (tiling, SMEM and
VMEM limits, no vector gathers) and that the compiled program holds the
kernel.  Results and times need the chip (``chip_smoke.py``).  The
topology is described inside a fixture, never at import: only the worker
that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.rfr_inference import rfr_capacity_sweep, rfr_forest_apply

DEPTH = 8
NN = (1 << DEPTH) - 1


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's programs cannot be read back from the
    # persistent cache; keep these compiles out of it
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _forest(one_chip, T):
    return (jax.ShapeDtypeStruct((T, NN), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((T, NN), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((T, NN + 1), jnp.float32,
                                 sharding=one_chip))


@pytest.mark.parametrize("T,F,N", [(24, 31, 4096), (32, 33, 100)])
def test_forest_apply_compiles_for_v5e(one_chip, T, F, N):
    x = jax.ShapeDtypeStruct((N, F), jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda *a: rfr_forest_apply(*a, interpret=False)).lower(
        x, *_forest(one_chip, T)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("T,F,S,R", [(24, 31, 3, 6), (32, 33, 1024, 8),
                                     (24, 33, 1025, 6),
                                     # the widest launch a 24-function
                                     # platform warms (R bucket 32)
                                     (24, 33, 128, 32)])
def test_capacity_sweep_compiles_for_v5e(one_chip, T, F, S, R):
    M = 16
    x = jax.ShapeDtypeStruct((S, M, R, F), jnp.float32, sharding=one_chip)
    lim = jax.ShapeDtypeStruct((S, M, R), jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda *a: rfr_capacity_sweep(*a, interpret=False)).lower(
        x, lim, *_forest(one_chip, T)).compile()
    assert "tpu_custom_call" in compiled.as_text()
