"""Production mesh definition (TPU v5e pods).

``make_production_mesh`` is a function (never a module-level constant) so
importing this module never touches jax device state.  The dry-run's
``main()`` sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512``
before the backend starts to get placeholder devices; tests and
benchmarks see 1 device.
"""
from __future__ import annotations

import jax

#: Published per-chip peaks keyed by ``jax.Device.device_kind``:
#: bf16 FLOP/s, HBM bytes/s, and ICI bytes/s per link.  TPU v5e, which
#: reports itself as "TPU v5 lite" (Google Cloud documentation, "TPU
#: v5e"): 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of interconnect
#: per chip over 4 links.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9,
                    "ici_bw": 1600e9 / 8 / 4},
}
#: the chip the production meshes below describe
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> dict:
    """Peaks of one chip; a device missing from ``PEAKS`` is an error,
    never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (have {sorted(PEAKS)})") from None


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_smoke_mesh(n_data: int = 2, n_model: int = 2,
                    n_pod: int = 0) -> jax.sharding.Mesh:
    """Small mesh for CPU tests (requires host-device override to >=4)."""
    if n_pod:
        return jax.make_mesh((n_pod, n_data, n_model),
                             ("pod", "data", "model"))
    return jax.make_mesh((n_data, n_model), ("data", "model"))
