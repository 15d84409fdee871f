"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

TPU v5e reports itself as "TPU v5 lite".  Source: Google Cloud
documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of
HBM at 819 GB/s.  A device that is not in the table is an error.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"ops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (have {sorted(PEAKS)})") from None
