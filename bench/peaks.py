"""Peaks of one chip, keyed by `jax.Device.device_kind`: the denominators of
every roofline and utilization share. A kind that is not in the table is an
error, never a default.

TPU v5e ("TPU v5 lite"), from the Google Cloud documentation page "TPU
v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bw": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
