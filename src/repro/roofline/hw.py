"""Per-chip hardware peaks, keyed by `jax.Device.device_kind` — the
roofline denominators.

A kind that is not in the table is an error, never a default: a roofline
share computed against another chip's peaks is a wrong number.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float      # FLOP/s, MXU bf16
    int8_ops: float        # OP/s, MXU int8
    hbm_bw: float          # bytes/s
    ici_bw: float          # bytes/s per inter-chip link


# TPU v5e per chip, from the Google Cloud documentation page "TPU v5e":
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, and
# 1,600 Gbit/s of inter-chip interconnect over four links (50 GB/s each).
V5E = "TPU v5 lite"

PEAKS = {
    V5E: Peaks(bf16_flops=197e12, int8_ops=393e12, hbm_bw=819e9,
               ici_bw=50e9),
}


def peaks(device_kind: str) -> Peaks:
    """The peaks of one chip of `device_kind` (as JAX reports it)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
