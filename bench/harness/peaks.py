"""Published peak rates of one chip, keyed by ``jax.Device.device_kind``.

Source of every row: Google Cloud documentation, "TPU v5e": 197 TFLOP/s
in bfloat16, 16 GB of HBM at 819 GB/s.  A kind that is not in the table
is an error, never a default.
"""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops: float        # bfloat16 FLOP/s
    hbm_bw: float       # HBM bytes/s


_PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return _PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(_PEAKS)}") from None
