"""Published peak rates of one chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16, 16 GB
of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.  A device
kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float    # bf16 matrix FLOP/s
    hbm_bw: float   # HBM bytes/s
    hbm_bytes: int  # HBM capacity
    ici_bw: float   # chip-to-chip bytes/s, all links
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, hbm_bytes=16 * 10**9,
                         ici_bw=1600e9 / 8,
                         source='Google Cloud documentation, "TPU v5e"'),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
