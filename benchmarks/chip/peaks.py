"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` string JAX reports. A kind that is not in the table is an
error, never a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB HBM at 819 GB/s, 1,600 Gbit/s
inter-chip interconnect per chip.
"""
from __future__ import annotations

import dataclasses

SOURCE = "Google Cloud documentation, TPU v5e system architecture"


@dataclasses.dataclass(frozen=True)
class Peak:
    bf16_flops: float      # FLOP/s, dense bf16 matmul
    int8_ops: float        # OP/s
    hbm_bytes: float       # bytes of HBM
    hbm_bw: float          # bytes/s
    ici_bw: float          # bytes/s of inter-chip interconnect per chip
    source: str = SOURCE


_V5E = Peak(bf16_flops=197e12, int8_ops=393e12, hbm_bytes=16 * 2**30,
            hbm_bw=819e9, ici_bw=1600e9 / 8)

#: device_kind as JAX reports it -> peaks
TABLE = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peak(device_kind: str) -> Peak:
    try:
        return TABLE[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(TABLE)}") from None
