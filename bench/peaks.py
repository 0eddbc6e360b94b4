"""Published peaks of each accelerator the benchmark may run on.

Keyed by ``jax.Device.device_kind``.  A device that is not in the table
is an error: a share of a peak is never computed against a guess.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float       # FLOP/s
    hbm_bytes_per_s: float  # bytes/s
    hbm_bytes: float        # bytes of device memory
    source: str


_V5E = Peaks(
    bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
    source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
           "16 GB HBM at 819 GB/s per chip")

# JAX names a v5e chip "TPU v5 lite"
TABLE = {"TPU v5 lite": _V5E}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return TABLE[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/peaks.py "
                       f"with their source") from None
