"""Published peaks of each chip, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bfloat16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of inter-chip
interconnect.  A kind that is not in the table is an error, never a
default.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "ici_bytes_per_s": 200e9},
}


def peak(device_kind: str, what: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}")
    return PEAKS[device_kind][what]
