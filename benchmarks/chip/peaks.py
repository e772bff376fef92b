"""Peak rates of each chip the benchmark runs on, keyed by JAX's
``device_kind``.  A kind that is not here is an error, never a default.

TPU v5e: 197 TFLOP/s bf16 and 819 GB/s HBM per chip (Google Cloud
documentation, "TPU v5e").  JAX names the chip "TPU v5 lite".
"""
from __future__ import annotations

from typing import Dict

_V5E = {"flops": 197e12, "hbm_bytes_per_s": 819e9}
PEAKS: Dict[str, Dict[str, float]] = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
