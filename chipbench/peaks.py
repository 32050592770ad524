"""Published peaks of each chip the benchmark runs on, keyed by JAX's
``device_kind``. A kind that is not here is an error, never a default.

TPU v5e (``TPU v5 lite``): Google Cloud documentation, "TPU v5e" system
architecture page: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at
819 GB/s per chip. (Same numbers as the program's
``core/roofline.DEVICE_PEAKS``, copied here so that no PR that changes the
program can move the yardstick.)
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def for_kind(kind: str) -> Dict[str, float]:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r} "
                       f"(known: {sorted(PEAKS)})")
    return PEAKS[kind]
