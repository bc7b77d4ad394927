"""Published peaks of each chip, keyed by JAX's ``device_kind``.

A chip missing from this table is an error, never a default.

TPU v5e: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/
v5e): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"name": "tpu-v5e", "bf16_flops_per_s": 197e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def for_kind(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         f"chipbench/harness/peaks.py; known: "
                         f"{sorted(PEAKS)}") from None
