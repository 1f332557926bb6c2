"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Copied from ``repro.launch.roofline.PEAKS``. Source: Google Cloud
documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s in
bf16, 16 GB of HBM at 819 GB/s. A kind not in the table is an error.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"name": "TPU v5e", "flops_bf16": 197e12, "hbm_bw": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})")
    return PEAKS[device_kind]
