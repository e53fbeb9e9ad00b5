"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A device that is not in the table is an error, never a
default.

Source for "TPU v5 lite": Google Cloud documentation, "TPU v5e"
(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB
of HBM at 819 GB/s.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,       # FLOP/s
        "ops_int8": 393e12,         # OP/s
        "hbm_bytes": 16e9,          # bytes
        "hbm_bytes_per_s": 819e9,   # bytes/s
    },
}


def peaks_for(device_kind: str, platform: str = "tpu") -> dict:
    """The peaks of ``device_kind``.  Off the accelerator (a rehearsal on
    the CPU) there are none, and an empty table is returned: no roofline
    share is ever read there."""
    if platform == "cpu":
        return {}
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add them "
                       f"with their source to bench/peaks.py") from None
