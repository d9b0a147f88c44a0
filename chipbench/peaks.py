"""Published per-chip peaks, the roofline denominators, keyed by JAX's
``device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect.  A kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bw": 819e9,
                    "ici_bw": 1600e9 / 8},
}


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; raises for an unknown kind."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to chipbench/peaks.py")
    return PEAKS[device_kind]


def least_seconds(flops: float, nbytes: float, kind: str,
                  chips: int = 1) -> tuple[float, str]:
    """The least time ``chips`` chips of ``kind`` could take for the work,
    and which bound sets it (``"flops"`` or ``"bytes"``)."""
    p = peaks(kind)
    t_flops = flops / (chips * p["bf16_flops"])
    t_bytes = nbytes / (chips * p["hbm_bw"])
    return (t_flops, "flops") if t_flops > t_bytes else (t_bytes, "bytes")
