"""Published peaks of one chip, keyed by JAX's ``device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e" -- 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB of HBM at 819 GB/s.  Float32 work is held to the
bf16 FLOP peak (the chip publishes no separate f32 matrix peak).  A device
that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def device_peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; raises for any other."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; have {sorted(PEAKS)}")
    return PEAKS[device_kind]
