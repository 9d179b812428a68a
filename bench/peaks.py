"""Peak rates of one chip, keyed by JAX's `device_kind`.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip. A kind
that is not in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    # JAX reports a v5e as "TPU v5 lite"
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
