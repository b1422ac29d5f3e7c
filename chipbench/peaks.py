"""Published peaks per chip, keyed by JAX's `device_kind`.

TPU v5e ("TPU v5 lite" to JAX): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of
HBM at 819 GB/s (Google Cloud documentation, "TPU v5e"). A kind that is not
here is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
