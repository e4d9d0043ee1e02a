"""Published peaks of each chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.  A kind that
is not in the table is an error, not a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it to "
                       f"bench/lib/peaks.py with its source") from None
