"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s in bfloat16, 393 TOP/s in int8, 16 GB of HBM2 at
819 GB/s. JAX names a v5e ``TPU v5 lite``. A kind that is not here is an
error, never a default.
"""
from __future__ import annotations

SOURCE = "Google Cloud documentation, TPU v5e system architecture"

_V5E = {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9, "source": SOURCE}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def least_seconds(flops: float, nbytes: float, device_kind: str) -> float:
    """The least time the chip can take for ``flops`` and ``nbytes``: the
    larger of the compute bound and the memory bound."""
    p = peaks(device_kind)
    return max(flops / p["bf16_flops_per_s"], nbytes / p["hbm_bytes_per_s"])
