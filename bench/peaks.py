"""Published peaks of the chips the benchmark runs on, keyed by
``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s).  The float32 vector work of
the coadd scan runs far below the bf16 matrix peak, so a share of that peak
is a lower bound of the time the work needs, never an overstatement.
"""

from __future__ import annotations

_V5E = {
    "flops_per_s": 197e12,
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "source": "Google Cloud documentation, TPU v5e",
}

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown chip is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
