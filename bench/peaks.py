"""Published peaks of the accelerators the benchmark runs on.

Keyed by ``jax.Device.device_kind``. A kind that is not in the table is
an error: a roofline share against a guessed peak would be a guess.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
        "16 GB HBM at 819 GB/s per chip",
    },
}


def peaks(device_kind: str) -> dict:
    """The peak table entry of ``device_kind``; raises ``KeyError`` for a
    device the table does not know."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
