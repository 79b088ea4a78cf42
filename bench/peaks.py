"""Published peak rates of each accelerator the benchmark may run on.

Keyed by ``jax.Device.device_kind``.  A device that is not in the table
is an error (``peak``), never a default: a roofline or MFU share against
a guessed peak means nothing.

TPU v5e, "TPU v5 lite": Google Cloud documentation, "TPU v5e" page
(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s int8,
16 GB of HBM at 819 GB/s.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"bench: no peak rates for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
