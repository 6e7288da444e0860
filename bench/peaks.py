"""Published per-chip peaks, keyed by `jax.Device.device_kind`.

Copied from `repro.launch.roofline.DEVICE_PEAKS`.  A kind that is not
listed has no peak: `peaks()` raises, and no share of a peak is reported
against a guess.
"""

from __future__ import annotations

from typing import Dict

DEVICE_PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud TPU documentation, 'TPU v5e' system "
                  "architecture page (per chip: 197 TFLOP/s bf16, "
                  "819 GB/s HBM)",
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    entry = DEVICE_PEAKS.get(device_kind)
    if entry is None:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"known kinds: {sorted(DEVICE_PEAKS)}")
    return entry
