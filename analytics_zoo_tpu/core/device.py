"""Published per-chip peak rates, keyed by ``device_kind``.

The one table behind every utilization figure (the estimator's
``train.mfu`` gauge and ``bench.py``'s MFU records).  A TPU whose kind is
not listed is an error, never a default: a utilization against an assumed
peak is a wrong number with a device metric's name.
"""

from __future__ import annotations

from typing import Optional

import jax

#: Peak dense bf16 FLOP/s per chip, matched as a lower-cased substring of
#: ``device_kind`` (first match wins, so "v5 lite" precedes "v5p").
#: Source: Google Cloud TPU documentation, system-architecture page of each
#: generation (v5e: 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s).
PEAK_BF16_FLOPS = (
    ("v5 lite", 197e12),   # v5e
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v6 lite", 918e12),   # Trillium / v6e
    ("v6e", 918e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def peak_bf16_flops(device: Optional[jax.Device] = None) -> Optional[float]:
    """Peak bf16 FLOP/s of ``device`` (default: the first device).  ``None``
    on a platform with no published peak (the CPU backend); raises on a TPU
    kind the table does not list."""
    dev = device if device is not None else jax.devices()[0]
    if dev.platform != "tpu":
        return None
    kind = dev.device_kind.lower()
    for key, peak in PEAK_BF16_FLOPS:
        if key in kind:
            return peak
    raise RuntimeError(
        f"unknown TPU device_kind {dev.device_kind!r}: add its published "
        f"peak bf16 FLOP/s to core/device.py PEAK_BF16_FLOPS rather than "
        f"reporting a utilization against an assumed peak")
