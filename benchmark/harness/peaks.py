"""Published per-chip peaks, keyed by ``device_kind``.

Copied from ``analytics_zoo_tpu/core/device.py`` (PERF.md, Open questions:
the original stays for the estimator's own gauge) so that no later PR can
move the yardstick.  Source: Google Cloud TPU documentation, the
system-architecture page of each generation (v5e: 197 TFLOP/s bf16, 16 GB of
HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip).  A kind that is not listed is an
error, never a default.
"""

from __future__ import annotations

from typing import Dict

#: matched as a lower-cased substring of ``device_kind``; first match wins,
#: so "v5 lite" stands before "v5p"
PEAKS = (
    ("v5 lite", {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                 "hbm_bytes": 16e9}),
    ("v5e", {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
             "hbm_bytes": 16e9}),
    ("v5p", {"bf16_flops": 459e12}),
    ("v6 lite", {"bf16_flops": 918e12}),
    ("v6e", {"bf16_flops": 918e12}),
    ("v4", {"bf16_flops": 275e12}),
    ("v3", {"bf16_flops": 123e12}),
    ("v2", {"bf16_flops": 45e12}),
)


def peaks(device_kind: str) -> Dict[str, float]:
    kind = device_kind.lower()
    for key, row in PEAKS:
        if key in kind:
            return row
    raise KeyError(
        f"no published peaks for device_kind {device_kind!r}: add the row, "
        "with its source, to benchmark/harness/peaks.py; a utilisation "
        "against an assumed peak is a wrong number under a device metric's "
        "name")
