"""The window between two ``MetricsRegistry.snapshot()`` dicts.

The arithmetic is a copy of ``core/metrics.py``'s ``snapshot_delta`` and
``_bucket_percentile`` (PERF.md, Open questions), kept here so that a change
to the program's own helpers cannot move a benchmark number.  Counters
subtract; histograms subtract bucket by bucket, and a quantile is a linear
interpolation inside the winning bucket (edges 0.1 ms .. 10 s, so a p50 is
good to its bucket, not to the microsecond).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence


def bucket_quantile(edges: Sequence[float], counts: Sequence[int],
                    q: float) -> Optional[float]:
    total = sum(counts)
    if total == 0:
        return None
    target, seen = q * total, 0
    for i, c in enumerate(counts):
        if c > 0 and seen + c >= target:
            lo = edges[i - 1] if i > 0 else 0.0
            hi = edges[i] if i < len(edges) else edges[-1]
            return lo + (target - seen) / c * (hi - lo)
        seen += c
    return float(edges[-1])


def window(before: Dict[str, Any], after: Dict[str, Any]) -> Dict[str, Any]:
    """What changed between the snapshots: counters as numbers, histograms
    as ``{"count", "sum", "edges", "counts"}``; gauges are left out (a
    point-in-time value has no difference)."""
    out: Dict[str, Any] = {}
    for series, val in after.items():
        old = before.get(series)
        if isinstance(val, dict) and "bucket_counts" in val:
            edges = list(val["bucket_edges"])
            counts = list(val["bucket_counts"])
            count, total = val["count"], val["sum"]
            if isinstance(old, dict) and \
                    list(old.get("bucket_edges", ())) == edges:
                counts = [c - p for c, p in zip(counts,
                                                old["bucket_counts"])]
                count -= old["count"]
                total -= old["sum"]
            out[series] = {"count": count, "sum": total, "edges": edges,
                           "counts": counts}
        elif isinstance(val, (int, float)) and not isinstance(val, bool):
            out[series] = val - (old if isinstance(old, (int, float))
                                 else 0)
    return out
