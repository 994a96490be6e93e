"""One ``<metric>.json`` per metric (its reader and the reader's arguments;
unit, layer and ``moves`` stand in BENCHMARK.json) and one ``<reader>.py``
per reader.  A reader is ``read(args, reading) -> float | None``: it takes
the metric from the job's values, the registry's window or the trace, and
returns None when there is nothing to read, so that the metric is left out
of the line.  ``benchmark/end_to_end/*.json`` use the same readers."""

from __future__ import annotations

import importlib
from dataclasses import dataclass
from typing import Any, Dict, Optional


@dataclass
class Reading:
    """What a reader may look at."""
    result: Any                      # harness.window.Result
    device: Dict[str, Any]           # platform, kind, count
    trace: Optional[Any] = None      # harness.xplane.Trace of the slice


def read(metric, reading: Reading) -> Optional[float]:
    reader = importlib.import_module(
        f"benchmark.layer_metrics.{metric.reader}")
    value = reader.read(metric.args, reading)
    return None if value is None else float(value)
