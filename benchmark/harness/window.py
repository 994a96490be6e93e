"""What a job needs around its measured window: the device it runs on, a
profiler slice inside the window, the bare host->device probe, and the
record it hands back to run.py."""

from __future__ import annotations

import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np


@dataclass
class Run:
    """One run of one cell, as run.py starts it."""
    cell: Any                 # manifest.Cell
    seed: int
    seconds: float
    trace: bool
    process_start: float      # time.perf_counter() at the top of run.py


@dataclass
class Result:
    """What a job hands back.  ``values`` are the job's own numbers by the
    job's own keys (``end_to_end/*.json`` and ``layer_metrics/*.json`` say
    which metric reads which); ``registry`` is the program's registry over
    the window (harness/registry.window); ``trace_dir`` is where the slice
    was written, when one was."""
    attempted: int
    failed: int
    problems: List[str]
    values: Dict[str, float]
    registry: Dict[str, Any] = field(default_factory=dict)
    window_s: float = 0.0
    trace_dir: Optional[str] = None


def device_info() -> Dict[str, Any]:
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


class MemoryWatch:
    """The most device memory a chip held at once, as far as the runtime
    says.  ``peak_bytes_in_use`` counts live buffers only; what a running
    program takes for its temporaries the TPU runtime books as
    ``bytes_reserved`` (PR 21 read 1.76 GB after BERT-base training against
    the compiler's 3.54 GiB for the step: the difference is this).  So the
    two are sampled together, ten times a second from start to ``peak()``,
    and the peak is the largest sum seen, or the runtime's own
    ``peak_bytes_in_use`` where that is larger.  Every reading is a lower
    bound of the true peak.  0 where the backend reports nothing (CPU)."""

    def __init__(self, every: float = 0.1):
        self._every = every
        self._most = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-memory-watch")

    def start(self) -> "MemoryWatch":
        self._thread.start()
        return self

    def _sample(self) -> None:
        import jax
        for d in jax.local_devices():
            st = d.memory_stats() or {}
            self._most = max(
                self._most, st.get("peak_bytes_in_use", 0),
                st.get("bytes_in_use", 0) + st.get("bytes_reserved", 0))

    def _run(self) -> None:
        while not self._stop.wait(self._every):
            self._sample()

    def peak(self) -> int:
        """Stops the sampling and returns the peak, in bytes."""
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join()
        self._sample()
        return int(self._most)


def probe_link(shape, dtype, seed: int, readings: int = 5) -> float:
    """MB/s of a bare ``device_put`` + ``block_until_ready`` of one host
    array of the cell's own batch shape: the median of ``readings``, after
    one copy that pays the first-use set-up (chip_smoke.py's link phase)."""
    import jax
    batch = np.random.default_rng(seed).integers(
        0, 127, shape).astype(dtype)
    jax.block_until_ready(jax.device_put(batch))
    rates = []
    for _ in range(readings):
        t0 = time.perf_counter()
        jax.block_until_ready(jax.device_put(batch))
        rates.append(batch.nbytes / (time.perf_counter() - t0) / 1e6)
    return float(np.median(rates))


def annotate(name: str):
    """A host span in the profiler's own trace, under the benchmark's
    prefix; costs a branch when no trace is running."""
    import jax
    return jax.profiler.TraceAnnotation("bench:" + name)


class TraceSlice:
    """A ``jax.profiler`` trace of ``seconds`` of the window, started
    ``after`` seconds from ``start()`` on a thread of its own (the job's
    main thread may be inside one long ``fit()``).  Python-level tracing is
    off: the slice holds device ops, JAX's own host events and the
    benchmark's annotations.  Even so one second of BERT-base training on a
    v5e is 460,000 device events and 48 MB, and ``stop_trace`` takes about a
    second a megabyte to write it (PR 22): keep slices near one second.

    A slice with no ``after`` (a rehearsal's) is open when ``start()``
    returns, so the window that follows begins inside it however long the
    window turns out to be."""

    def __init__(self, after: float, seconds: float):
        self.after, self.seconds = after, seconds
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-trace-slice")
        self.error: Optional[BaseException] = None

    def start(self) -> "TraceSlice":
        if self.after <= 0:
            self._open()
        self._thread.start()
        return self

    def _open(self) -> None:
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)

    def _run(self) -> None:
        import jax
        try:
            if self.after > 0:
                if self._stop.wait(self.after):
                    return
                self._open()
            try:
                self._stop.wait(self.seconds)
            finally:
                jax.profiler.stop_trace()
        except BaseException as e:  # noqa: BLE001 — re-raised by finish()
            self.error = e

    def finish(self) -> str:
        """Stops the slice if it still runs, waits for the trace to be
        written, and returns its directory."""
        self._stop.set()
        t0 = time.perf_counter()
        self._thread.join()
        print(f"benchmark: trace slice written in "
              f"{time.perf_counter() - t0:.1f} s", file=sys.stderr)
        if self.error is not None:
            raise self.error
        return self.dir
