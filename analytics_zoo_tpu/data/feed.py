"""DataFeed: host-side batching + prefetch feeding the device mesh.

Reference (SURVEY.md §2.2, §3.2): data reached compute through per-framework
feeders — BigDL MiniBatch from FeatureSet, ``tf.data`` per TFRunner actor,
torch DataLoader per TorchRunner — all downstream of a Spark→Ray object-store
hop.  TPU-native: each host process batches its local numpy data and places
it directly onto its devices, sharded along the mesh's batch axes
(``data``/``fsdp``).  XLA overlaps the host→HBM copy of batch N+1 with the
compute of batch N because ``jax.device_put`` dispatches asynchronously; we
additionally keep a one-batch lookahead so the host-side slicing/stacking is
off the critical path.

Static shapes: batches are fixed-size (remainder dropped or padded) so the
``jit``-compiled train step compiles exactly once.
"""

from __future__ import annotations

import queue as queue_mod
import threading
import time
from typing import Any, Dict, Iterator, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from analytics_zoo_tpu.core import metrics as _metrics_lib
from analytics_zoo_tpu.core import trace as _trace_lib
from analytics_zoo_tpu.core.faults import get_registry as _fault_registry
from .shards import XShards

BATCH_AXES = ("data", "fsdp")  # mesh axes a batch dim is sharded over


def batch_sharding(mesh: Mesh, leaf_rank: int = 1,
                   seq_dim_size: Optional[int] = None,
                   dim0_size: Optional[int] = None) -> NamedSharding:
    """NamedSharding that shards dim 0 over the mesh's batch axes.

    ``seq_dim_size``: pass the leaf's dim-1 size to ALSO shard dim 1 over the
    mesh's ``seq`` axis (sequence/context parallelism) — applied only to
    feature ('x') leaves whose dim 1 divides the axis; labels and
    non-divisible shapes stay batch-sharded only.

    ``dim0_size``: pass the leaf's GLOBAL dim-0 size so a batch that does
    not divide the batch axes falls back to replicated placement (small
    inference batches must work on any mesh) instead of erroring.  The
    fallback is only legal single-process: with multiple processes each
    host holds different rows, and a "replicated" assembly would silently
    disagree across hosts — there we raise instead."""
    present = tuple(a for a in BATCH_AXES if a in mesh.axis_names)
    dim0 = present if present else None
    if dim0 is not None and dim0_size is not None:
        axis_size = int(np.prod([mesh.shape[a] for a in present]))
        if dim0_size % axis_size != 0:
            if jax.process_count() > 1:
                raise ValueError(
                    f"global batch dim {dim0_size} does not divide the "
                    f"mesh batch axes (size {axis_size}); pick a batch "
                    "size divisible by the data/fsdp axes in multihost "
                    "runs (no replicated fallback across processes)")
            dim0 = None
    seq_ok = (seq_dim_size is not None and leaf_rank >= 2
              and "seq" in mesh.axis_names and mesh.shape["seq"] > 1
              and seq_dim_size % mesh.shape["seq"] == 0)
    if seq_ok:
        spec = P(dim0, "seq", *([None] * (leaf_rank - 2)))
    else:
        spec = P(dim0, *([None] * (leaf_rank - 1)))
    return NamedSharding(mesh, spec)


def batch_axis_size(mesh: Mesh) -> int:
    size = 1
    for a in BATCH_AXES:
        if a in mesh.axis_names:
            size *= mesh.shape[a]
    return size


def shard_batch(batch: Any, mesh: Mesh) -> Any:
    """Place a host-local pytree of numpy arrays onto the mesh.

    Single-process: ``device_put`` splits the global batch across devices.
    Multi-process: each process passes its *local* slice and
    ``make_array_from_process_local_data`` assembles the global logical array
    (the SPMD contract: global batch = concat of per-host batches).
    """
    multi = jax.process_count() > 1

    def place(leaf: np.ndarray, is_feature: bool) -> jax.Array:
        leaf = np.asarray(leaf)
        seq_size = leaf.shape[1] if (is_feature and leaf.ndim >= 2) else None
        # dim0_size must be the GLOBAL batch: each process contributes an
        # equal local slice, so global = local * process_count
        dim0 = (leaf.shape[0] * jax.process_count() if multi
                else leaf.shape[0]) if leaf.ndim else None
        sharding = batch_sharding(mesh, max(leaf.ndim, 1),
                                  seq_dim_size=seq_size,
                                  dim0_size=dim0)
        if multi:
            return jax.make_array_from_process_local_data(sharding, leaf)
        return jax.device_put(leaf, sharding)

    if isinstance(batch, dict):
        # seq-axis sharding applies to features only, never labels
        return {k: jax.tree_util.tree_map(
                    lambda l: place(l, is_feature=(k == "x")), v)
                for k, v in batch.items()}
    return jax.tree_util.tree_map(lambda l: place(l, True), batch)


class EpochEnd:
    """What a multi-epoch iterator (``FeedBase.epochs``) yields after the
    last batch of epoch ``epoch``: the consumer tells epochs apart by it,
    not by ``StopIteration``."""

    __slots__ = ("epoch",)

    def __init__(self, epoch: int):
        self.epoch = epoch

    def __repr__(self) -> str:
        return f"EpochEnd({self.epoch})"


class EpochsIterator:
    """What ``FeedBase.epochs`` returns: an iterator over a generator
    (``_gen``) of batches and ``EpochEnd`` markers that can be closed and
    asked whether its next batch is decoded already."""

    _gen: Any  # the subclass's generator

    def __iter__(self) -> "EpochsIterator":
        return self

    def __next__(self):
        return next(self._gen)

    def next_is_ready(self) -> bool:
        return False

    def close(self) -> None:
        self._gen.close()


class EpochChain(EpochsIterator):
    """``FeedBase.epochs``' default: one ``epoch()`` after another, an
    ``EpochEnd`` behind each.  Nothing of epoch k+1 exists before epoch
    k's last batch has been handed out, so nothing is ever ready ahead
    (a ``PrefetchIterator`` over the chain still runs it ahead of the
    consumer by its depth)."""

    def __init__(self, open_epoch, first: int, last: int):
        self._gen = self._run(open_epoch, first, last)

    @staticmethod
    def _run(open_epoch, first: int, last: int):
        for e in range(first, last):
            yield from open_epoch(e)  # closing the chain closes the epoch
            yield EpochEnd(e)


class FeedBase:
    """Shared feed contract: global-vs-local batch math, epoch step count,
    and the per-epoch shuffle index.  ``batch_size`` is the **global** batch
    (reference Estimator semantics: pyzoo/zoo/orca/learn/pytorch/
    pytorch_ray_estimator.py divided it across workers); each host
    contributes batch_size / process_count rows."""

    def __init__(self, num_samples: int, batch_size: int, shuffle: bool,
                 seed: int, drop_remainder: bool):
        self._n = num_samples
        self.global_batch = batch_size
        self._local_batch = max(1, batch_size // max(1, jax.process_count()))
        self.shuffle = shuffle
        self.seed = seed
        self.drop_remainder = drop_remainder

    @property
    def num_rows(self) -> int:
        return self._n

    def steps_per_epoch(self) -> int:
        if self.drop_remainder:
            return self._n // self._local_batch
        return -(-self._n // self._local_batch)

    def _epoch_index(self, epoch_idx: int) -> np.ndarray:
        """Row order for one epoch; also validates it yields >= 1 batch."""
        if self.steps_per_epoch() == 0:
            raise ValueError(
                f"dataset of {self._n} rows yields no batches of local "
                f"size {self._local_batch}")
        idx = np.arange(self._n)
        if self.shuffle:
            np.random.default_rng(self.seed + epoch_idx).shuffle(idx)
        return idx

    def _batch_index(self, idx: np.ndarray, step: int) -> np.ndarray:
        sel = idx[step * self._local_batch:(step + 1) * self._local_batch]
        if len(sel) < self._local_batch:  # pad the last partial batch
            sel = np.resize(sel, self._local_batch)
        return sel

    def epochs(self, mesh: Mesh, first: int, last: int, **kw: Any):
        """One iterator over epochs ``[first, last)``: every batch of
        ``epoch(mesh, e, **kw)`` for each ``e`` in order, and an
        ``EpochEnd(e)`` after each epoch's last.  ``Estimator.fit`` opens
        it once a call.  It has ``close()`` and ``next_is_ready()`` (is
        the next batch decoded already?).  Feeds that can work ahead
        across the boundary override it (``StreamingDataFeed``)."""
        return EpochChain(lambda e: self.epoch(mesh, e, **kw), first, last)

    def step_mask(self, step: int) -> np.ndarray:
        """Real-row weights for this process's ``step`` batch: 1.0 for rows
        that exist, 0.0 for padding (only the last non-drop_remainder batch
        is ever padded).  Lets a jit-compiled eval step cover the tail rows
        exactly under static shapes."""
        real = min(self._local_batch,
                   max(0, self._n - step * self._local_batch))
        m = np.zeros((self._local_batch,), np.float32)
        m[:real] = 1.0
        return m

    def dropped_rows(self, epoch_idx: int = 0):
        """The rows a drop_remainder epoch skips, respecting THAT epoch's
        shuffle order (shuffled feeds drop a permutation-dependent tail).
        None if nothing is dropped or the subclass cannot reconstruct them
        (callers fall back to a warning)."""
        if not self.shuffle:
            return self.remainder()
        return None


class DataFeed(FeedBase):
    """An epoch-iterable source of device-resident, mesh-sharded batches,
    holding the whole (host-local) dataset in RAM.  For datasets that don't
    fit, use stream.StreamingDataFeed."""

    def __init__(self, data: Dict[str, Any], batch_size: int,
                 shuffle: bool = True, seed: int = 0,
                 drop_remainder: bool = True):
        if "x" not in data:
            raise ValueError("DataFeed requires at least an 'x' entry")
        self._data = {k: v for k, v in data.items()}
        n = _nrows(self._data["x"])
        for k, v in self._data.items():
            if _nrows(v) != n:
                raise ValueError(
                    f"feature/label row mismatch: {k} has {_nrows(v)} rows, "
                    f"x has {n}")
        super().__init__(n, batch_size, shuffle, seed, drop_remainder)

    # -- constructors ---------------------------------------------------------

    @staticmethod
    def from_arrays(x: Any, y: Any = None, batch_size: int = 32,
                    **kw: Any) -> "DataFeed":
        data = {"x": x}
        if y is not None:
            data["y"] = y
        return DataFeed(data, batch_size, **kw)

    @staticmethod
    def from_shards(shards: XShards, batch_size: int = 32,
                    **kw: Any) -> "DataFeed":
        """Numpy-dict XShards ({"x": ..., "y": ...}) → DataFeed."""
        data = shards.concatenated()
        if not isinstance(data, dict):
            data = {"x": data}
        return DataFeed(data, batch_size, **kw)

    # -- iteration ------------------------------------------------------------

    def remainder(self) -> Optional[Dict[str, np.ndarray]]:
        """The tail rows a drop_remainder epoch skips (unshuffled order), or
        None.  Used by Estimator.evaluate so metrics cover every row."""
        r = self._n % self._local_batch
        if r == 0:
            return None
        sel = np.arange(self._n - r, self._n)
        return jax.tree_util.tree_map(lambda a: _take(a, sel), self._data)

    def dropped_rows(self, epoch_idx: int = 0):
        """Exact drop_remainder coverage even when shuffled: the dropped
        rows are the tail of THIS epoch's permutation."""
        r = self._n % self._local_batch
        if r == 0:
            return None
        sel = self._epoch_index(epoch_idx)[self._n - r:]
        return jax.tree_util.tree_map(lambda a: _take(a, sel), self._data)

    def epoch(self, mesh: Mesh, epoch_idx: int = 0
              ) -> Iterator[Dict[str, jax.Array]]:
        """Yield mesh-sharded batches for one epoch (one-batch lookahead)."""
        idx = self._epoch_index(epoch_idx)
        steps = self.steps_per_epoch()
        # batch-assembly latency (slice + stack + device_put dispatch):
        # the host-side cost the one-batch lookahead hides from training
        m_assemble = _metrics_lib.get_registry().histogram(
            "feed.batch_assembly_ms")

        def host_batch(step: int) -> Dict[str, np.ndarray]:
            t0 = time.monotonic()
            sel = self._batch_index(idx, step)
            out = jax.tree_util.tree_map(
                lambda a: _take(a, sel), self._data)
            m_assemble.observe((time.monotonic() - t0) * 1000.0)
            return out

        pending = shard_batch(host_batch(0), mesh)
        for step in range(steps):
            # ``feed.stall`` injection point (core/faults.py): an armed
            # delay models a slow storage read / augmentation hiccup, so
            # resilience tests can prove training-side timing behavior
            _fault_registry().fire("feed.stall")
            nxt = (shard_batch(host_batch(step + 1), mesh)
                   if step + 1 < steps else None)
            yield pending
            pending = nxt


class PrefetchIterator:
    """Depth-bounded background prefetch over a batch iterator.

    A producer thread drives the wrapped iterator — for DataFeed /
    StreamingDataFeed epochs that means the host-side batch indexing,
    ``shard_batch`` and the ``device_put`` dispatch all happen OFF the
    training thread — and parks up to ``depth`` ready batches in a
    bounded queue (``depth=2`` is classic double buffering: batch k+1
    stages while the device computes batch k, and one more is in
    flight).  The consumer's ``next()`` then only blocks when the feed
    is genuinely slower than the step, which is exactly what the
    ``train.data_wait_ms`` histogram should measure.

    ``place``: optional callable applied to every item INSIDE the
    producer thread (e.g. ``stream.make_placer(mesh)`` = ``shard_batch``
    over host batches).  With ``depth >= 2`` this is double-buffered
    ``device_put``: the host→HBM copy of batch N+1 is dispatched — and
    completes — while the device computes batch N.  Items carrying a
    ``release()`` handle (shared-memory pool slots from the streaming
    feed's process backend) are retired one item behind the placement:
    once the NEXT item is dispatched, the previous transfer is synced
    (dispatch plus that tail observed as ``feed.h2d_ms``) and the slot
    recycled.

    Over a multi-epoch iterator (``FeedBase.epochs``) the producer runs
    straight across the epoch boundary: an ``EpochEnd`` marker passes
    through unplaced, in its position, and the first batches of epoch
    k+1 are staged while the consumer still trains on epoch k's last.

    Exceptions from the producer (loader failures, injected
    ``feed.stall``-adjacent faults) re-raise in the consumer at the
    position they occurred.  ``close()`` is safe mid-epoch (rollback,
    preemption, crash injection): it unblocks and joins the producer
    without draining the rest of the epoch.
    """

    _END = object()

    def __init__(self, it: Iterator, depth: int = 2,
                 gauge: Optional[Any] = None,
                 place: Optional[Any] = None):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._it = iter(it)
        self._q: "queue_mod.Queue" = queue_mod.Queue(maxsize=depth)
        self._gauge = gauge  # e.g. the train.prefetch_depth gauge
        self._place = place
        self._staged = None  # (placed, releasable_raw, dispatch_ms)
        self._m_h2d = None  # feed.h2d_ms, made when a pool slot retires
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._produce, daemon=True,
                                        name="zoo-prefetch")
        self._thread.start()

    def _put(self, item) -> bool:
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                if self._gauge is not None:
                    self._gauge.set(self._q.qsize())
                return True
            except queue_mod.Full:
                continue
        return False

    def _stage(self, raw: Any) -> Any:
        """Dispatch the device copy of THIS item, then retire the
        previous one (sync its transfer tail, recycle its pool slot) —
        the one-item lag is what guarantees a slot is never reused
        while its bytes are still in flight to the device."""
        t0 = time.monotonic()
        with _trace_lib.phase("feed.place"):
            placed = self._place(raw)
        disp_ms = (time.monotonic() - t0) * 1000.0
        self._retire()
        self._staged = (placed, raw if hasattr(raw, "release") else None,
                        disp_ms)
        return placed

    def _retire(self) -> None:
        # producer-thread only (close() leaves the last slot to the
        # SlotBatch GC safety net rather than racing the producer)
        staged, self._staged = self._staged, None
        if staged is None:
            return
        placed, raw, disp_ms = staged
        if raw is None:  # nothing to recycle: no forced sync, no copy time
            return
        t0 = time.monotonic()
        jax.block_until_ready(placed)
        if self._m_h2d is None:
            self._m_h2d = _metrics_lib.get_registry().histogram(
                "feed.h2d_ms")
        self._m_h2d.observe(disp_ms + (time.monotonic() - t0) * 1000.0)
        raw.release()

    def _produce(self) -> None:
        try:
            for batch in self._it:
                if self._place is not None and \
                        not isinstance(batch, EpochEnd):
                    batch = self._stage(batch)
                if not self._put(("item", batch)):
                    return  # closed mid-epoch
                if self._stop.is_set():
                    return
            self._retire()
        except BaseException as e:  # noqa: BLE001 — re-raised in consumer
            self._put(("error", e))
            return
        self._put((self._END, None))

    def __iter__(self) -> "PrefetchIterator":
        return self

    def __next__(self):
        if self._stop.is_set():
            raise StopIteration
        kind, payload = self._q.get()
        if self._gauge is not None:
            self._gauge.set(self._q.qsize())
        if kind == "item":
            return payload
        self._stop.set()
        if kind == "error":
            raise payload
        raise StopIteration

    def next_is_ready(self) -> bool:
        """Is the next item staged already, so that ``next()`` will not
        wait?  (``Estimator.fit`` asks before an epoch's first batch:
        registry counter ``feed.epochs_carried``.)"""
        return not self._q.empty()

    def close(self, timeout: float = 5.0) -> None:
        """Stop the producer and reclaim its thread (idempotent).  The
        wait is BOUNDED: a producer wedged inside the wrapped iterator
        itself (a hung loader) cannot be interrupted from here — after
        ``timeout`` the daemon thread is abandoned (it exits at its next
        queue handoff) rather than turning the caller's own exit (e.g. a
        clean preemption) into a hang."""
        self._stop.set()
        deadline = time.monotonic() + timeout
        while self._thread.is_alive():
            try:  # unblock a producer stuck on a full queue
                self._q.get_nowait()
            except queue_mod.Empty:
                pass
            self._thread.join(timeout=0.05)
            if time.monotonic() > deadline:
                break
        if not self._thread.is_alive():
            close_it = getattr(self._it, "close", None)
            if close_it is not None:
                try:  # prompt generator cleanup (stream feeds join
                    close_it()  # their decode workers)
                except (RuntimeError, ValueError):
                    pass
        if self._gauge is not None:
            self._gauge.set(0.0)


def as_feed(data: Any, batch_size: int, **kw: Any) -> DataFeed:
    """Coerce the estimator's accepted data forms into a DataFeed.

    Accepts: DataFeed (passthrough), XShards of numpy dicts, a (x, y) tuple,
    a dict {"x": ..., "y": ...}, or a bare array (unsupervised).
    """
    if isinstance(data, FeedBase):
        return data  # DataFeed / StreamingDataFeed / any FeedBase subclass
    if isinstance(data, XShards):
        return DataFeed.from_shards(data, batch_size, **kw)
    if isinstance(data, dict):
        return DataFeed(data, batch_size, **kw)
    if isinstance(data, tuple) and len(data) == 2:
        return DataFeed.from_arrays(data[0], data[1], batch_size, **kw)
    return DataFeed.from_arrays(data, None, batch_size, **kw)


def _nrows(v: Any) -> int:
    if isinstance(v, (tuple, list)):
        return _nrows(v[0])
    if isinstance(v, dict):
        return _nrows(next(iter(v.values())))
    return len(v)


def _take(a: Any, sel: np.ndarray) -> np.ndarray:
    return np.asarray(a)[sel]
