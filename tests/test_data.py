"""Data layer tests: XShards ops, readers, DataFeed sharding."""

import numpy as np
import pandas as pd
import pytest

from analytics_zoo_tpu.core import init_orca_context
from analytics_zoo_tpu.data import (DataFeed, XShards, as_feed, read_csv,
                                    read_json, read_npz, shard_batch)


def _df(n=20, seed=0):
    rng = np.random.default_rng(seed)
    return pd.DataFrame({"a": rng.normal(size=n), "b": rng.integers(0, 5, n),
                         "y": rng.integers(0, 2, n)})


class TestXShards:
    def test_partition_array(self):
        s = XShards.partition(np.arange(10), num_shards=3)
        assert s.num_partitions() == 3
        np.testing.assert_array_equal(s.concatenated(), np.arange(10))

    def test_partition_dict(self):
        s = XShards.partition({"x": np.ones((10, 2)), "y": np.zeros(10)}, 4)
        assert s.num_partitions() == 4
        assert len(s) == 10
        out = s.concatenated()
        assert out["x"].shape == (10, 2)

    def test_transform_shard(self):
        s = XShards.partition(np.arange(10), 2).transform_shard(lambda a: a * 2)
        np.testing.assert_array_equal(s.concatenated(), np.arange(10) * 2)

    def test_transform_with_args(self):
        s = XShards.partition(np.arange(4), 2).transform_shard(
            lambda a, k: a + k, 5)
        np.testing.assert_array_equal(s.concatenated(), np.arange(4) + 5)

    def test_repartition_pandas(self):
        s = XShards([_df(10), _df(10, 1)])
        r = s.repartition(5)
        assert r.num_partitions() == 5
        assert sum(len(d) for d in r.collect()) == 20

    def test_partition_by(self):
        s = XShards([_df(50)])
        parts = s.partition_by("b", num_partitions=3)
        assert parts.num_partitions() == 3
        seen = {}
        for i, df in enumerate(parts.collect()):
            for v in df["b"].unique():
                assert v not in seen, "key split across partitions"
                seen[v] = i

    def test_split(self):
        s = XShards([(np.ones(3), np.zeros(3)), (np.ones(2), np.zeros(2))])
        xs, ys = s.split()
        assert len(xs) == 5 and len(ys) == 5

    def test_to_numpy_dict(self):
        s = XShards([_df(10)]).to_numpy_dict(feature_cols=["a", "b"],
                                             label_cols=["y"])
        d = s.collect()[0]
        assert d["x"].shape == (10, 2) and d["y"].shape == (10,)


class TestReaders:
    def test_read_csv_glob(self, tmp_path):
        for i in range(3):
            _df(10, i).to_csv(tmp_path / f"part{i}.csv", index=False)
        s = read_csv(str(tmp_path / "*.csv"))
        assert s.num_partitions() == 3
        assert len(s) == 30

    def test_read_csv_dir_and_repartition(self, tmp_path):
        for i in range(4):
            _df(5, i).to_csv(tmp_path / f"p{i}.csv", index=False)
        s = read_csv(str(tmp_path), num_shards=2)
        assert s.num_partitions() == 2
        assert len(s) == 20

    def test_read_json(self, tmp_path):
        _df(8).to_json(tmp_path / "d.json", orient="records")
        s = read_json(str(tmp_path / "d.json"))
        assert len(s) == 8

    def test_read_npz(self, tmp_path):
        np.savez(tmp_path / "d.npz", x=np.ones((6, 2)), y=np.zeros(6))
        s = read_npz(str(tmp_path / "d.npz"))
        assert s.collect()[0]["x"].shape == (6, 2)

    def test_missing_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            read_csv(str(tmp_path / "none*.csv"))

    def test_extension_matching_is_case_insensitive(self, tmp_path):
        """.CSV / .JPG-style uppercase extensions were silently dropped
        from directory reads (ISSUE 7 satellite)."""
        _df(6, 0).to_csv(tmp_path / "lower.csv", index=False)
        _df(4, 1).to_csv(tmp_path / "UPPER.CSV", index=False)
        s = read_csv(str(tmp_path))
        assert s.num_partitions() == 2
        assert len(s) == 10

    def test_file_readahead_overlaps_and_counts_waits(self, tmp_path):
        from analytics_zoo_tpu.data import FileReadahead
        paths = []
        for i in range(4):
            p = tmp_path / f"f{i}.bin"
            p.write_bytes(bytes([i]) * 64)
            paths.append(str(p))
        ra = FileReadahead(depth=2)
        ra.hint(paths)
        import time
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline and not ra._cache:
            time.sleep(0.005)
        for i, p in enumerate(paths):
            assert ra.get(p) == bytes([i]) * 64
        # un-hinted miss reads inline and counts the blocked time
        miss = tmp_path / "miss.bin"
        miss.write_bytes(b"z" * 8)
        before = ra.wait_ms
        assert ra.get(str(miss)) == b"z" * 8
        assert ra.wait_ms >= before
        # a lost race must RETIRE the hint: no consumed path may linger
        # in (or later enter) the cache, or depth such entries would
        # park the reader forever
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline:
            with ra._cond:
                stale = set(ra._cache) & set(paths)
                idle = ra._reading is None and not ra._want
            if idle and not stale:
                break
            time.sleep(0.005)
        assert not stale, stale
        ra.close()


class TestDataFeed:
    def test_batches_are_sharded(self):
        mesh = init_orca_context("local")
        feed = DataFeed.from_arrays(np.ones((64, 4), np.float32),
                                    np.zeros(64, np.int32), batch_size=16)
        batches = list(feed.epoch(mesh, 0))
        assert len(batches) == 4
        b = batches[0]
        assert b["x"].shape == (16, 4)
        assert b["x"].sharding.is_fully_replicated is False
        # dim 0 split over the 8-device data axis
        assert b["x"].addressable_shards[0].data.shape == (2, 4)

    def test_shuffle_deterministic(self):
        mesh = init_orca_context("local")
        feed = DataFeed.from_arrays(np.arange(32, dtype=np.float32),
                                    batch_size=8, shuffle=True, seed=3)
        e1 = [np.asarray(b["x"]) for b in feed.epoch(mesh, 0)]
        e2 = [np.asarray(b["x"]) for b in feed.epoch(mesh, 0)]
        e3 = [np.asarray(b["x"]) for b in feed.epoch(mesh, 1)]
        np.testing.assert_array_equal(np.concatenate(e1), np.concatenate(e2))
        assert not np.array_equal(np.concatenate(e1), np.concatenate(e3))

    def test_row_mismatch_raises(self):
        with pytest.raises(ValueError):
            DataFeed({"x": np.ones(10), "y": np.ones(9)}, 2)

    def test_as_feed_forms(self):
        f1 = as_feed((np.ones(8), np.ones(8)), 4)
        f2 = as_feed({"x": np.ones(8)}, 4)
        f3 = as_feed(XShards.partition({"x": np.ones(8)}, 2), 4)
        assert f1.num_rows == f2.num_rows == f3.num_rows == 8
        assert as_feed(f1, 4) is f1

    def test_shard_batch_tree(self):
        mesh = init_orca_context("local")
        out = shard_batch({"x": np.ones((8, 3)), "y": np.ones(8)}, mesh)
        assert out["x"].shape == (8, 3) and out["y"].shape == (8,)

    def test_empty_batch_raises(self):
        mesh = init_orca_context("local")
        feed = DataFeed.from_arrays(np.ones((2, 2)), batch_size=8)
        with pytest.raises(ValueError):
            next(feed.epoch(mesh))


class TestStreamingResilience:
    """Loader-failure policies: bounded retries, skip-and-count, visible
    degradation counters (data/stream.py) — the SAME suite runs against
    both decode backends (ISSUE 7: ``workers="process"`` must pass the
    ordering/resilience/fault-injection contracts unchanged)."""

    @pytest.fixture(params=["thread", "process"])
    def backend(self, request):
        if request.param == "process":
            from analytics_zoo_tpu.data import shm_pool
            if not shm_pool.available():
                pytest.skip("process backend unavailable")
        return request.param

    def _mesh(self):
        from analytics_zoo_tpu.core import init_orca_context
        return init_orca_context("local")

    def test_transient_failure_retried_no_row_lost(self, backend):
        from analytics_zoo_tpu.data import StreamingDataFeed
        mesh = self._mesh()
        fails = {"n": 0}

        def flaky(i, rng=None):
            if i == 3 and fails["n"] < 2:
                fails["n"] += 1
                raise OSError("transient read")
            return {"x": np.full((2,), float(i), np.float32)}

        feed = StreamingDataFeed(8, flaky, batch_size=4, shuffle=False,
                                 num_workers=1, retries=2, workers=backend)
        rows = sorted(float(v) for b in feed.epoch(mesh, 0)
                      for v in np.asarray(b["x"])[:, 0])
        assert rows == [float(i) for i in range(8)]  # nothing lost
        assert feed.load_failures == 2
        assert feed.skipped_rows == 0

    def test_persistent_failure_skipped_and_counted(self, backend):
        from analytics_zoo_tpu.data import StreamingDataFeed
        mesh = self._mesh()

        def corrupt(i, rng=None):
            if i == 3:
                raise OSError("corrupt sample")
            return {"x": np.full((2,), float(i), np.float32)}

        feed = StreamingDataFeed(8, corrupt, batch_size=4, shuffle=False,
                                 num_workers=1, retries=1, on_error="skip",
                                 workers=backend)
        rows = sorted(float(v) for b in feed.epoch(mesh, 0)
                      for v in np.asarray(b["x"])[:, 0])
        # row 3 was substituted with its neighbor: batch shape intact,
        # degradation visible in the counter
        assert len(rows) == 8
        assert 3.0 not in rows and rows.count(4.0) == 2
        assert feed.skipped_rows == 1
        assert feed.load_failures == 2  # initial try + 1 retry

    def test_max_skipped_bounds_degradation(self, backend):
        from analytics_zoo_tpu.data import StreamingDataFeed
        mesh = self._mesh()

        def corrupt(i, rng=None):
            if i % 2 == 0 and i != 0:
                raise OSError("corrupt sample")
            return {"x": np.full((2,), float(i), np.float32)}

        feed = StreamingDataFeed(8, corrupt, batch_size=4, shuffle=False,
                                 num_workers=1, on_error="skip",
                                 max_skipped=1, workers=backend)
        with pytest.raises(RuntimeError, match="max_skipped"):
            list(feed.epoch(mesh, 0))

    def test_default_raise_policy_unchanged(self, backend):
        from analytics_zoo_tpu.data import StreamingDataFeed
        mesh = self._mesh()

        def bad(i, rng=None):
            if i == 5:
                raise ValueError("corrupt sample")
            return {"x": np.zeros((2,), np.float32)}

        feed = StreamingDataFeed(8, bad, batch_size=4, shuffle=False,
                                 num_workers=2, workers=backend)
        with pytest.raises(ValueError, match="corrupt sample"):
            list(feed.epoch(mesh, 0))

    def test_read_fail_injection_absorbed_from_workers(self, backend):
        """The armed ``feed.read_fail`` point fires in the decode worker
        (forked or threaded) and the parent registry's fired()/times
        accounting stays coherent either way."""
        from analytics_zoo_tpu.core import faults
        from analytics_zoo_tpu.data import StreamingDataFeed
        mesh = self._mesh()
        reg = faults.get_registry()
        feed = StreamingDataFeed(
            8, lambda i, rng=None: {"x": np.full((2,), float(i),
                                                 np.float32)},
            batch_size=4, shuffle=False, num_workers=1, retries=1,
            workers=backend)
        before = reg.fired("feed.read_fail")
        with reg.armed("feed.read_fail", times=1):
            batches = list(feed.epoch(mesh, 0))
        assert reg.fired("feed.read_fail") - before == 1
        assert feed.load_failures == 1
        assert feed.skipped_rows == 0
        rows = sorted(float(v) for b in batches
                      for v in np.asarray(b["x"])[:, 0])
        assert rows == [float(i) for i in range(8)]

    def test_policy_validated(self):
        from analytics_zoo_tpu.data import StreamingDataFeed
        with pytest.raises(ValueError, match="on_error"):
            StreamingDataFeed(8, lambda i, rng=None: {}, batch_size=4,
                              on_error="ignore")
        with pytest.raises(ValueError, match="retries"):
            StreamingDataFeed(8, lambda i, rng=None: {}, batch_size=4,
                              retries=-1)


class TestPrefetchIterator:
    """Background feed lookahead (the training half of the pipelined hot
    path): order, exception propagation, and mid-epoch shutdown."""

    def test_order_preserved_and_complete(self):
        from analytics_zoo_tpu.data import PrefetchIterator
        items = [np.full((3,), float(i)) for i in range(17)]
        got = list(PrefetchIterator(iter(items), depth=2))
        assert len(got) == 17
        for i, a in enumerate(got):
            np.testing.assert_array_equal(a, items[i])

    def test_producer_exception_reraises_in_consumer(self):
        from analytics_zoo_tpu.data import PrefetchIterator

        def gen():
            yield 1
            yield 2
            raise OSError("loader died")

        it = PrefetchIterator(gen(), depth=2)
        assert next(it) == 1 and next(it) == 2
        with pytest.raises(OSError, match="loader died"):
            next(it)
        # after the error the iterator is exhausted, not wedged
        assert next(it, None) is None

    def test_close_mid_epoch_stops_producer(self):
        import itertools
        import threading
        from analytics_zoo_tpu.data import PrefetchIterator
        produced = []

        def gen():
            for i in itertools.count():
                produced.append(i)
                yield i

        it = PrefetchIterator(gen(), depth=2)
        assert next(it) == 0
        it.close()
        n_threads = threading.active_count()
        it.close()  # idempotent
        assert threading.active_count() == n_threads
        # the producer stopped near the depth bound, not at infinity
        assert len(produced) <= 8
        with pytest.raises(StopIteration):
            next(it)

    def test_depth_validated(self):
        from analytics_zoo_tpu.data import PrefetchIterator
        with pytest.raises(ValueError, match="depth"):
            PrefetchIterator(iter([]), depth=0)

    def test_overlaps_slow_feed_with_slow_consumer(self):
        """With depth-2 double buffering, a feed taking F per batch and a
        consumer taking C per step run in ~max(F, C) per item, not F+C —
        the wall-clock proof that host feed work overlaps consumption."""
        import time as _t
        from analytics_zoo_tpu.data import PrefetchIterator

        def slow_feed(n=8, per=0.03):
            for i in range(n):
                _t.sleep(per)
                yield i

        # inline baseline: feed + consume serialize
        t0 = _t.monotonic()
        for _ in slow_feed():
            _t.sleep(0.03)
        inline = _t.monotonic() - t0

        t0 = _t.monotonic()
        it = PrefetchIterator(slow_feed(), depth=2)
        for _ in it:
            _t.sleep(0.03)
        overlapped = _t.monotonic() - t0
        # ~0.48s inline vs ~0.27s overlapped; generous margin for CI noise
        assert overlapped < inline * 0.8, (inline, overlapped)


def _feed_threads():
    import threading
    return [t.name for t in threading.enumerate()
            if t.name.startswith(("zoo-feed", "zoo-prefetch"))]


class TestEpochsCarried:
    """One pipeline over several epochs (ISSUE 28): ``epochs()`` yields what
    the same number of ``epoch()`` calls would, with a marker behind each
    epoch, from one set of workers that runs ahead across the boundary."""

    BATCH, STEPS = 4, 3

    def _feed(self, load=None, **kw):
        from analytics_zoo_tpu.data import StreamingDataFeed

        def by_index(i, rng=None):
            return {"x": np.full((2,), float(i), np.float32),
                    "y": np.int32(i)}
        kw.setdefault("shuffle", True)
        return StreamingDataFeed(self.BATCH * self.STEPS, load or by_index,
                                 batch_size=self.BATCH, seed=5, **kw)

    @pytest.mark.parametrize("workers", [1, 4])
    def test_three_epochs_are_three_epoch_calls_in_order(self, workers):
        from analytics_zoo_tpu.data import EpochEnd
        mesh = init_orca_context("local")
        feed = self._feed(num_workers=workers)
        want = []
        for e in range(3):
            want += [np.asarray(b["y"]) for b in
                     feed.epoch(mesh, e, place=False)] + [e]
        got = [item.epoch if isinstance(item, EpochEnd)
               else np.asarray(item["y"])
               for item in feed.epochs(mesh, 0, 3, place=False)]
        assert len(got) == len(want) == 3 * (self.STEPS + 1)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        # the epochs differ (shuffled), so the order above was a real check
        assert not np.array_equal(want[0], want[self.STEPS + 1])
        assert _feed_threads() == []

    def test_the_next_epochs_first_batch_is_decoded_before_this_ones_last(
            self):
        """Epoch 0's last batch hangs in its loader; meanwhile the other
        workers go on to epoch 1's step 0, which lands in ``ready`` under
        its global position."""
        import threading
        import time
        mesh = init_orca_context("local")
        gate, seen = threading.Event(), set()
        last_rows = set(range(self.BATCH * (self.STEPS - 1),
                              self.BATCH * self.STEPS))

        def load(i, rng=None):
            first_time = i not in seen
            seen.add(i)
            if i in last_rows and first_time:
                assert gate.wait(30)
            return {"y": np.int32(i)}

        feed = self._feed(load, shuffle=False, num_workers=4)
        run = feed.epochs(mesh, 0, 2, place=False)
        try:
            assert run.next_is_ready() is False     # nothing decoded yet
            first = next(run)       # needs step 1 decoded too, not step 2
            np.testing.assert_array_equal(first["y"], np.arange(self.BATCH))
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                with run.ready_cond:
                    if self.STEPS in run.ready:     # epoch 1, step 0
                        break
                time.sleep(0.01)
            with run.ready_cond:
                assert self.STEPS in run.ready
                assert self.STEPS - 1 not in run.ready  # epoch 0's last
                np.testing.assert_array_equal(
                    run.ready[self.STEPS]["y"], np.arange(self.BATCH))
            gate.set()
            rest = list(run)
        finally:
            gate.set()
            run.close()
        assert len(rest) == 2 * self.STEPS - 1 + 2  # batches and two markers
        assert _feed_threads() == []

    def test_nothing_past_the_last_epoch_is_loaded(self):
        import threading
        import time
        mesh = init_orca_context("local")
        calls, lock = [], threading.Lock()

        def load(i, rng=None):
            with lock:
                calls.append(i)
            return {"y": np.int32(i)}

        feed = self._feed(load, num_workers=4, prefetch_batches=8)
        run = feed.epochs(mesh, 3, 5, place=False)
        next(run)
        time.sleep(0.2)             # room to run as far ahead as allowed
        rest = list(run)
        assert len(rest) == 2 * self.STEPS - 1 + 2
        # two epochs' rows exactly, each row once an epoch
        assert sorted(calls) == sorted(2 * list(range(self.BATCH
                                                      * self.STEPS)))

    def test_a_batchs_rng_follows_seed_epoch_and_step(self):
        """What augmentation draws no longer depends on which worker got
        the step: two runs with four workers give the same bytes, and they
        are ``default_rng((seed, epoch, step))``'s."""
        mesh = init_orca_context("local")

        def load(i, rng=None):
            return {"x": rng.random(3), "y": np.int32(i)}

        runs = [[b["x"] for b in self._feed(load, num_workers=4).epochs(
                    mesh, 1, 3, place=False) if isinstance(b, dict)]
                for _ in range(2)]
        for a, b in zip(*runs):
            np.testing.assert_array_equal(a, b)
        want = np.random.default_rng((5, 2, 1))
        np.testing.assert_array_equal(
            runs[0][self.STEPS + 1],
            np.stack([want.random(3) for _ in range(self.BATCH)]))

    def test_closing_mid_run_joins_the_workers_and_drops_what_is_ahead(self):
        mesh = init_orca_context("local")
        run = self._feed(num_workers=4).epochs(mesh, 0, 50, place=False)
        for _ in range(self.STEPS + 2):
            next(run)
        assert any(n.startswith("zoo-feed-w") for n in _feed_threads())
        run.close()
        assert _feed_threads() == []
        assert next(run, None) is None

    def test_many_workers_claim_every_position_once_and_in_order(self):
        """Stress: more workers than cores over many short epochs, with the
        interpreter switching threads every 10 us — a lost or doubled
        claim, or a batch filed under the wrong position, breaks the
        sequence."""
        import sys
        from analytics_zoo_tpu.data import EpochEnd
        mesh = init_orca_context("local")
        epochs, rows = 40, self.BATCH * self.STEPS
        feed = self._feed(num_workers=32, prefetch_batches=2)
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            got = list(feed.epochs(mesh, 0, epochs, place=False))
        finally:
            sys.setswitchinterval(old)
        assert [g.epoch for g in got if isinstance(g, EpochEnd)] \
            == list(range(epochs))
        seen = [np.asarray(g["y"]) for g in got if isinstance(g, dict)]
        assert len(seen) == epochs * self.STEPS
        for e in range(epochs):
            order = np.arange(rows)
            np.random.default_rng(5 + e).shuffle(order)
            np.testing.assert_array_equal(
                np.concatenate(seen[e * self.STEPS:(e + 1) * self.STEPS]),
                order)
        assert _feed_threads() == []

    def test_the_default_chains_epoch_calls(self):
        """``FeedBase.epochs``: an in-RAM feed's epochs one after another;
        nothing is ever ready ahead of the consumer."""
        from analytics_zoo_tpu.data import EpochEnd
        mesh = init_orca_context("local")
        x = np.arange(24, dtype=np.float32).reshape(12, 2)
        feed = DataFeed({"x": x}, batch_size=4, shuffle=True, seed=3)
        want = [np.asarray(b["x"]) for e in (2, 3)
                for b in feed.epoch(mesh, e)]
        run = feed.epochs(mesh, 2, 4)
        assert run.next_is_ready() is False
        got = list(run)
        assert [g.epoch for g in got if isinstance(g, EpochEnd)] == [2, 3]
        assert isinstance(got[3], EpochEnd) and isinstance(got[7], EpochEnd)
        for g, w in zip([g for g in got if isinstance(g, dict)], want):
            np.testing.assert_array_equal(np.asarray(g["x"]), w)

    def test_the_prefetcher_passes_a_marker_through_unplaced(self):
        from analytics_zoo_tpu.data import EpochEnd, PrefetchIterator
        placed = []

        def place(item):
            placed.append(item)
            return item * 10

        it = PrefetchIterator(iter([1, 2, EpochEnd(0), 3, EpochEnd(1)]),
                              depth=2, place=place)
        got = list(it)
        assert [g for g in got if not isinstance(g, EpochEnd)] == [10, 20, 30]
        assert [type(g) for g in got].index(EpochEnd) == 2
        assert got[-1].epoch == 1 and placed == [1, 2, 3]
        assert it.next_is_ready() is False
