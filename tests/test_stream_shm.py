"""Streaming-input rebuild tests (ISSUE 7): shared-memory slot pool
lifecycle, process decode backend (ordering / identity / crash
resilience / fallback), device-side augmentation, the PrefetchIterator
place hook, and the uint8→device loss-parity acceptance criterion."""

import glob
import os
import time

import numpy as np
import pytest

import analytics_zoo_tpu.nn as nn
from analytics_zoo_tpu.core import get_mesh, init_orca_context
from analytics_zoo_tpu.data import (DeviceAugment, DeviceNormalize,
                                    DeviceRandomCrop, DeviceRandomFlip,
                                    PrefetchIterator, ShmBatchPool,
                                    SlotBatch, StreamingDataFeed)
from analytics_zoo_tpu.data import shm_pool
from analytics_zoo_tpu.data.image import ImageNormalize
from analytics_zoo_tpu.orca.learn import Estimator

needs_process = pytest.mark.skipif(
    not shm_pool.available(),
    reason="multiprocessing.shared_memory / fork unavailable")


def _mesh():
    return init_orca_context("local")


def _shm_leaks():
    return glob.glob(f"/dev/shm/{shm_pool.SHM_PREFIX}*")


def _det_load(i, rng=None):
    """Deterministic from the index (what a decode is), rng-free."""
    r = np.random.default_rng(i)
    return {"x": r.normal(size=(3,)).astype(np.float32),
            "y": np.int32(i % 5)}


# -- pool lifecycle -----------------------------------------------------------

class TestShmPool:
    def test_roundtrip_and_views_shared(self):
        pool = ShmBatchPool(2, 4, {"x": ((3,), np.float32),
                                   "y": ((), np.int32)})
        try:
            s = pool.acquire(timeout=1)
            v = pool.views(s)
            v["x"][:] = 7.0
            v["y"][:] = np.arange(4)
            again = pool.views(s)
            np.testing.assert_array_equal(again["x"], np.full((4, 3), 7.0))
            np.testing.assert_array_equal(again["y"], np.arange(4))
            pool.release(s)
            assert pool.acquire(timeout=1) is not None
        finally:
            pool.close()

    def test_acquire_blocks_at_capacity(self):
        pool = ShmBatchPool(2, 2, {"x": ((2,), np.float32)})
        try:
            a = pool.acquire(timeout=1)
            b = pool.acquire(timeout=1)
            assert a is not None and b is not None
            assert pool.acquire(timeout=0.1) is None  # the memory bound
            pool.release(a)
            assert pool.acquire(timeout=1) == a
        finally:
            pool.close()

    def test_close_unlinks_every_segment(self):
        assert not _shm_leaks()
        pool = ShmBatchPool(3, 4, {"x": ((8,), np.uint8)})
        assert len(_shm_leaks()) == 3
        pool.close()
        assert not _shm_leaks()
        pool.close()  # idempotent

    def test_slot_batch_release_idempotent_and_on_gc(self):
        pool = ShmBatchPool(2, 2, {"x": ((2,), np.float32)})
        try:
            s = pool.acquire(timeout=1)
            sb = SlotBatch(pool.views(s), s, pool)
            sb.release()
            sb.release()  # idempotent: slot must not enter the pool twice
            assert pool.acquire(timeout=1) is not None
            assert pool.acquire(timeout=1) is not None
            assert pool.acquire(timeout=0.1) is None
            # GC safety net: dropping an unreleased batch frees its slot
            pool2 = ShmBatchPool(2, 2, {"x": ((2,), np.float32)})
            try:
                s2 = pool2.acquire(timeout=1)
                SlotBatch(pool2.views(s2), s2, pool2)  # dropped immediately
                assert pool2.acquire(timeout=1) is not None
            finally:
                pool2.close()
        finally:
            pool.close()


# -- process backend ----------------------------------------------------------

@needs_process
class TestProcessBackend:
    def test_bitwise_identical_to_thread_backend(self):
        mesh = _mesh()
        kw = dict(batch_size=4, shuffle=True, seed=11, num_workers=2)
        ft = StreamingDataFeed(24, _det_load, workers="thread", **kw)
        fp = StreamingDataFeed(24, _det_load, workers="process", **kw)
        bt = [{k: np.asarray(v) for k, v in b.items()}
              for b in ft.epoch(mesh, 0)]
        bp = [{k: np.asarray(v) for k, v in b.items()}
              for b in fp.epoch(mesh, 0)]
        assert len(bt) == len(bp) == 6
        for a, b in zip(bt, bp):
            np.testing.assert_array_equal(a["x"], b["x"])
            np.testing.assert_array_equal(a["y"], b["y"])
        assert not _shm_leaks()

    def test_step_order_survives_straggler_decodes(self):
        mesh = _mesh()

        def slow_early(i, rng=None):
            if i < 4:
                time.sleep(0.05)  # first batch decodes LAST
            return {"x": np.full((2,), float(i), np.float32)}

        feed = StreamingDataFeed(16, slow_early, batch_size=4,
                                 shuffle=False, num_workers=3,
                                 workers="process")
        rows = [np.asarray(b["x"])[:, 0] for b in feed.epoch(mesh, 0)]
        flat = [float(v) for batch in rows for v in batch]
        assert flat == [float(i) for i in range(16)]  # strict step order

    def test_worker_crash_mid_write_releases_slot(self):
        mesh = _mesh()
        main_pid = os.getpid()

        def killer(i, rng=None):
            if i == 6 and os.getpid() != main_pid:
                os._exit(3)  # hard death while its slot is checked out
            return {"x": np.full((2,), float(i), np.float32)}

        feed = StreamingDataFeed(32, killer, batch_size=4, shuffle=False,
                                 num_workers=2, workers="process")
        with pytest.raises(RuntimeError, match="died"):
            list(feed.epoch(mesh, 0))
        # the crashed worker's half-written slot was reclaimed and every
        # segment unlinked — nothing left in /dev/shm
        assert not _shm_leaks()

    def test_abandoned_epoch_unlinks_segments(self):
        mesh = _mesh()
        feed = StreamingDataFeed(64, _det_load, batch_size=4,
                                 shuffle=False, num_workers=2,
                                 workers="process")
        it = feed.epoch(mesh, 0)
        next(it)
        assert _shm_leaks()    # pool is live mid-epoch
        it.close()
        assert not _shm_leaks()

    def test_thread_fallback_when_shm_unavailable(self, monkeypatch,
                                                  caplog):
        monkeypatch.setattr(shm_pool, "available", lambda: False)
        feed = StreamingDataFeed(8, _det_load, batch_size=4,
                                 shuffle=False, workers="process")
        assert feed.workers == "thread"
        mesh = _mesh()
        assert len(list(feed.epoch(mesh, 0))) == 2

    def test_invalid_backend_rejected(self):
        with pytest.raises(ValueError, match="workers"):
            StreamingDataFeed(8, _det_load, batch_size=4, workers="actor")

    def test_host_batches_are_slot_views_and_release(self):
        mesh = _mesh()
        feed = StreamingDataFeed(16, _det_load, batch_size=4,
                                 shuffle=False, num_workers=2,
                                 workers="process")
        seen = []
        for b in feed.epoch(mesh, 0, place=False):
            assert isinstance(b, SlotBatch)
            seen.append({k: np.asarray(v).copy() for k, v in b.items()})
            b.release()
        assert len(seen) == 4
        np.testing.assert_array_equal(
            seen[0]["x"][0], _det_load(0)["x"])
        assert not _shm_leaks()

    def test_multi_epoch_reuse_and_counter_sync(self):
        mesh = _mesh()

        def corrupt(i, rng=None):
            if i == 2:
                raise OSError("bad sample")
            return {"x": np.full((2,), float(i), np.float32)}

        feed = StreamingDataFeed(8, corrupt, batch_size=4, shuffle=False,
                                 num_workers=2, on_error="skip",
                                 workers="process")
        list(feed.epoch(mesh, 0))
        assert feed.skipped_rows == 1
        list(feed.epoch(mesh, 1))
        assert feed.skipped_rows == 2  # counters accumulate across epochs
        assert not _shm_leaks()


@needs_process
def test_process_backend_observes_the_whole_copy_in_feed_h2d_ms():
    """Under ``workers="process"`` a batch's host->device copy is synced
    before its shm slot is recycled, so ``feed.h2d_ms`` holds one whole
    copy (dispatch + synced tail) a batch placed."""
    from analytics_zoo_tpu.core import metrics
    init_orca_context("local")
    feed = StreamingDataFeed(
        32, lambda i, rng=None: {"x": np.full((3,), float(i), np.float32),
                                 "y": np.float32([i % 2])},
        batch_size=8, shuffle=False, num_workers=2, workers="process")
    est = Estimator.from_keras(nn.Sequential([nn.Dense(1)]), loss="mse",
                               learning_rate=1e-3)
    est.fit(feed, epochs=2, batch_size=8, verbose=False, prefetch=2)
    snap = metrics.get_registry().snapshot()
    assert snap["train.steps"] == 8
    # every batch but the last retires inside the producer's loop; the
    # last one's slot is left to close()
    assert snap["feed.h2d_ms"]["count"] in (7, 8)
    assert snap["feed.h2d_ms"]["sum"] > 0
    assert not _shm_leaks()


# -- pooled tail loading ------------------------------------------------------

class TestTailThroughWorkerPool:
    def test_remainder_values_and_parallelism(self):
        _mesh()
        calls = []

        def load(i, rng=None):
            calls.append(i)
            return {"x": np.full((2,), float(i), np.float32)}

        feed = StreamingDataFeed(10, load, batch_size=4, shuffle=False,
                                 num_workers=4)
        rem = feed.remainder()
        np.testing.assert_array_equal(rem["x"][:, 0], [8.0, 9.0])
        assert sorted(calls) == [8, 9]

    def test_dropped_rows_match_epoch_permutation(self):
        _mesh()
        feed = StreamingDataFeed(10, _det_load, batch_size=4, shuffle=True,
                                 seed=3, num_workers=4)
        sel = feed._epoch_index(0)[8:]
        dropped = feed.dropped_rows(0)
        for k, i in enumerate(sel):
            np.testing.assert_array_equal(dropped["x"][k],
                                          _det_load(int(i))["x"])


# -- device augmentation ------------------------------------------------------

class TestDeviceAugment:
    def test_normalize_matches_host_chain(self):
        _mesh()
        rng = np.random.default_rng(0)
        imgs = rng.integers(0, 256, (4, 6, 6, 3), dtype=np.uint8)
        host = np.stack([ImageNormalize()(im) for im in imgs])
        dev = np.asarray(DeviceNormalize()(imgs, None, training=True))
        np.testing.assert_allclose(dev, host, rtol=1e-6)

    def test_flip_probabilities_and_eval_identity(self):
        import jax
        _mesh()
        x = np.arange(2 * 1 * 4 * 1, dtype=np.float32).reshape(2, 1, 4, 1)
        key = jax.random.PRNGKey(0)
        always = np.asarray(DeviceRandomFlip(1.0)(x, key, training=True))
        np.testing.assert_array_equal(always, x[:, :, ::-1, :])
        never = np.asarray(DeviceRandomFlip(0.0)(x, key, training=True))
        np.testing.assert_array_equal(never, x)
        eval_out = np.asarray(DeviceRandomFlip(1.0)(x, key, training=False))
        np.testing.assert_array_equal(eval_out, x)

    def test_random_crop_shape_and_center_eval(self):
        import jax
        _mesh()
        x = np.arange(2 * 6 * 6 * 1, dtype=np.float32).reshape(2, 6, 6, 1)
        key = jax.random.PRNGKey(1)
        out = np.asarray(DeviceRandomCrop(4, 4)(x, key, training=True))
        assert out.shape == (2, 4, 4, 1)
        center = np.asarray(DeviceRandomCrop(4, 4)(x, None, training=False))
        np.testing.assert_array_equal(center, x[:, 1:5, 1:5, :])
        with pytest.raises(ValueError, match="resize"):
            DeviceRandomCrop(8, 8)(x, key)

    def test_chain_is_deterministic_per_key_and_jittable(self):
        import jax
        _mesh()
        rng = np.random.default_rng(0)
        x = rng.integers(0, 256, (4, 8, 8, 3), dtype=np.uint8)
        aug = DeviceAugment([DeviceRandomCrop(6, 6), DeviceRandomFlip(),
                             DeviceNormalize()])
        key = jax.random.PRNGKey(42)
        a = np.asarray(jax.jit(lambda x, k: aug(x, k, True))(x, key))
        b = np.asarray(jax.jit(lambda x, k: aug(x, k, True))(x, key))
        np.testing.assert_array_equal(a, b)
        c = np.asarray(aug(x, jax.random.PRNGKey(43), True))
        assert a.shape == c.shape == (4, 6, 6, 3)
        assert not np.array_equal(a, c)  # different key, different draws


# -- PrefetchIterator place hook ----------------------------------------------

class TestPrefetchPlace:
    def test_place_runs_in_producer_and_retires_slots(self):
        released = []

        class FakeSlot(dict):
            def __init__(self, i):
                super().__init__(x=np.full((2,), float(i)))
                self.i = i

            def release(self):
                released.append(self.i)

        placed_order = []

        def place(b):
            placed_order.append(b.i)
            return dict(b)

        items = [FakeSlot(i) for i in range(5)]
        out = list(PrefetchIterator(iter(items), depth=2, place=place))
        assert len(out) == 5
        assert placed_order == [0, 1, 2, 3, 4]
        assert sorted(released) == [0, 1, 2, 3, 4]
        # retirement trails placement by exactly one item
        assert released[0] == 0 and released[-1] == 4

    def test_plain_items_pass_through_unreleased(self):
        out = list(PrefetchIterator(iter([{"x": 1}, {"x": 2}]), depth=2,
                                    place=lambda b: b))
        assert out == [{"x": 1}, {"x": 2}]


# -- acceptance: uint8-to-device loss parity ----------------------------------

class TestUint8DeviceAugmentParity:
    """The uint8-batch + DeviceAugment path must reach loss parity with
    the host-float32 path (same seed, rtol 1e-5) — ISSUE 7 acceptance."""

    MEAN, STD = (0.5, 0.5, 0.5), (0.25, 0.25, 0.25)

    def _build(self, augment):
        return Estimator.from_keras(
            nn.Sequential([nn.Conv2D(8, 3, activation="relu"),
                           nn.Flatten(), nn.Dense(4)]),
            loss="sparse_categorical_crossentropy", learning_rate=1e-2,
            seed=0, augment=augment)

    @pytest.mark.parametrize("backend", ["thread", "process"])
    def test_loss_parity_host_f32_vs_uint8_device(self, backend):
        if backend == "process" and not shm_pool.available():
            pytest.skip("process backend unavailable")
        _mesh()
        rng = np.random.default_rng(0)
        imgs = rng.integers(0, 256, (64, 8, 8, 3), dtype=np.uint8)
        labels = rng.integers(0, 4, 64).astype(np.int32)
        mean = np.asarray(self.MEAN, np.float32)
        std = np.asarray(self.STD, np.float32)

        def load_f32(i, rng=None):
            return {"x": (imgs[i].astype(np.float32) / 255.0 - mean) / std,
                    "y": labels[i]}

        def load_u8(i, rng=None):
            return {"x": imgs[i], "y": labels[i]}

        host = self._build(None)
        h_hist = host.fit(
            StreamingDataFeed(64, load_f32, batch_size=16, shuffle=False,
                              num_workers=2),
            epochs=2, batch_size=16, verbose=False)
        dev = self._build(DeviceAugment([DeviceNormalize(self.MEAN,
                                                         self.STD)]))
        d_hist = dev.fit(
            StreamingDataFeed(64, load_u8, batch_size=16, shuffle=False,
                              num_workers=2, workers=backend),
            epochs=2, batch_size=16, verbose=False)
        np.testing.assert_allclose(h_hist["loss"], d_hist["loss"],
                                   rtol=1e-5)
        assert not _shm_leaks()

    def test_augmented_eval_is_deterministic(self):
        mesh = _mesh()
        rng = np.random.default_rng(0)
        imgs = rng.integers(0, 256, (32, 8, 8, 3), dtype=np.uint8)
        labels = rng.integers(0, 4, 32).astype(np.int32)
        est = self._build(DeviceAugment([DeviceRandomCrop(6, 6),
                                         DeviceRandomFlip(),
                                         DeviceNormalize()]))
        est.fit((imgs, labels), epochs=1, batch_size=16, verbose=False)
        m1 = est.evaluate((imgs, labels), batch_size=16)
        m2 = est.evaluate((imgs, labels), batch_size=16)
        assert m1 == m2  # random stages are off at eval
