"""Asynchronous checkpoint manager (ISSUE 15, core/ckpt_manager.py):
non-blocking snapshots with explicit in-flight policies, delta
checkpoints for sharded-embedding tables, manifest-driven retention/GC,
and crash-consistent restore — plus the estimator, serving-registry and
CLI integrations."""

import json
import os
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from analytics_zoo_tpu.core import checkpoint as ckpt_io
from analytics_zoo_tpu.core import ckpt_manager as cm
from analytics_zoo_tpu.core import faults as faults_lib
from analytics_zoo_tpu.core import init_orca_context
from analytics_zoo_tpu.core import metrics as metrics_lib


def _tree(table_val=0.0, w_val=1.0, rows=16, dim=4):
    return {"params": {"w": jnp.full((3, 3), w_val),
                       "emb": {"sharded_embeddings":
                               jnp.full((rows, dim), table_val)}},
            "step": jnp.asarray(0)}


TP = "params/emb/sharded_embeddings"


def _assert_trees_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# -- core manager semantics ---------------------------------------------------

def test_full_then_delta_roundtrip_and_verify(tmp_path):
    d = str(tmp_path / "c")
    t = _tree()
    with cm.CheckpointManager(d) as m:
        assert m.save_async(t, step=1)
        m.flush()
        t["params"]["emb"]["sharded_embeddings"] = \
            t["params"]["emb"]["sharded_embeddings"].at[3].set(7.5)
        assert m.save_async(t, step=2, touched={TP: np.array([3])})
        m.flush()
        kinds = [r["kind"] for r in m.generations()]
        assert kinds == ["full", "delta"]
        assert m.verify() == []
        _assert_trees_equal(m.restore(), t)


def test_delta_restore_equals_full_restore_exactly(tmp_path):
    """Base + ordered deltas must reproduce the same bytes a full save
    of the final state does — params, scalars, and embedding rows."""
    da, db = str(tmp_path / "delta"), str(tmp_path / "full")
    t = _tree()
    with cm.CheckpointManager(da) as m:
        m.save(t, step=1)
        for i, step in enumerate(range(2, 5)):
            tbl = t["params"]["emb"]["sharded_embeddings"]
            t["params"]["emb"]["sharded_embeddings"] = \
                tbl.at[i].set(float(step))
            t["params"]["w"] = t["params"]["w"] + 1.0
            t["step"] = jnp.asarray(step)
            m.save(t, step=step, touched={TP: np.array([i])})
        assert [r["kind"] for r in m.generations()] == \
            ["full", "delta", "delta", "delta"]
        got = m.restore()
    with cm.CheckpointManager(db) as m2:
        m2.save(t, step=4)
        want = m2.restore()
    _assert_trees_equal(got, want)


def test_delta_rows_preserve_ml_dtypes_bit_exact(tmp_path):
    """bfloat16 tables: npz stores journaled rows as uint16 bit-pattern
    views (ckpt_io._npz_safe), so restore must reinterpret bits via the
    manifest's ``rows_dtype`` — a value cast would turn every journaled
    row into garbage numerics while the file still crc-verifies."""
    d = str(tmp_path / "c")
    t = {"params": {"emb": {"sharded_embeddings":
                            jnp.zeros((8, 4), jnp.bfloat16)}},
         "step": jnp.asarray(0)}
    with cm.CheckpointManager(d) as m:
        m.save(t, step=1)
        tbl = t["params"]["emb"]["sharded_embeddings"]
        t["params"]["emb"]["sharded_embeddings"] = \
            tbl.at[jnp.asarray([1, 3])].set(
                jnp.asarray([[0.1] * 4, [-2.5] * 4], jnp.bfloat16))
        m.save(t, step=2, touched={TP: np.array([1, 3])})
        rec = m.generations()[-1]
        assert rec["kind"] == "delta"
        assert rec["rows_dtype"] == {TP: "bfloat16"}
        assert m.verify() == []
        got = m.restore()
    got_tbl = np.asarray(got["params"]["emb"]["sharded_embeddings"])
    want_tbl = np.asarray(t["params"]["emb"]["sharded_embeddings"])
    assert got_tbl.dtype == want_tbl.dtype
    np.testing.assert_array_equal(got_tbl.view(np.uint16),
                                  want_tbl.view(np.uint16))


def test_latest_wins_supersedes_pending_and_keeps_newest(tmp_path):
    """Two saves queued behind a stalled writer: the second supersedes
    the first, and the merged journal restores the NEWEST state —
    including rows only the superseded window touched."""
    d = str(tmp_path / "c")
    t = _tree()
    with cm.CheckpointManager(d, inflight="latest-wins") as m:
        m.save(t, step=1)  # the base full
        faults = faults_lib.get_registry()
        faults.enable("checkpoint.slow_write", times=1, delay=1.0)
        t["params"]["emb"]["sharded_embeddings"] = \
            t["params"]["emb"]["sharded_embeddings"].at[2].set(2.0)
        assert m.save_async(t, step=2, touched={TP: np.array([2])})
        deadline = time.monotonic() + 30.0
        while not faults.fired("checkpoint.slow_write"):
            assert time.monotonic() < deadline, "writer never took step 2"
            time.sleep(0.005)
        # writer stalled on step 2; this one waits in pending...
        t["params"]["emb"]["sharded_embeddings"] = \
            t["params"]["emb"]["sharded_embeddings"].at[5].set(5.0)
        assert m.save_async(t, step=3, touched={TP: np.array([5])})
        # ...and is superseded before the writer ever sees it
        t["params"]["emb"]["sharded_embeddings"] = \
            t["params"]["emb"]["sharded_embeddings"].at[5].set(9.0)
        assert m.save_async(t, step=4, touched={TP: np.array([5])})
        m.flush()
        # step 3 was superseded while the writer held step 2, and the
        # newest survived
        assert [r["step"] for r in m.generations()] == [1, 2, 4]
        assert m.verify() == []
        got = m.restore()
        tbl = np.asarray(got["params"]["emb"]["sharded_embeddings"])
        assert tbl[5, 0] == 9.0 and tbl[2, 0] == 2.0
    snap = metrics_lib.get_registry().snapshot()
    assert snap.get("ckpt.skipped", 0) >= 1


def test_skip_policy_drops_while_in_flight(tmp_path):
    d = str(tmp_path / "c")
    t = _tree()
    with cm.CheckpointManager(d, inflight="skip") as m:
        faults_lib.get_registry().enable("checkpoint.slow_write",
                                         times=1, delay=0.4)
        assert m.save_async(t, step=1)
        assert m.save_async(t, step=2) is False  # writer busy: dropped
        m.flush()
        assert [r["step"] for r in m.generations()] == [1]
    assert metrics_lib.get_registry().snapshot().get("ckpt.skipped",
                                                     0) >= 1


def test_save_for_exit_reuses_inflight_snapshot(tmp_path):
    """The SIGTERM path: with a write already in flight, the exit save
    drains it and reports ITS step instead of paying a fresh device
    sync inside the grace window."""
    d = str(tmp_path / "c")
    t = _tree()
    with cm.CheckpointManager(d) as m:
        faults_lib.get_registry().enable("checkpoint.slow_write",
                                         times=1, delay=0.3)
        assert m.save_async(t, step=7)
        assert m.save_for_exit(t, step=9, timeout=30.0) == 7
        assert [r["step"] for r in m.generations()] == [7]
        # nothing in flight: a fresh blocking save reports its own step
        assert m.save_for_exit(t, step=9, timeout=30.0) == 9


def test_retention_gc_never_breaks_a_live_chain(tmp_path):
    """keep_last=1 with a delta chain: the base full must survive GC as
    long as a visible delta depends on it, and the swept generations are
    recorded in a ``gc`` manifest line before their bytes vanish."""
    d = str(tmp_path / "c")
    t = _tree()
    with cm.CheckpointManager(d, keep_last=1, compact_every=100) as m:
        m.save(t, step=1)
        for step in range(2, 6):
            t["params"]["emb"]["sharded_embeddings"] = \
                t["params"]["emb"]["sharded_embeddings"].at[step].set(
                    float(step))
            m.save(t, step=step, touched={TP: np.array([step])})
        assert m.verify() == []
        _assert_trees_equal(m.restore(), t)
        # now break the chain dependency: two fresh FULLS — the old
        # base + deltas become collectable, and only then are swept
        m.save(t, step=6, force_full=True)
        m.save(t, step=7, force_full=True)
        recs, gcd = cm.read_manifest(d)
        assert gcd, "GC never fired"
        on_disk = {n for n in os.listdir(d) if n != cm.MANIFEST}
        assert not any(r["dir"] in on_disk for r in recs
                       if r.get("kind") != "gc" and r["gen"] in gcd)
        assert m.verify() == []
        _assert_trees_equal(m.restore(), t)


def test_anchor_generations_survive_retention(tmp_path):
    d = str(tmp_path / "c")
    t = _tree()
    with cm.CheckpointManager(d, keep_last=2, anchor_every=3,
                              delta=False) as m:
        for step in range(8):
            t["step"] = jnp.asarray(step)
            m.save(t, step=step)
        steps = [r["step"] for r in m.generations()]
    # ordinals 0, 3, 6 are anchors; 6 and 7 are the last-2
    assert steps == [0, 3, 6, 7], steps


def test_torn_manifest_tail_is_ignored(tmp_path):
    d = str(tmp_path / "c")
    t = _tree()
    with cm.CheckpointManager(d) as m:
        m.save(t, step=1)
    # a kill -9 mid-append leaves a torn final line: reader skips it
    with open(os.path.join(d, cm.MANIFEST), "a") as f:
        f.write('{"kind": "full", "gen": "999999-dead", "ste')
    assert [r["step"] for r in cm.visible_generations(d)] == [1]
    tree, rec = cm.restore_path(d)
    assert rec["step"] == 1
    _assert_trees_equal(tree, t)


def test_corrupt_generation_falls_back_to_older(tmp_path):
    d = str(tmp_path / "c")
    t = _tree(w_val=1.0)
    with cm.CheckpointManager(d, delta=False) as m:
        m.save(t, step=1)
        t2 = _tree(w_val=2.0)
        m.save(t2, step=2)
        newest = m.generations()[-1]
    gen_dir = os.path.join(d, newest["dir"])
    victim = next(os.path.join(gen_dir, f) for f in os.listdir(gen_dir)
                  if f.endswith(".npz"))
    with open(victim, "r+b") as f:
        f.seek(0)
        f.write(b"\xde\xad\xbe\xef")
    errors, _ = cm.verify_path(d)
    assert errors, "corruption not detected"
    tree, rec = cm.restore_path(d)  # falls back to the clean gen
    assert rec["step"] == 1
    _assert_trees_equal(tree, t)


def test_write_failure_rewinds_chain_and_forces_full(tmp_path):
    d = str(tmp_path / "c")
    t = _tree()
    with cm.CheckpointManager(d, retries=1, retry_delay=0.01) as m:
        m.save(t, step=1)
        faults_lib.get_registry().enable("checkpoint.write_fail",
                                         times=1)
        t["params"]["emb"]["sharded_embeddings"] = \
            t["params"]["emb"]["sharded_embeddings"].at[1].set(1.0)
        with pytest.raises(OSError):
            m.save(t, step=2, touched={TP: np.array([1])})
        # failed write: the NEXT save must not chain onto the ghost
        t["params"]["emb"]["sharded_embeddings"] = \
            t["params"]["emb"]["sharded_embeddings"].at[2].set(2.0)
        m.save(t, step=3, touched={TP: np.array([2])})
        recs = m.generations()
        assert recs[-1]["kind"] == "full"  # forced: no dangling prev
        assert m.verify() == []
        _assert_trees_equal(m.restore(), t)
    snap = metrics_lib.get_registry().snapshot()
    assert snap.get("ckpt.write_errors", 0) >= 1


def test_compact_folds_deltas_into_fresh_full(tmp_path):
    d = str(tmp_path / "c")
    t = _tree()
    with cm.CheckpointManager(d, compact_every=100) as m:
        m.save(t, step=1)
        for step in (2, 3):
            t["params"]["emb"]["sharded_embeddings"] = \
                t["params"]["emb"]["sharded_embeddings"].at[step].set(
                    float(step))
            m.save(t, step=step, touched={TP: np.array([step])})
        assert m.generations()[-1]["kind"] == "delta"
        gen = m.compact()
        newest = m.generations()[-1]
        assert newest["kind"] == "full" and newest["gen"] == gen
        _assert_trees_equal(m.restore(), t)


def test_delta_cadence_promotes_full_every_compact_every(tmp_path):
    d = str(tmp_path / "c")
    t = _tree()
    with cm.CheckpointManager(d, compact_every=2, keep_last=100) as m:
        for step in range(6):
            t["step"] = jnp.asarray(step)
            m.save(t, step=step, touched={TP: np.array([0])})
        kinds = [r["kind"] for r in m.generations()]
    assert kinds == ["full", "delta", "delta", "full", "delta",
                     "delta"], kinds


# -- CLI ----------------------------------------------------------------------

def test_cli_ls_verify_compact(tmp_path, capsys):
    d = str(tmp_path / "c")
    t = _tree()
    with cm.CheckpointManager(d, compact_every=100) as m:
        m.save(t, step=1)
        m.save(t, step=2, touched={TP: np.array([0])})
    assert cm.main(["ls", d]) == 0
    out = capsys.readouterr().out
    assert "full" in out and "delta" in out
    assert cm.main(["verify", d]) == 0
    assert cm.main(["compact", d]) == 0
    assert cm.main(["verify", d]) == 0
    # corrupt the newest generation: verify must exit non-zero
    newest = cm.visible_generations(d)[-1]
    gen_dir = os.path.join(d, newest["dir"])
    victim = next(os.path.join(gen_dir, f) for f in os.listdir(gen_dir)
                  if f.endswith(".npz"))
    with open(victim, "r+b") as f:
        f.write(b"\xde\xad\xbe\xef")
    capsys.readouterr()
    assert cm.main(["verify", d]) == 1
    assert "ERROR" in capsys.readouterr().out


# -- estimator integration ----------------------------------------------------

def _ncf():
    from analytics_zoo_tpu.models import NeuralCF
    return NeuralCF(user_count=64, item_count=40, class_num=2,
                    user_embed=8, item_embed=8, hidden_layers=(16, 8),
                    mf_embed=8, sharded_embeddings=True)


def _ratings(n=256, seed=42):
    rng = np.random.default_rng(seed)
    x = np.stack([rng.integers(0, 64, n),
                  rng.integers(0, 40, n)], 1).astype(np.int32)
    y = (rng.random(n) < 0.5).astype(np.int32)
    return x, y


def test_estimator_async_equals_sync_bit_identical(tmp_path):
    """The restore-equivalence acceptance: the same fit checkpointed
    through the async manager and through the sync ckpt_io path must
    load back bit-identical — params, opt state, embedding rows."""
    from analytics_zoo_tpu.orca.learn import Estimator
    from analytics_zoo_tpu.orca.learn.trigger import SeveralIteration
    init_orca_context("local")
    x, y = _ratings()
    da, ds = str(tmp_path / "async"), str(tmp_path / "sync")
    kw = dict(loss="sparse_categorical_crossentropy", optimizer="adam",
              learning_rate=1e-2, seed=7)
    ea = Estimator.from_keras(_ncf(), model_dir=da,
                              checkpoint_async=True,
                              checkpoint_inflight="block", **kw)
    ea.fit((x, y), epochs=2, batch_size=64, verbose=False,
           checkpoint_trigger=SeveralIteration(2))
    es = Estimator.from_keras(_ncf(), model_dir=ds, **kw)
    es.fit((x, y), epochs=2, batch_size=64, verbose=False,
           checkpoint_trigger=SeveralIteration(2))
    ra = Estimator.from_keras(_ncf(), model_dir=da,
                              checkpoint_async=True, **kw)
    ra.load(da)
    rs = Estimator.from_keras(_ncf(), model_dir=ds, **kw)
    rs.load(ds)
    keys = ("params", "state", "opt_state")
    _assert_trees_equal(jax.device_get({k: ra._ts[k] for k in keys}),
                        jax.device_get({k: rs._ts[k] for k in keys}))
    assert int(np.asarray(ra._ts["step"])) == \
        int(np.asarray(rs._ts["step"]))
    assert ra._ckpt_mgr.verify() == []
    kinds = [r["kind"] for r in ra._ckpt_mgr.generations()]
    assert kinds[0] == "full" and "delta" in kinds, kinds


def test_estimator_async_restores_error_feedback_exactly(tmp_path):
    """int8 grad compression (dense model — sparse forbids it): the
    ``ts["ef"]`` residuals ride the async checkpoint bit-exactly."""
    import analytics_zoo_tpu.nn as nn
    from analytics_zoo_tpu.orca.learn import Estimator
    init_orca_context("local")
    rng = np.random.default_rng(0)
    x = rng.normal(size=(128, 4)).astype(np.float32)
    y = rng.normal(size=(128, 1)).astype(np.float32)
    d = str(tmp_path / "m")
    kw = dict(loss="mse", learning_rate=1e-3, seed=3,
              grad_compression="int8")
    est = Estimator.from_keras(
        nn.Sequential([nn.Dense(8, activation="relu"), nn.Dense(1)]),
        model_dir=d, checkpoint_async=True, **kw)
    est.fit((x, y), epochs=1, batch_size=32, verbose=False,
            checkpoint_trigger="every_epoch")
    est._ckpt_mgr.flush()
    est2 = Estimator.from_keras(
        nn.Sequential([nn.Dense(8, activation="relu"), nn.Dense(1)]),
        model_dir=d, checkpoint_async=True, **kw)
    est2.load(d)
    keys = ("params", "opt_state", "ef")
    _assert_trees_equal(jax.device_get({k: est._ts[k] for k in keys}),
                        jax.device_get({k: est2._ts[k] for k in keys}))


def test_checkpoint_async_resumes_legacy_sync_checkpoint(tmp_path):
    """checkpoint_async=True turned on over a model_dir holding a
    pre-manager sync checkpoint (ckpt_io layout, no MANIFEST.jsonl)
    must resume from it — not crash on a missing manifest — and the
    next trigger save starts the manifest with a full generation."""
    import analytics_zoo_tpu.nn as nn
    from analytics_zoo_tpu.orca.learn import Estimator

    def _model():
        return nn.Sequential([nn.Dense(8, activation="relu"),
                              nn.Dense(1)])

    init_orca_context("local")
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 4)).astype(np.float32)
    y = rng.normal(size=(64, 1)).astype(np.float32)
    d = str(tmp_path / "m")
    kw = dict(loss="mse", learning_rate=1e-3, seed=3)
    legacy = Estimator.from_keras(_model(), model_dir=d, **kw)
    legacy.fit((x, y), epochs=1, batch_size=32, verbose=False)
    legacy.save(d)
    assert ckpt_io.exists(d) and not cm.has_manifest(d)
    est = Estimator.from_keras(_model(), model_dir=d,
                               checkpoint_async=True, **kw)
    est.load(d)  # routes to the legacy layout, not the empty manifest
    _assert_trees_equal(jax.device_get(est._ts["params"]),
                        jax.device_get(legacy._ts["params"]))
    assert int(np.asarray(est._ts["step"])) == \
        int(np.asarray(legacy._ts["step"]))
    # auto_resume + trigger saves upgrade the dir to manifest format
    est2 = Estimator.from_keras(_model(), model_dir=d,
                                checkpoint_async=True, **kw)
    est2.fit((x, y), epochs=2, batch_size=32, verbose=False,
             checkpoint_trigger="every_epoch", auto_resume=True)
    est2._ckpt_mgr.flush()
    gens = est2._ckpt_mgr.generations()
    assert gens and gens[0]["kind"] == "full"
    assert est2._ckpt_mgr.verify() == []


def test_checkpoint_async_requires_model_dir():
    from analytics_zoo_tpu.orca.learn import Estimator
    import analytics_zoo_tpu.nn as nn
    init_orca_context("local")
    with pytest.raises(ValueError, match="model_dir"):
        Estimator.from_keras(nn.Dense(1), loss="mse",
                             checkpoint_async=True)


def test_bad_inflight_policy_rejected(tmp_path):
    with pytest.raises(ValueError, match="inflight"):
        cm.CheckpointManager(str(tmp_path / "c"), inflight="yolo")


def test_estimator_delta_generations_hold_touched_rows_not_tables(tmp_path):
    """What a delta generation is for: between fulls the manager journals
    the rows a save window touched, not the tables.  3,000 table rows,
    at most 64 touched a table between saves (2 steps of 32): every
    delta is under a quarter of a full generation's bytes, and the chain
    still verifies and restores."""
    from analytics_zoo_tpu.models import NeuralCF
    from analytics_zoo_tpu.orca.learn import Estimator
    from analytics_zoo_tpu.orca.learn.trigger import SeveralIteration
    init_orca_context("local")
    users, items = 2000, 1000
    rng = np.random.default_rng(0)
    x = np.stack([rng.integers(0, users, 256),
                  rng.integers(0, items, 256)], 1).astype(np.int32)
    y = (rng.random(256) < 0.5).astype(np.int32)

    def ncf():
        return NeuralCF(user_count=users, item_count=items, class_num=2,
                        user_embed=16, item_embed=16, hidden_layers=(16, 8),
                        mf_embed=16, sharded_embeddings=True)

    d = str(tmp_path / "m")
    kw = dict(loss="sparse_categorical_crossentropy", optimizer="adam",
              learning_rate=1e-2, seed=7, model_dir=d,
              checkpoint_async=True)
    est = Estimator.from_keras(ncf(), checkpoint_inflight="block", **kw)
    est.fit((x, y), epochs=1, batch_size=32, verbose=False,
            checkpoint_trigger=SeveralIteration(2))
    est._ckpt_mgr.flush()
    gens = est._ckpt_mgr.generations()
    fulls = [g["bytes"] for g in gens if g["kind"] == "full"]
    deltas = [g["bytes"] for g in gens if g["kind"] == "delta"]
    assert fulls and deltas, [g["kind"] for g in gens]
    assert max(deltas) < min(fulls) / 4, (deltas, fulls)
    assert est._ckpt_mgr.verify() == []
    rest = Estimator.from_keras(ncf(), **kw)
    rest.load(d)
    _assert_trees_equal(jax.device_get(rest._ts["params"]),
                        jax.device_get(est._ts["params"]))


# -- serving integration ------------------------------------------------------

def test_swap_from_checkpoint_serves_latest_generation(tmp_path):
    from analytics_zoo_tpu.serving import ModelRegistry
    d = str(tmp_path / "c")
    with cm.CheckpointManager(d, delta=False) as m:
        m.save(_tree(w_val=1.0), step=1)
        m.save(_tree(w_val=5.0), step=2)

    class _M:
        def __init__(self, w):
            self.w = w

        def predict(self, xs):
            return np.asarray(xs, np.float32) * self.w

    reg = ModelRegistry()
    reg.register("default", _M(0.0), version="v1")
    seen = {}

    def loader(tree, rec):
        seen.update(rec)
        return _M(float(np.asarray(tree["params"]["w"])[0, 0]))

    ver = reg.swap_from_checkpoint("default", loader, d)
    assert ver == f"ckpt-{seen['gen']}"
    assert seen["step"] == 2
    model, _, active = reg.resolve("default")
    assert active == ver
    np.testing.assert_allclose(model.predict(np.ones(2, np.float32)),
                               [5.0, 5.0])
    # an unchanged checkpoint refresh collides loudly, not silently
    with pytest.raises(ValueError, match="already has a version"):
        reg.swap_from_checkpoint("default", loader, d)
