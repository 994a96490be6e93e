"""Does the system still start on the chip?  One process, one pass.

    python chip_smoke.py              one TPU chip: device, link, kernel,
                                      train, serve — through the entry points
                                      a user calls
    python chip_smoke.py --chips 4    four chips: BERT-base fit under dp=4 and
                                      fsdp=4 against the same steps on one
                                      device, and no other phase
    python chip_smoke.py --rehearse [--chips 4]
                                      the same control flow at a tiny size on
                                      whatever platform JAX finds (CPU; four
                                      virtual devices with --chips 4; the
                                      Pallas kernel in interpret mode)

Every line of standard output is one JSON object naming ``platform`` and
``device_kind``.  Times and rates in them are smoke observations — one
reading each, taken while checking that things work — not a benchmark.  Any
phase that fails raises, and the exit code is then non-zero.  Without
``--rehearse`` a platform other than ``tpu`` is such a failure, before any
result is printed.  The last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import importlib
import importlib.metadata
import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import jaxlib
import numpy as np

import analytics_zoo_tpu.nn as nn
from analytics_zoo_tpu.core import (init_orca_context, metrics,
                                    stop_orca_context, trace)
from analytics_zoo_tpu.data import shard_batch
from analytics_zoo_tpu.data.stream import StreamingDataFeed
from analytics_zoo_tpu.models import BERT, ResNet
from analytics_zoo_tpu.native import NativeQueue
from analytics_zoo_tpu.nn import attention
from analytics_zoo_tpu.nn.module import Module
from analytics_zoo_tpu.ops import flash_attention, mha_reference
from analytics_zoo_tpu.orca.learn import Estimator
from analytics_zoo_tpu.serving import (ClusterServing, InferenceModel,
                                       InputQueue, OutputQueue,
                                       enable_aot_cache)

#: BERT-base as published (models/bert.py defaults) at seq 512, global
#: batch 32 = micro 4 x accum 8.
REAL = dict(vocab=30522, hidden=768, layers=12, heads=12, seq=512,
            micro=4, accum=8, image=224, classes=1000, width=64,
            flash_bh=(4, 12), flash_d=64, flash_t=((2048, False),
                                                   (4096, True)),
            mha_heads=12, mha_dim=768, link_batch=128, requests=24)
#: Rehearsal: same control flow, sizes a CPU finishes in seconds.
TINY = dict(vocab=1000, hidden=64, layers=2, heads=4, seq=32,
            micro=4, accum=2, image=32, classes=10, width=8,
            flash_bh=(1, 2), flash_d=16, flash_t=((256, False),
                                                  (200, True)),
            mha_heads=2, mha_dim=32, link_batch=4, requests=6)

#: Losses under dp=4 / fsdp=4 must equal the one-device losses to the
#: tolerance __graft_entry__.dryrun_multichip holds its modes to.
MULTICHIP_RTOL = 1e-3
#: Flash kernel (bf16 in and out) against the float32 reference, as a share
#: of the reference's largest magnitude: bf16 keeps 8 bits of mantissa.
FLASH_TOL = 2e-2
#: bf16 ResNet-18 replies against the float32 forward, same measure: 18
#: layers of bf16-rounded weights and activations.
SERVE_TOL = 5e-2


class BertMLM(Module):
    """``models.BERT`` trunk + vocabulary head, bf16 activations."""

    def __init__(self, s: dict):
        super().__init__()
        self.vocab = s["vocab"]
        # dropout off: the loss on repeated data must fall, and four chips
        # must reproduce one chip's losses, neither through dropout noise
        self.bert = BERT(vocab_size=s["vocab"], hidden_size=s["hidden"],
                         n_layers=s["layers"], n_heads=s["heads"],
                         max_position=s["seq"], dropout=0.0,
                         remat_attention=True, dtype=jnp.bfloat16)

    def forward(self, scope, ids):
        h = scope.child(self.bert, ids, name="bert").astype(jnp.bfloat16)
        return scope.child(nn.Dense(self.vocab), h, name="mlm_head")


class ServeNet(Module):
    """uint8 NHWC -> on-device normalize -> ResNet-18 classifier."""

    def __init__(self, classes: int = 1000, width: int = 64):
        super().__init__()
        self.net = ResNet(depth=18, class_num=classes, width=width)

    def forward(self, scope, x):
        x = (x.astype(jnp.float32) - 127.0) * (1.0 / 64.0)
        return scope.child(self.net, x, name="resnet")


class Smoke:
    def __init__(self, sizes: dict, seed: int):
        self.s = sizes
        self.seed = seed
        dev = jax.devices()[0]
        self.device = {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())}

    def emit(self, phase: str, **fields) -> None:
        print(json.dumps({"phase": phase,
                          "platform": self.device["platform"],
                          "device_kind": self.device["kind"], **fields}),
              flush=True)

    def peak_bytes(self):
        stats = jax.devices()[0].memory_stats()  # None on the CPU backend
        return stats and stats.get("peak_bytes_in_use")

    # -- phases ---------------------------------------------------------------

    def phase_device(self, cache_dir: str) -> None:
        native = NativeQueue(1).is_native
        self.emit("device", count=self.device["count"],
                  jax=jax.__version__, jaxlib=jaxlib.__version__,
                  libtpu=importlib.metadata.version("libtpu"),
                  compile_cache_dir=cache_dir,
                  compile_cache_entries_before=_cache_entries(cache_dir),
                  native_queue=native)
        if not native:
            raise RuntimeError(
                "NativeQueue fell back to queue.Queue (no g++?): every "
                "feed and serving number would mean something else")

    def phase_link(self) -> None:
        """Five bare host->device copies of one uint8 image batch."""
        n, size = self.s["link_batch"], self.s["image"]
        batch = np.random.default_rng(self.seed).integers(
            0, 256, (n, size, size, 3), dtype=np.uint8)
        jax.block_until_ready(jax.device_put(batch))  # first-use set-up
        mbps = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(jax.device_put(batch))
            mbps.append(batch.nbytes / (time.perf_counter() - t0) / 1e6)
        mbps.sort()
        self.emit("link", bytes=batch.nbytes, readings=5,
                  mb_per_s_median=mbps[2], mb_per_s_min=mbps[0],
                  mb_per_s_max=mbps[-1])

    def phase_kernel(self) -> None:
        """Flash attention forward and gradient against mha_reference, and
        MultiHeadAttention(use_flash="auto") taking the kernel."""
        on_tpu = self.device["platform"] == "tpu"
        if not on_tpu:
            # rehearsal: the kernel, interpreted.  (import_module: the
            # package re-exports a function under the module's own name)
            importlib.import_module(
                "analytics_zoo_tpu.ops.flash_attention").INTERPRET = True
        (b, h), d = self.s["flash_bh"], self.s["flash_d"]
        key = jax.random.PRNGKey(self.seed)
        for t, causal in self.s["flash_t"]:
            q, k, v, g = (jax.random.normal(kk, (b, t, h, d), jnp.bfloat16)
                          for kk in jax.random.split(key, 4))

            def loss(q, k, v, g):
                out = flash_attention(q, k, v, causal=causal)
                return (out.astype(jnp.float32)
                        * g.astype(jnp.float32)).sum(), out

            flash = jax.jit(jax.value_and_grad(loss, (0, 1, 2),
                                               has_aux=True))
            lowered = flash.lower(q, k, v, g).as_text()
            if on_tpu and "tpu_custom_call" not in lowered:
                raise RuntimeError(
                    f"flash_attention T={t} lowered without the TPU kernel")
            t0 = time.perf_counter()
            (_, out), grads = jax.block_until_ready(flash(q, k, v, g))
            first_s = time.perf_counter() - t0

            # float32 reference, one batch row at a time: the materialized
            # [H, T, T] logits of all rows at T=4096 would not leave room
            @jax.jit
            def reference(q, k, v, g):
                out, vjp = jax.vjp(
                    lambda q, k, v: mha_reference(q, k, v, causal=causal),
                    q, k, v)
                return (out,) + vjp(g)

            errs = {"out": 0.0, "dq": 0.0, "dk": 0.0, "dv": 0.0}
            with jax.default_matmul_precision("highest"):
                for i in range(b):
                    refs = reference(*(x[i:i + 1].astype(jnp.float32)
                                       for x in (q, k, v, g)))
                    for name, got, ref in zip(errs, (out,) + grads, refs):
                        err = float(jnp.max(jnp.abs(
                            got[i:i + 1].astype(jnp.float32) - ref))
                            / jnp.max(jnp.abs(ref)))
                        errs[name] = max(errs[name], err)
            self.emit("kernel", op="flash_attention", shape=[b * h, t, d],
                      causal=causal, dtype="bfloat16",
                      tpu_custom_call="tpu_custom_call" in lowered,
                      max_abs_err_over_ref_max=errs, tolerance=FLASH_TOL,
                      first_call_s=first_s)
            bad = {n: e for n, e in errs.items()
                   if not e <= FLASH_TOL}  # NaN fails too
            if bad:
                raise RuntimeError(f"flash_attention T={t} disagrees with "
                                   f"mha_reference: {bad}")

        # the layer users call: "auto" must take the kernel at this length
        t = attention.FLASH_AUTO_MIN_SEQ
        heads, dim = self.s["mha_heads"], self.s["mha_dim"]
        x = jax.random.normal(key, (1, t, dim), jnp.bfloat16)
        auto = nn.MultiHeadAttention(heads, use_flash="auto")
        dense = nn.MultiHeadAttention(heads, use_flash=False)
        variables = jax.jit(lambda r, a: auto.init(r, a))(key, x)
        fwd = jax.jit(lambda v, a: auto.apply(v, a)[0])
        lowered = fwd.lower(variables, x).as_text()
        if on_tpu and "tpu_custom_call" not in lowered:
            raise RuntimeError("MultiHeadAttention(use_flash='auto') did "
                               f"not take the kernel at T={t}")
        got = fwd(variables, x).astype(jnp.float32)
        ref = jax.jit(lambda v, a: dense.apply(v, a)[0])(
            variables, x).astype(jnp.float32)
        err = float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))
        self.emit("kernel", op="MultiHeadAttention(use_flash='auto')",
                  shape=[1, t, dim], heads=heads,
                  tpu_custom_call="tpu_custom_call" in lowered,
                  max_abs_err_over_ref_max=err, tolerance=FLASH_TOL)
        if not err <= FLASH_TOL:
            raise RuntimeError(f"flash and dense attention disagree: {err}")

    def _bert_fit(self, epochs_per_fit, steps_per_epoch, **est_kwargs):
        """BERT through Estimator.fit over a StreamingDataFeed (default
        feed backend); returns (estimator, [(history, wall s, trace id)
        per fit]).  The same seeded samples repeat every epoch."""
        s = self.s
        global_batch = s["micro"] * s["accum"]
        # Zipfian token ids, as text has: a few optimizer steps can learn
        # the frequent ones, so the loss on repeated data falls visibly
        p = 1.0 / np.arange(1, s["vocab"] + 1)
        p /= p.sum()

        def load_sample(i: int, rng=None) -> dict:
            r = np.random.default_rng([self.seed, i])
            ids = r.choice(s["vocab"], s["seq"], p=p).astype(np.int32)
            return {"x": ids, "y": ids}

        est = Estimator.from_keras(
            BertMLM(s), loss="sparse_categorical_crossentropy",
            optimizer="adamw", learning_rate=1e-4, grad_accum=s["accum"],
            seed=self.seed, profile=True, **est_kwargs)
        fits = []
        for epochs in epochs_per_fit:
            feed = StreamingDataFeed(
                num_samples=steps_per_epoch * global_batch,
                load_sample=load_sample, batch_size=global_batch,
                shuffle=False, seed=self.seed)
            t0 = time.perf_counter()
            hist = est.fit(feed, epochs=epochs, batch_size=global_batch,
                           verbose=False)
            # fit() read every step's loss; wait for the last update too
            jax.block_until_ready(jax.live_arrays())
            fits.append((hist, time.perf_counter() - t0, est.trace_id))
        return est, fits

    def phase_train(self) -> None:
        s = self.s
        steps_per_epoch, warm_epochs, timed_epochs = 4, 1, 2
        reg = metrics.get_registry()
        steps0 = reg.snapshot().get("train.steps", 0)
        est, fits = self._bert_fit([warm_epochs, timed_epochs],
                                   steps_per_epoch)
        (_, first_fit_s, first_tid), (_, timed_fit_s, _) = fits
        losses = [l for hist, _, _ in fits for l in hist["loss"]]
        steps = reg.snapshot()["train.steps"] - steps0
        # the first step's span: model init + the train step's compile
        first_step = next(r for r in trace.find(first_tid)
                          if r.where == "train.step")
        timed_steps = timed_epochs * steps_per_epoch
        step_s = timed_fit_s / timed_steps
        tokens = s["micro"] * s["accum"] * s["seq"]
        self.emit("train", model="BERT + vocab head", hidden=s["hidden"],
                  layers=s["layers"], heads=s["heads"], vocab=s["vocab"],
                  seq=s["seq"], global_batch=s["micro"] * s["accum"],
                  micro_batch=s["micro"], grad_accum=s["accum"],
                  optimizer_steps=steps, epoch_losses=losses,
                  train_step_compiles=est.compile_count,
                  first_fit_s=first_fit_s,
                  cold_compile_s=first_step.dur_ms / 1000,
                  note="smoke observation, not a benchmark: one timed "
                       "fit() of %d steps incl. feed start-up" % timed_steps,
                  step_ms=1000 * step_s, tokens_per_s=tokens / step_s,
                  peak_bytes_in_use=self.peak_bytes())
        want = (warm_epochs + timed_epochs) * steps_per_epoch
        if steps != want:
            raise RuntimeError(f"took {steps} optimizer steps, not {want}")
        if not np.all(np.isfinite(losses)):
            raise RuntimeError(f"non-finite loss: {losses}")
        if not losses[-1] < losses[0]:
            raise RuntimeError(f"loss did not fall on repeated data: "
                               f"{losses}")
        if est.compile_count != 1:
            raise RuntimeError(f"train step compiled {est.compile_count} "
                               "times; expected once, in the first fit")

    def phase_serve(self) -> None:
        s = self.s
        size, buckets, n_clients = s["image"], (1, 4, 16), 2
        per_client = s["requests"] // n_clients
        model = ServeNet(s["classes"], s["width"])
        rows = np.random.default_rng(self.seed).integers(
            0, 256, (s["requests"], size, size, 3), dtype=np.uint8)
        variables = jax.jit(lambda r, x: model.init(r, x))(
            jax.random.PRNGKey(self.seed), rows[:1])
        with jax.default_matmul_precision("highest"):
            reference = np.asarray(jax.jit(
                lambda v, x: model.apply(v, x, training=False)[0])(
                    variables, rows))

        im = InferenceModel(batch_buckets=buckets).load(
            model, variables, dtype=jnp.bfloat16)
        compile_s = {}
        for b in buckets:
            t0 = time.perf_counter()
            im.warm([(size, size, 3)], dtype=np.uint8, buckets=[b])
            compile_s[str(b)] = time.perf_counter() - t0
        warm_compiles = im.compile_count

        replies, latency_ms, errors = {}, [], []

        def client(lo: int) -> None:
            try:
                inq = InputQueue(port=srv.port)
                outq = OutputQueue(input_queue=inq)
                for i in range(lo, lo + per_client):
                    t0 = time.perf_counter()
                    uid = inq.enqueue("smoke", t=rows[i])
                    out = outq.query(uid, timeout=120.0)
                    if out is None:
                        raise RuntimeError(f"request {i} timed out")
                    latency_ms.append(1000 * (time.perf_counter() - t0))
                    replies[i] = np.asarray(out)
                inq.close()
            except Exception as e:  # noqa: BLE001 — re-raised below
                errors.append(e)

        threads_before = set(threading.enumerate())
        srv = ClusterServing(im, batch_size=16, batch_timeout_ms=5).start()
        try:
            clients = [threading.Thread(target=client,
                                        args=(c * per_client,))
                       for c in range(n_clients)]
            for c in clients:
                c.start()
            for c in clients:
                c.join()
            stats = srv.stats()
        finally:
            srv.stop()
        deadline = time.monotonic() + 10.0
        while (left := [t.name for t in threading.enumerate()
                        if t not in threads_before and t.is_alive()]
               ) and time.monotonic() < deadline:
            time.sleep(0.05)

        n = n_clients * per_client
        got = np.stack([replies[i].reshape(-1) for i in sorted(replies)]
                       ) if replies else np.zeros((0,))
        err = (float(np.max(np.abs(got - reference[sorted(replies)]))
                     / np.max(np.abs(reference))) if len(replies) else None)
        latency_ms.sort()
        self.emit("serve", model="uint8 %dx%d -> ResNet-18, bf16" % (size,
                                                                   size),
                  buckets=list(buckets), cold_compile_s=compile_s,
                  requests=n, answered=len(replies),
                  client_errors=len(errors),
                  round_trip_ms_p50=latency_ms[len(latency_ms) // 2]
                  if latency_ms else None,
                  max_abs_err_over_ref_max=err, tolerance=SERVE_TOL,
                  compiles_at_warm=warm_compiles,
                  compiles_after_traffic=im.compile_count,
                  server_mean_batch=stats["mean_batch_size"],
                  threads_left=left, peak_bytes_in_use=self.peak_bytes())
        if errors:
            raise errors[0]
        if len(replies) != n:
            raise RuntimeError(f"{len(replies)} of {n} requests answered")
        if not err <= SERVE_TOL:
            raise RuntimeError(f"replies differ from the float32 forward "
                               f"by {err} of its range")
        if warm_compiles != len(buckets) or im.compile_count != len(buckets):
            raise RuntimeError(
                f"compile count {warm_compiles} at warm(), "
                f"{im.compile_count} after traffic; expected "
                f"{len(buckets)} and no more")
        if left:
            raise RuntimeError(f"ClusterServing.stop() left threads: {left}")

    def phase_multichip(self, chips: int) -> None:
        """The same BERT fit on one device, under dp=chips and under
        fsdp=chips: equal losses, shards on distinct devices."""
        s = self.s
        global_batch = s["micro"] * s["accum"]
        results = {}
        for name, mesh_shape, sharding in (
                ("one_device", {"data": 1}, "dp"),
                (f"dp{chips}", {"data": chips}, "dp"),
                (f"fsdp{chips}", {"fsdp": chips}, "fsdp")):
            stop_orca_context()
            mesh = init_orca_context("local", mesh_shape=mesh_shape)
            # one optimizer step an epoch: history is then per step
            est, [(hist, wall, tid)] = self._bert_fit([3], 1,
                                                      sharding=sharding)
            # wall of each one-step epoch (the first holds the compile)
            epoch_ms = [r.dur_ms for r in trace.find(tid)
                        if r.where == "train.epoch"]
            # where the feed's placer puts a batch ...
            placed = shard_batch(
                {"x": np.zeros((global_batch, s["seq"]), np.int32)}, mesh)
            batch_devices = {sh.device.id
                             for sh in placed["x"].addressable_shards}
            # ... and where fit() left the parameters (inspection only: no
            # public accessor keeps the placement)
            results[name] = dict(
                losses=hist["loss"], batch_devices=len(batch_devices),
                train_step_compiles=est.compile_count, fit_s=wall,
                epoch_ms=epoch_ms,
                **_param_placement(est._ts["params"]))
            self.emit("multichip", config=name, mesh=mesh_shape,
                      sharding=sharding, **results[name])
            del est
        stop_orca_context()

        base = results["one_device"]["losses"]
        if not base[-1] < base[0]:
            raise RuntimeError(f"loss did not fall on repeated data: {base}")
        for name, r in results.items():
            if not np.all(np.isfinite(r["losses"])):
                raise RuntimeError(f"{name}: non-finite loss {r['losses']}")
            np.testing.assert_allclose(
                r["losses"], base, rtol=MULTICHIP_RTOL,
                err_msg=f"{name} losses diverged from one device")
            if name != "one_device" and r["batch_devices"] != chips:
                raise RuntimeError(f"{name}: batch on {r['batch_devices']} "
                                   f"device(s), not {chips}")
        f = results[f"fsdp{chips}"]
        if f["param_devices"] != chips or \
                f.get("largest_split_leaf_devices") != chips or \
                abs(f["largest_split_leaf_share_per_device"]
                    - 1 / chips) > 1e-6:
            raise RuntimeError(
                f"fsdp: parameters on {f['param_devices']} device(s), "
                f"largest split leaf on "
                f"{f.get('largest_split_leaf_devices')}; expected {chips} "
                f"and 1/{chips} of it on each")
        # "about a quarter" of the parameter bytes on a device: a quarter of
        # every leaf that is split, plus the leaves left whole (0.2502 for
        # BERT-base on four v5e chips, PR 21)
        if not f["param_share_per_device"] < 0.5:
            raise RuntimeError(
                f"fsdp keeps {f['param_share_per_device']:.3f} of the "
                "parameter bytes on one device")


def _param_placement(params) -> dict:
    """Bytes of a parameter tree by device, from its leaves' shards."""
    leaves = jax.tree_util.tree_leaves(params)
    per_device: dict = {}
    for leaf in leaves:
        for sh in leaf.addressable_shards:
            per_device[sh.device.id] = (per_device.get(sh.device.id, 0)
                                        + sh.data.nbytes)
    total = sum(l.nbytes for l in leaves)
    split = [l for l in leaves if not l.sharding.is_fully_replicated]
    out = dict(param_devices=len(per_device), param_bytes=total,
               param_bytes_replicated=total - sum(l.nbytes for l in split),
               param_share_per_device=max(per_device.values()) / total)
    if split:
        big = max(split, key=lambda l: l.nbytes)
        out.update(
            largest_split_leaf_shape=list(big.shape),
            largest_split_leaf_devices=len(
                {sh.device.id for sh in big.addressable_shards}),
            largest_split_leaf_share_per_device=max(
                sh.data.nbytes for sh in big.addressable_shards)
            / big.nbytes)
    return out


def _cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the multi-chip path and its one-device "
                         "comparison, and no other phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever platform JAX finds")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.rehearse and args.chips > 1 and \
            "xla_force_host_platform_device_count" not in os.environ.get(
                "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}")

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found platform {platform!r}, not a TPU "
              "(--rehearse runs the tiny CPU rehearsal)", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    smoke = Smoke(TINY if args.rehearse else REAL, args.seed)
    # places the compile cache (as init_orca_context does) and keeps EVERY
    # executable in it, so a second run shows what a warm cache saves
    cache_dir = enable_aot_cache()
    smoke.phase_device(cache_dir)
    if args.chips > 1:
        smoke.phase_multichip(args.chips)
    else:
        init_orca_context("local")
        smoke.phase_link()
        smoke.phase_kernel()
        smoke.phase_train()
        smoke.phase_serve()
        stop_orca_context()
    smoke.emit("cache", compile_cache_dir=cache_dir,
               compile_cache_entries_after=_cache_entries(cache_dir),
               peak_bytes_in_use=smoke.peak_bytes())
    print(json.dumps({"ok": True, "device": smoke.device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
