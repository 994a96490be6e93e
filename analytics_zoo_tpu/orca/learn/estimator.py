"""The unified Estimator: fit/evaluate/predict/save/load over a device mesh.

Reference (SURVEY.md §2.4, §3.2–3.4): Orca's Estimator façade dispatched to
five per-framework backends — PyTorchRayEstimator (Ray actors + Gloo
all-reduce, pyzoo/zoo/orca/learn/pytorch/pytorch_ray_estimator.py),
TF2Estimator (Ray + MultiWorkerMirroredStrategy, .../tf2/tf_ray_estimator.py),
TF1 TFOptimizer and BigDL/OpenVINO paths — each spinning up worker processes
that re-created the model and averaged gradients over TCP per step.

TPU-native collapse: ONE estimator.  The model is a pure function; the train
step is jit-compiled once over the global mesh; the batch arrives sharded
along the ``data``/``fsdp`` axes, so XLA inserts the gradient all-reduce as an
ICI ``psum`` fused into the step — the entire §3.2 actor/Gloo call stack
becomes a single compiled program.  Per-worker data sharding is DataFeed's
job; multi-host coordination is jax.distributed (core.context).

API parity: ``Estimator.from_keras(...)`` / ``from_fn(...)``, then
``fit(data, epochs, batch_size) / evaluate / predict / save / load /
get_model``, with TensorBoard-style summaries and checkpoint triggers.
"""

from __future__ import annotations

import contextlib
import logging
import math
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from analytics_zoo_tpu.core import checkpoint as ckpt_io
from analytics_zoo_tpu.core import get_mesh
from analytics_zoo_tpu.core.config import ZooConfig
from analytics_zoo_tpu.core import faults as faults_lib
from analytics_zoo_tpu.core import metrics as telemetry
from analytics_zoo_tpu.core import trace as trace_lib
from analytics_zoo_tpu.core.context import heartbeat, mesh_scope
from analytics_zoo_tpu.core.summary import SummaryWriter
from analytics_zoo_tpu.data import (EpochEnd, PrefetchIterator, as_feed,
                                    batch_sharding, make_placer,
                                    shard_batch)
from analytics_zoo_tpu.nn import losses as losses_lib
from analytics_zoo_tpu.nn import metrics as metrics_lib
from analytics_zoo_tpu.nn.module import Module
from . import optimizers as opt_lib
from .trigger import Trigger

logger = logging.getLogger("analytics_zoo_tpu")

#: Valid values for ``ZooEstimator(nan_policy=...)``.
NAN_POLICIES = ("warn", "skip_step", "rollback", "raise")


def _hlo_text_of(step: Any, mesh: Any, *args: Any) -> Callable[[], str]:
    """A zero-argument callable that returns the compiled HLO text of the
    jitted ``step`` for ``args`` (``trace_lib.register_program``).  It keeps
    the function, the mesh the step was built for and the arguments' SHAPES
    with their shardings, taken here, before the call that donates them —
    never the arrays.  Called, it lowers and compiles as ``fit()``'s own
    dispatch did, with that mesh as ``get_mesh()``'s answer (the context
    may be stopped by then, and none is started); with the persistent
    compile cache on, the compile is a hit on the executable that ran."""
    def abstract(leaf: Any) -> jax.ShapeDtypeStruct:
        placed = isinstance(leaf, jax.Array)   # else a host batch's array
        return jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, weak_type=placed and leaf.weak_type,
            sharding=leaf.sharding if placed and leaf.committed else None)
    shapes = jax.tree_util.tree_map(abstract, args)

    def hlo_text() -> str:
        with mesh_scope(mesh):
            return step.lower(*shapes).compile().as_text()
    return hlo_text


def _jit_cache_size(fn: Any) -> int:
    """How many executables a jitted function has compiled so far —
    the per-step compile-event probe (``InferenceModel.compile_count``'s
    pattern applied to the training step)."""
    return int(fn._cache_size())


class NonFiniteLossError(RuntimeError):
    """A training step produced a non-finite loss and the configured
    ``nan_policy`` could not (or was told not to) heal it."""

    def __init__(self, step: int, message: Optional[str] = None):
        super().__init__(message
                         or f"non-finite loss at train step {step}")
        self.step = step


class Estimator:
    """Factory façade (reference: per-framework ``Estimator.from_*`` in
    pyzoo/zoo/orca/learn/*/estimator.py)."""

    @staticmethod
    def from_keras(model: Module, loss: Any, optimizer: Any = "adam",
                   learning_rate: Optional[Any] = None,
                   metrics: Optional[Sequence[Any]] = None,
                   **kwargs: Any) -> "ZooEstimator":
        """An estimator over an ``nn.Module`` (Keras-style model)."""
        return ZooEstimator(model=model, loss=loss, optimizer=optimizer,
                            learning_rate=learning_rate, metrics=metrics,
                            **kwargs)

    # The reference's from_torch/from_graph/from_bigdl all reduce to "a model
    # function + loss + optimizer"; foreign-model import lives in
    # analytics_zoo_tpu.models.net loaders.
    from_fn = from_keras

    @staticmethod
    def from_torch(*, model: Any, loss: Any, optimizer: Any = "adam",
                   example_input: Any = None,
                   learning_rate: Optional[Any] = None,
                   metrics: Optional[Sequence[Any]] = None,
                   **kwargs: Any) -> "ZooEstimator":
        """Name-parity shim for ported reference scripts (reference:
        ``Estimator.from_torch(model=..., loss=..., optimizer=...)`` —
        pyzoo/zoo/orca/learn/pytorch/estimator.py).  A ``torch.nn.Module``
        (or TorchScript path) is converted via ``Net.load_torch`` and then
        trains natively; already-native ``nn.Module``s pass through so
        scripts can migrate incrementally.

        ``example_input``: one example batch (torch layout), required for
        torch modules — conversion traces per-layer shapes with it."""
        if not isinstance(model, Module):
            from analytics_zoo_tpu.models.net import Net
            if example_input is None:
                raise ValueError(
                    "from_torch needs example_input= (one example batch, "
                    "torch layout) to convert a torch module")
            model = Net.load_torch(model, example_input)
        return ZooEstimator(model=model, loss=loss, optimizer=optimizer,
                            learning_rate=learning_rate, metrics=metrics,
                            **kwargs)

    @staticmethod
    def from_graph(model: Any, loss: Any, optimizer: Any = "adam",
                   learning_rate: Optional[Any] = None,
                   metrics: Optional[Sequence[Any]] = None,
                   **kwargs: Any) -> "ZooEstimator":
        """Name-parity shim for TF-graph reference scripts (reference:
        ``Estimator.from_graph`` — pyzoo/zoo/orca/learn/tf/estimator.py).
        Accepts a tf.keras model (object or saved path), converted via
        ``Net.load_tf``; native ``nn.Module``s pass through."""
        if not isinstance(model, Module):
            from analytics_zoo_tpu.models.net import Net
            model = Net.load_tf(model)
        return ZooEstimator(model=model, loss=loss, optimizer=optimizer,
                            learning_rate=learning_rate, metrics=metrics,
                            **kwargs)


class ZooEstimator:
    """The single concrete estimator."""

    #: Process-wide device-work lock.  Two estimators dispatching jit
    #: programs from different threads (automl thread-pool trials)
    #: intermittently wedge XLA:CPU — observed as trial threads stuck
    #: forever inside train/eval steps, both at compile AND at plain
    #: execution.  fit/evaluate/predict therefore serialize their WHOLE
    #: bodies on this reentrant lock (coarse on purpose: a per-step lock
    #: would need a device sync inside every step to prevent overlapped
    #: executions, taxing the single-threaded hot path).  Concurrent
    #: trials still overlap on everything they do OUTSIDE those calls —
    #: window rolling, feature prep, metric math in trial_fn — and one
    #: device computation at a time is the single-TPU-pod reality anyway.
    _device_lock = threading.RLock()

    def __init__(self, model: Module, loss: Any, optimizer: Any = "adam",
                 learning_rate: Optional[Any] = None,
                 metrics: Optional[Sequence[Any]] = None,
                 grad_clip_norm: Optional[float] = None,
                 seed: int = 0,
                 log_dir: Optional[str] = None,
                 app_name: str = "train",
                 model_dir: Optional[str] = None,
                 sharding: Any = "dp",
                 aux_loss_weight: float = 0.01,
                 profile_dir: Optional[str] = None,
                 profile_steps: Any = (10, 20),
                 preemption_checkpoint: bool = False,
                 preemption_sync_every: int = 10,
                 frozen: Any = None,
                 grad_accum: int = 1,
                 checkpoint_retries: int = 3,
                 nan_policy: Optional[str] = None,
                 nan_max_rollbacks: int = 3,
                 augment: Any = None,
                 grad_compression: Optional[str] = None,
                 embedding_lr: Optional[float] = None,
                 profile: Any = None,
                 checkpoint_async: bool = False,
                 checkpoint_inflight: str = "latest-wins",
                 checkpoint_keep_last: int = 3,
                 checkpoint_anchor_every: int = 0,
                 checkpoint_delta: bool = True,
                 checkpoint_compact_every: int = 8):
        """``sharding``: parameter-sharding strategy over the mesh —
        "dp" (replicate params; batch sharding only, the reference's only
        mode), "tp" (Megatron tensor-parallel rules over the ``model`` axis),
        "fsdp" (ZeRO-3 over the ``fsdp`` axis), "tp+fsdp", "2d" (the
        data × model pod layout: batch sharded along ``data``, tp rules
        along ``model`` — build the mesh with
        ``init_orca_context(mesh_shape="2d")``), or an explicit list of
        parallel.ShardingRule.  A strategy whose mesh axis is missing
        trims to replication with a one-time WARNING (see
        docs/distributed-training.md).

        ``grad_compression``: wire width of the data-parallel gradient
        all-reduce (EQuARX ladder, PAPERS.md) — the dominant communication
        cost of scale-out training:

        - ``None`` (default): feature off — today's implicit-psum step,
          bit-for-bit unchanged, zero overhead.
        - ``"none"``: uncompressed but METERED — the same step numerics
          (bit-identical loss history, the bisection baseline) plus the
          ``train.grad_bytes`` counter.
        - ``"bf16"``: each batch shard's gradient contribution rounds to
          bfloat16 before the reduce (2 bytes/param on the wire, f32
          accumulation).
        - ``"int8"``: per-shard symmetric int8 quantization with
          error-feedback residuals carried in the train state
          (``ts["ef"]``, checkpointed) — 4× less collective traffic; safe
          once past the first few warmup steps of very sharp loss
          landscapes (see docs/distributed-training.md).

        Compressed modes decompose the batch into one slice per mesh batch
        shard inside the jit step (vmap) so each shard quantizes its OWN
        contribution — the numerics of a real quantized collective.
        Requires ``grad_accum=1``.

        ``frozen``: transfer-learning freeze (reference: GraphNet.freezeUpTo
        — SURVEY §2.3 Net loaders): a list of param-path prefixes
        (e.g. ``["bert"]``) or a predicate ``fn(path_str) -> bool``; matched
        parameters get zero updates (optax.multi_transform + set_to_zero),
        which XLA folds into the compiled step.

        ``grad_accum``: micro-batch gradient accumulation — each train
        step splits its batch into ``grad_accum`` equal micro-batches,
        scans forward/backward over them accumulating f32 gradients, and
        applies ONE optimizer update on the mean.  For models whose loss
        is a per-example mean (no cross-example coupling), this equals a
        single step at the full batch exactly (asserted in tests); with
        BatchNormalization each micro-batch normalizes by its OWN
        statistics and running stats update once per micro-batch — the
        standard grad-accumulation semantics, not bit-identical to the
        full-batch step.  On bandwidth-bound models it amortizes the
        optimizer's full f32 parameter/moment sweep — profiled at ~26% of
        a BERT-base step — over ``grad_accum`` micro-batches, and keeps
        each micro-batch at its best-fusing size.

        On a mesh with S > 1 batch shards (``data`` x ``fsdp``) micro-batch
        ``i`` is the same rows ``i*B/grad_accum ...`` of the global batch
        as on one device; only their placement follows the mesh: each
        micro-batch's rows are sharded over the batch axes, so a chip
        computes ``B/(grad_accum*S)`` rows of every micro-batch and none
        twice (one all-to-all of the input batch a step puts them there).
        Losses, per-micro-batch rng and BatchNormalization statistics (the
        whole micro-batch's, reduced across the shards) are one device's
        for the same seed.  The gradients meet in ONE all-reduce a step,
        after the accumulation loop: the compiler moves the reduce of each
        ``sum + g`` out of the scan (XLA's while-loop all-reduce code
        motion, on TPU and GPU; an embedding table's scatter-add gradient
        is the exception and is reduced once a micro-batch; ``fsdp``
        reduce-scatters into its sharded sum once a micro-batch, as ZeRO
        does).  Where ``B/grad_accum`` does not divide into S, GSPMD
        places the split itself and may compute rows more than once.

        ``nan_policy``: training-loop self-healing for non-finite loss /
        gradients (None = unguarded, zero overhead):

        - ``"skip_step"``: the guard compiles INTO the train step — if the
          loss or gradient norm is non-finite, params/state/optimizer stay
          at their pre-step values (only ``step`` advances) and the
          on-device ``bad_steps`` counter increments.  No per-step host
          sync; the counter is read once per epoch.
        - ``"warn"``: log and count the bad step, keep training (the step
          HAS been applied — use this for visibility only).
        - ``"rollback"``: restore the latest ``model_dir`` checkpoint and
          continue from it; at most ``nan_max_rollbacks`` times, then
          raises.  Requires ``model_dir`` and a checkpoint trigger (or
          preemption checkpoints) so there is something to roll back to.
        - ``"raise"``: raise ``NonFiniteLossError`` immediately.

        ``warn``/``rollback``/``raise`` read the loss on the host every
        step (one device sync per step); ``skip_step`` does not.  Bad-step
        counts surface as ``history["bad_steps"]`` (per epoch), the
        ``bad_steps`` summary scalar, and ``est.bad_steps`` (total).

        ``augment``: a ``data.DeviceAugment`` chain (or any callable
        ``(x, key, training) -> x``) compiled INTO the jit steps — the
        streaming-input split: host workers ship compact uint8 batches,
        normalize/random-crop/flip run on device, keyed from the train
        step's per-step rng (reproducible, scheduling-independent).
        Train steps run the chain with a fresh fold of the step rng;
        evaluate/predict run it deterministically (center crop, no flip,
        normalize applies).

        ``embedding_lr``: row learning rate for ``ShardedEmbedding``
        tables (parallel/embedding.py).  Sparse tables update by plain
        SGD scatter-add on the batch's unique rows — stateful optimizers
        would need full ``[rows, dim]`` moment tensors, recreating the
        memory problem the sharded table exists to avoid — so their rate
        is decoupled from the dense optimizer's schedule.  Default: the
        numeric ``learning_rate`` if one was given, else 1e-3.  Ignored
        for models without sparse tables.

        ``profile``: the step profiler (ISSUE 9) — ``None`` (off, zero
        overhead), ``True``, or a dict:

        - **compile events**: every step that grew the train step's
          executable cache (a retrace — new input shape/dtype, changed
          static config) bumps ``train.compiles`` and records a
          ``train.compile`` span, so "why was step 847 slow?" has an
          answer (``InferenceModel.compile_count``'s pattern, applied
          to training);
        - **device trace**: dict keys ``trace_dir`` + ``trace_steps``
          ``(k, k+n)`` capture a ``jax.profiler`` trace for steps
          [k, k+n) — the same machinery as the ``profile_dir`` /
          ``profile_steps`` constructor args, reachable from the one
          ``profile=`` knob.

        Any other key in the dict raises ``ValueError``."""
        self.model = model
        self.loss_fn = losses_lib.get(loss)
        self.tx = opt_lib.get(optimizer, learning_rate, grad_clip_norm)
        self.frozen = frozen
        self._tx_wrapped = False
        self.metrics = [metrics_lib.get(m) for m in (metrics or [])]
        self.sharding = sharding
        self.aux_loss_weight = aux_loss_weight
        if grad_accum < 1:
            raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")
        self.grad_accum = grad_accum
        self.seed = seed
        self.model_dir = model_dir
        # transient checkpoint-write failures (shared-filesystem blips)
        # are retried with backoff before a save gives up — critical for
        # the preemption window, where there is no second chance
        self.checkpoint_retries = max(1, checkpoint_retries)
        if nan_policy is not None and nan_policy not in NAN_POLICIES:
            raise ValueError(f"nan_policy must be one of {NAN_POLICIES} "
                             f"or None, got {nan_policy!r}")
        self.nan_policy = nan_policy
        self.nan_max_rollbacks = max(0, nan_max_rollbacks)
        self.augment = augment
        if grad_compression is None:
            from analytics_zoo_tpu.core.context import config_default
            grad_compression = config_default("grad_compression", None)
        if grad_compression is not None:
            from analytics_zoo_tpu.parallel.util import GRAD_COMPRESSION
            if grad_compression not in GRAD_COMPRESSION:
                raise ValueError(
                    f"grad_compression must be one of {GRAD_COMPRESSION} "
                    f"or None, got {grad_compression!r}")
            if grad_compression != "none" and self.grad_accum > 1:
                raise ValueError(
                    "grad_compression='bf16'/'int8' requires grad_accum=1 "
                    "(the compressed collective already decomposes the "
                    "batch per shard)")
        self.grad_compression = grad_compression
        self.embedding_lr = embedding_lr
        self._learning_rate = learning_rate
        self._sparse_paths: tuple = ()  # ShardedEmbedding table paths
        self._grad_bytes_step = 0   # analytic wire bytes per train step
        self._warned_mesh = False
        self.bad_steps = 0       # total non-finite steps seen (host mirror)
        self._rollbacks = 0
        self._writer = (SummaryWriter(log_dir, app_name)
                        if log_dir else None)
        self._ts: Optional[Dict[str, Any]] = None  # train state pytree
        self._train_step = None
        self._eval_step = None
        self._pred_step = None
        self._epoch = 0
        self._py_step = 0  # host-side mirror of ts["step"] (no device sync)
        # device-side counters (layers' ``counters`` state) at their last
        # read; None = never read, and a fresh state starts them at zero
        self._counters_seen: Optional[Dict[str, Any]] = None
        # jax.profiler integration (SURVEY.md §5.1 tracing parity): capture
        # a device trace for steps [start, end) into profile_dir, viewable
        # in TensorBoard/XProf/Perfetto
        self.profile_dir = profile_dir
        self.profile_steps = tuple(profile_steps)
        self._profiling = False
        # step profiler (ISSUE 9): compile events; trace_dir / trace_steps
        # in the dict ride the jax.profiler machinery above
        self._profile_on = bool(profile)
        if profile:
            pcfg = {} if profile is True else dict(profile)
            unknown = sorted(set(pcfg) - {"trace_dir", "trace_steps"})
            if unknown:
                raise ValueError(
                    f"profile= takes the keys 'trace_dir' and 'trace_steps', "
                    f"got {unknown}")
            if pcfg.get("trace_dir"):
                self.profile_dir = pcfg["trace_dir"]
                self.profile_steps = tuple(
                    pcfg.get("trace_steps", self.profile_steps))
        self.compile_count = 0  # train-step executables compiled (profile=)
        # preemption-safe training (core/failover.py): SIGTERM → consensus
        # checkpoint to model_dir → raise Preempted
        self._preempt = None
        if preemption_checkpoint:
            if model_dir is None:
                raise ValueError(
                    "preemption_checkpoint=True needs model_dir")
            from analytics_zoo_tpu.core.failover import PreemptionGuard
            self._preempt = PreemptionGuard(preemption_sync_every).install()
        # async checkpointing (ISSUE 15, core/ckpt_manager.py): trigger
        # saves, preemption saves, rollback and auto_resume all route
        # through one CheckpointManager on model_dir.  Default OFF — the
        # sync ckpt_io path below is byte-for-byte the pre-15 behavior.
        self._ckpt_mgr = None
        self._track_touched = False
        if checkpoint_async:
            if model_dir is None:
                raise ValueError("checkpoint_async=True needs model_dir")
            if jax.process_count() > 1:
                # multihost saves are collective (every process writes
                # its own shards); a background thread on process 0
                # cannot run that protocol alone — fall back to the
                # inline collective save rather than deadlock
                logger.warning(
                    "checkpoint_async=True is single-host only; "
                    "multihost run falls back to synchronous saves")
            else:
                from analytics_zoo_tpu.core.ckpt_manager import (
                    CheckpointManager)
                self._ckpt_mgr = CheckpointManager(
                    model_dir, keep_last=checkpoint_keep_last,
                    anchor_every=checkpoint_anchor_every,
                    inflight=checkpoint_inflight,
                    compact_every=checkpoint_compact_every,
                    retries=self.checkpoint_retries,
                    delta=checkpoint_delta)
                # journal (table, ids, rows) deltas between full saves:
                # needs the in-jit touched-row bitmask (cleared in
                # _ensure_initialized when the model has no tables)
                self._track_touched = bool(checkpoint_delta)

    # -- state ----------------------------------------------------------------

    def _wrap_frozen_tx(self, params: Any) -> None:
        """One-time: wrap the optimizer so frozen params get zero updates
        (with their own empty optimizer state — adamw weight decay must not
        touch them either)."""
        if self._tx_wrapped or not self.frozen:
            return
        # match on path-component boundaries so frozen=["bert"] does not
        # also freeze siblings like "bert_head/..." or "bert2/..."
        pred = (self.frozen if callable(self.frozen)
                else lambda p, pre=tuple(self.frozen):
                any(p == x or p.startswith(x + "/") for x in pre))
        from analytics_zoo_tpu.parallel.sharding import _key_str
        labels = jax.tree_util.tree_map_with_path(
            lambda path, l: "freeze"
            if pred("/".join(_key_str(k) for k in path)) else "train",
            params)
        if not any(l == "freeze"
                   for l in jax.tree_util.tree_leaves(labels)):
            logger.warning("frozen=%r matched no parameters", self.frozen)
        self.tx = optax.multi_transform(
            {"train": self.tx, "freeze": optax.set_to_zero()}, labels)
        self._tx_wrapped = True

    def _check_sparse_support(self) -> None:
        """Feature-interaction guardrails for ShardedEmbedding models:
        fail at init with an actionable message instead of silently
        training wrong (or densifying the very gradient the sparse path
        exists to avoid)."""
        if not self._sparse_paths:
            return
        if self.grad_accum > 1:
            raise ValueError(
                "grad_accum > 1 is not supported with ShardedEmbedding "
                f"tables (found {list(self._sparse_paths)}): the "
                "accumulation scan would need a dense [rows, dim] "
                "gradient carry, defeating the sparse update.  Use "
                "grad_accum=1 (the deduped gather already keeps the "
                "per-step embedding traffic small).")
        if self.grad_compression in ("bf16", "int8"):
            raise ValueError(
                "grad_compression='bf16'/'int8' is not supported with "
                f"ShardedEmbedding tables (found "
                f"{list(self._sparse_paths)}): sparse row gradients "
                "always travel f32 and never enter the quantized "
                "collective.  Use grad_compression=None (or 'none' for "
                "wire metering of the dense leaves).")
        if self.frozen is not None:
            pred = (self.frozen if callable(self.frozen)
                    else lambda p, pre=tuple(self.frozen):
                    any(p == x or p.startswith(x + "/") for x in pre))
            if any(pred(p) for p in self._sparse_paths):
                raise ValueError(
                    "frozen= matches a ShardedEmbedding table "
                    f"({[p for p in self._sparse_paths if pred(p)]}); "
                    "sparse tables bypass the optax freeze machinery — "
                    "remove them from frozen= (they can be excluded from "
                    "updates by setting embedding_lr=0.0).")

    def _embed_lr(self) -> float:
        if self.embedding_lr is not None:
            return float(self.embedding_lr)
        if isinstance(self._learning_rate, (int, float)):
            return float(self._learning_rate)
        return 1e-3

    def _ensure_initialized(self, example_x: Any) -> None:
        if self._ts is not None:
            return
        mesh = get_mesh()
        rng = jax.random.PRNGKey(self.seed)
        if self.augment is not None:
            # the model sees POST-augment batches (a crop changes the
            # spatial shape); init with the deterministic chain so the
            # parameter shapes match what the train step applies
            example_x = self.augment(example_x, None, training=False)
        # init under jit: ONE compiled program instead of hundreds of
        # eager per-op dispatches.  Eager init was the trigger surface
        # for an intermittent native abort in XLA:CPU under dispatch load
        # (big-model init inside test_models).
        variables = jax.jit(
            lambda r, x: self.model.init(r, x, training=True)
        )(rng, example_x)
        from analytics_zoo_tpu.parallel import embedding as emb_lib
        self._sparse_paths = emb_lib.sparse_paths(variables["params"])
        self._check_sparse_support()
        # sparse tables never see the dense optimizer — freeze labels and
        # opt_state are built over the dense part only (identical to the
        # full tree when no ShardedEmbedding is present)
        dense_of = (lambda p: emb_lib.split_sparse(p)[0]) \
            if self._sparse_paths else (lambda p: p)
        self._wrap_frozen_tx(dense_of(variables["params"]))
        self._warn_strategy_mesh_mismatch(mesh)
        rules = _resolve_sharding_rules(self.sharding)
        replicated = NamedSharding(mesh, P())
        if rules:
            from analytics_zoo_tpu.parallel import shard_variables
            variables = shard_variables(variables, rules, mesh)
            # jit propagates the param shardings into mu/nu etc., so the
            # optimizer state is sharded exactly like its parameters
            opt_state = _ensure_on_mesh(
                jax.jit(self.tx.init)(dense_of(variables["params"])), mesh)
            params = variables["params"]
        else:
            # "dp": replicate params; batches arrive sharded, so jit's
            # propagation yields psum'd (replicated) gradients
            params = jax.device_put(variables["params"], replicated)
            opt_state = jax.device_put(
                self.tx.init(dense_of(variables["params"])), replicated)
        ts = {"params": params,
              "state": jax.device_put(variables["state"], replicated),
              "opt_state": opt_state,
              "step": jax.device_put(jnp.zeros((), jnp.int32), replicated),
              "rng": jax.device_put(rng, replicated),
              # on-device non-finite-step counter (nan_policy="skip_step"
              # increments it inside the jit step; others leave it at the
              # host mirror's value) — in ts so it checkpoints with step
              "bad_steps": jax.device_put(jnp.zeros((), jnp.int32),
                                          replicated)}
        if self.grad_compression == "int8":
            # error-feedback residuals: one [n_shards, ...] f32 tensor per
            # param, dim 0 sharded over the batch axes so each mesh slice
            # keeps ITS OWN quantization error — in ts so it checkpoints
            # (and donates) with the rest of the train state
            ts["ef"] = self._init_error_feedback(params, mesh)
        # delta checkpoints (ISSUE 15): one bool bitmask per sparse table
        # marking rows touched since the last accepted save.  Lives in ts
        # so the jit step updates it in place (donated with the rest) —
        # the sparse path already dedups touched ids, so marking them is
        # one scatter per table.  NEVER checkpointed (stripped in save).
        self._track_touched = bool(self._track_touched
                                   and self._sparse_paths)
        if self._track_touched:
            ts["touched"] = self._init_touched(ts["params"])
        self._ts = ts
        self._build_steps(mesh)

    def _init_touched(self, params: Any) -> Dict[str, Any]:
        from analytics_zoo_tpu.parallel import embedding as emb_lib
        _dense, tables = emb_lib.split_sparse(params)
        return {tp: jnp.zeros((t.shape[0],), dtype=bool)
                for tp, t in tables.items()}

    def _collect_touched(self) -> Optional[Dict[str, np.ndarray]]:
        """Touched-row ids per table since the last accepted save, keyed
        by FULL-TREE path (the manager splits the whole train state, so
        table paths carry the ``params/`` prefix)."""
        masks = (self._ts or {}).get("touched")
        if not masks:
            return None
        return {"params/" + tp: np.nonzero(np.asarray(mask))[0]
                for tp, mask in masks.items()}

    def _reset_touched(self) -> None:
        masks = (self._ts or {}).get("touched")
        if masks:
            self._ts["touched"] = {tp: jnp.zeros_like(m)
                                   for tp, m in masks.items()}

    def _init_error_feedback(self, params: Any, mesh) -> Any:
        from analytics_zoo_tpu.parallel.util import (batch_shard_count,
                                                     batch_shard_spec)
        s = batch_shard_count(mesh)

        def zero(p):
            z = np.zeros((s,) + tuple(p.shape), np.float32)
            return jax.device_put(z, NamedSharding(
                mesh, batch_shard_spec(mesh, z.ndim)))

        return jax.tree_util.tree_map(zero, params)

    def _warn_strategy_mesh_mismatch(self, mesh) -> None:
        """One-time heads-up when a named strategy asks for mesh axes the
        current mesh does not have: the rules trim to replication (the
        portable behavior), but silently training dp when the user asked
        for "2d" is a debugging trap worth a WARNING."""
        if self._warned_mesh or not isinstance(self.sharding, str):
            return
        self._warned_mesh = True
        parts = set(self.sharding.replace(" ", "").split("+"))

        def size(ax: str) -> int:
            return mesh.shape[ax] if ax in mesh.axis_names else 1

        missing = []
        if parts & {"tp", "2d"} and size("model") <= 1:
            missing.append("model")
        if "fsdp" in parts and size("fsdp") <= 1:
            missing.append("fsdp")
        if "2d" in parts and size("data") <= 1:
            missing.append("data")
        if missing:
            # remediation hint: a dict covering EVERY missing axis, with
            # one wildcard batch axis so it spans any device count (a bare
            # strategy name would be wrong for composites like "tp+fsdp"
            # and circular when for_strategy already degraded a "2d" mesh
            # that couldn't fit this device count)
            hint = {"fsdp": 0} if "fsdp" in missing else {"data": 0}
            if "model" in missing:
                hint["model"] = 2
            logger.warning(
                "sharding=%r but the mesh has no sized %s axis (mesh %s): "
                "affected rules trim to replication and training proceeds "
                "data-parallel.  Build the mesh with init_orca_context("
                "mesh_shape=%r) to get the requested layout (needs a "
                "device count the fixed axes divide).",
                self.sharding, "/".join(missing),
                dict(zip(mesh.axis_names, mesh.devices.shape)), hint)

    def _build_steps(self, mesh) -> None:
        model, loss_fn, tx = self.model, self.loss_fn, self.tx
        metrics = self.metrics
        aux_w = self.aux_loss_weight

        accum = self.grad_accum
        guard_skip = self.nan_policy == "skip_step"
        guard_host = self.nan_policy in ("warn", "rollback", "raise")
        aug = self.augment
        comp = self.grad_compression
        compress_wire = comp in ("bf16", "int8")
        sparse_paths = self._sparse_paths
        embed_lr = self._embed_lr()
        if sparse_paths:
            from analytics_zoo_tpu.parallel import embedding as emb_lib
        from analytics_zoo_tpu.parallel.util import batch_shard_count
        nshards = batch_shard_count(mesh)
        if compress_wire:
            from analytics_zoo_tpu.parallel.util import (
                batch_shard_spec, compressed_allreduce)

        def split_micro(l, is_feature):
            """``[B, ...]`` -> ``[accum, B/accum, ...]``: micro-batch ``i``
            is rows ``i*B/accum ...``, as on one device.  The batch arrives
            sharded on dim 0; left alone, the reshape puts that sharding on
            the accumulation axis, which the scan needs whole, and GSPMD
            then replicates the micro-batch's rows (every chip computed 2x
            its share at accum 2, the whole micro-batch at accum >= the
            shard count).  So each micro-batch's ROWS are pinned to the
            mesh's batch axes, as the feed would place a batch of that
            size ('x' keeps its ``seq`` sharding): one all-to-all of the
            input batch a step, and no row computed twice.  Where the rows
            do not divide into the shards, GSPMD places them as before."""
            l = l.reshape((accum, l.shape[0] // accum) + l.shape[1:])
            if nshards > 1 and l.shape[1] % nshards == 0:
                rows = batch_sharding(
                    mesh, l.ndim - 1,
                    seq_dim_size=(l.shape[2] if is_feature and l.ndim > 2
                                  else None))
                l = jax.lax.with_sharding_constraint(
                    l, NamedSharding(mesh, P(None, *rows.spec)))
            return l

        def train_step(ts, batch):
            step_rng = jax.random.fold_in(ts["rng"], ts["step"])
            new_ef = None

            def lossf(params, xb, yb, state, rng):
                if aug is not None:
                    # device-side fused augmentation (data/augment.py):
                    # uint8 batch in, keyed per step — XLA fuses the
                    # normalize into the first layer's prologue
                    a_rng, rng = jax.random.split(rng)
                    xb = aug(xb, a_rng, training=True)
                out, new_state = model.apply(
                    {"params": params, "state": state}, xb,
                    training=True, rng=rng)
                with jax.named_scope("loss"):
                    loss = loss_fn(out, yb)
                    # auxiliary losses recorded in state (e.g. MoE
                    # load-balance)
                    loss = loss + aux_w * _collect_aux_losses(new_state)
                return loss, new_state

            if accum > 1:
                if batch["x"].shape[0] % accum:
                    raise ValueError(
                        f"batch size {batch['x'].shape[0]} is not divisible "
                        f"by grad_accum={accum}")
                # micro-batch accumulation: scan fwd/bwd over accum equal
                # slices, ONE optimizer update on the mean gradient —
                # numerically the full-batch step, minus accum-1 optimizer
                # sweeps.  On several batch shards each micro-batch's
                # gradient is a sum over the shards; the replicated carry
                # would have it reduced once a micro-batch, but XLA's
                # while-loop code motion moves the all-reduce of a plain
                # ``gsum + g`` out of the loop, to once a step (checked in
                # tests/test_tpu_compile.py)
                micro = {k: jax.tree_util.tree_map(
                    lambda l, f=(k == "x"): split_micro(l, f), v)
                    for k, v in batch.items()}
                gzero = jax.tree_util.tree_map(jnp.zeros_like, ts["params"])

                def body(carry, mb):
                    gsum, state, i = carry
                    (loss, new_state), grads = jax.value_and_grad(
                        lossf, has_aux=True)(
                            ts["params"], mb["x"], mb["y"], state,
                            jax.random.fold_in(step_rng, i))
                    gsum = jax.tree_util.tree_map(jnp.add, gsum, grads)
                    return (gsum, new_state, i + 1), loss

                with jax.named_scope("grad_accum"):
                    (gsum, new_state, _), losses = jax.lax.scan(
                        body,
                        (gzero, ts["state"], jnp.zeros((), jnp.int32)),
                        micro)
                grads = jax.tree_util.tree_map(lambda g: g / accum, gsum)
                loss_val = losses.mean()
            elif compress_wire:
                # quantized gradient collective (EQuARX ladder): split the
                # global batch into one slice per mesh batch shard, vmap
                # per-shard forward/backward, then reduce the per-shard
                # gradients through the compressed wire — each shard
                # quantizes its OWN contribution (with its own scale and,
                # for int8, its own error-feedback residual), exactly as a
                # quantized AllReduce would on hardware.  XLA turns the
                # trailing sum-over-shards into the actual collective.
                b = _first_leaf(batch["x"]).shape[0]
                if b % nshards:
                    raise ValueError(
                        f"global batch {b} is not divisible into the "
                        f"mesh's {nshards} batch shard(s); "
                        "grad_compression needs equal per-shard slices")

                def stack(l):
                    l = l.reshape((nshards, l.shape[0] // nshards)
                                  + l.shape[1:])
                    return jax.lax.with_sharding_constraint(
                        l, NamedSharding(mesh,
                                         batch_shard_spec(mesh, l.ndim)))

                micro = jax.tree_util.tree_map(stack, batch)

                def shard_grads(mb, rng):
                    (loss, st), g = jax.value_and_grad(
                        lossf, has_aux=True)(ts["params"], mb["x"],
                                             mb["y"], ts["state"], rng)
                    return loss, st, g

                rngs = jax.vmap(lambda i: jax.random.fold_in(step_rng, i)
                                )(jnp.arange(nshards))
                shard_losses, states, gshards = jax.vmap(shard_grads)(
                    micro, rngs)
                grads, new_ef = compressed_allreduce(gshards, comp,
                                                     ef=ts.get("ef"))
                new_state = jax.tree_util.tree_map(_merge_shard_leaf,
                                                   states)
                loss_val = shard_losses.mean()
            elif sparse_paths:
                # sparse-embedding step: differentiate the DENSE params
                # plus per-lookup "taps" on the gathered unique rows —
                # the tap gradient IS the [unique, dim] row gradient, so
                # the backward pass never materializes (and the optimizer
                # never shadows) a [rows, dim] dense table gradient.
                dense_p, tables = emb_lib.split_sparse(ts["params"])
                # abstract pass (zero runtime): each lookup's static
                # unique-buffer shape, keyed by table application
                tap_shapes = emb_lib.record_tap_shapes(
                    lambda: lossf(ts["params"], batch["x"], batch["y"],
                                  ts["state"], step_rng))
                taps = {k: jnp.zeros(s.shape, s.dtype)
                        for k, s in tap_shapes.items()}

                def lossf_sparse(dense_params, taps, xb, yb, state, rng):
                    merged = emb_lib.merge_sparse(dense_params, tables)
                    with emb_lib.inject_taps(taps) as uniqs:
                        loss, new_state = lossf(merged, xb, yb, state,
                                                rng)
                    return loss, (new_state, uniqs)

                ((loss_val, (new_state, uniqs)),
                 (grads, tap_grads)) = jax.value_and_grad(
                    lossf_sparse, argnums=(0, 1), has_aux=True)(
                        dense_p, taps, batch["x"], batch["y"],
                        ts["state"], step_rng)
            else:
                (loss_val, new_state), grads = jax.value_and_grad(
                    lossf, has_aux=True)(ts["params"], batch["x"],
                                         batch["y"], ts["state"], step_rng)
            new_touched = None
            if sparse_paths:
                # dense optimizer over dense params; sparse tables update
                # below by scatter-add on the unique rows only
                with jax.named_scope("optimizer"):
                    updates, opt_state = tx.update(grads, ts["opt_state"],
                                                   dense_p)
                    dense_new = optax.apply_updates(dense_p, updates)
                new_tables = dict(tables)
                if "touched" in ts:
                    new_touched = dict(ts["touched"])
                for key, g in tap_grads.items():
                    tp = emb_lib.table_path_of(key)
                    with jax.named_scope("optimizer"):
                        new_tables[tp] = new_tables[tp].at[uniqs[key]].add(
                            (-embed_lr * g).astype(new_tables[tp].dtype))
                    if new_touched is not None:
                        # delta checkpoints (ISSUE 15): mark the batch's
                        # unique rows dirty.  The dedup buffer pads with
                        # id 0, and a skip_step guard leaves rows
                        # unmodified — both make the mask a SUPERSET of
                        # truly-changed rows, which only costs journal
                        # bytes, never correctness.
                        new_touched[tp] = new_touched[tp].at[
                            uniqs[key]].set(True)
                params = emb_lib.merge_sparse(dense_new, new_tables)
                grads_for_norm = (grads, tap_grads)
            else:
                with jax.named_scope("optimizer"):
                    updates, opt_state = tx.update(grads, ts["opt_state"],
                                                   ts["params"])
                    params = optax.apply_updates(ts["params"], updates)
                grads_for_norm = grads
            bad_steps = ts["bad_steps"]
            if guard_skip:
                # in-jit self-healing: a non-finite loss or gradient keeps
                # params/state/opt_state at their pre-step values.  Must
                # live inside the compiled step — donate_argnums=0 means
                # the pre-step buffers are gone once the call returns, so
                # a host-side "skip" could never restore them.
                ok = jnp.isfinite(loss_val) & jnp.isfinite(
                    optax.global_norm(grads_for_norm))

                def keep(new, old):
                    return jnp.where(ok, new, old)

                params = jax.tree_util.tree_map(keep, params, ts["params"])
                new_state = jax.tree_util.tree_map(keep, new_state,
                                                   ts["state"])
                opt_state = jax.tree_util.tree_map(keep, opt_state,
                                                   ts["opt_state"])
                if new_ef is not None:
                    # a skipped step must not bank the bad step's
                    # quantization error into the residual either
                    new_ef = jax.tree_util.tree_map(keep, new_ef,
                                                    ts["ef"])
                bad_steps = bad_steps + jnp.where(ok, 0, 1).astype(jnp.int32)
            elif guard_host:
                # host policies read only the loss — fold the gradient
                # check into it so a finite-loss / non-finite-grad step
                # (backward-only overflow) is not missed: report NaN, and
                # the host-side policy reacts exactly as for a NaN loss
                loss_val = jnp.where(
                    jnp.isfinite(optax.global_norm(grads_for_norm)),
                    loss_val, jnp.nan)
            new_ts = {"params": params, "state": new_state,
                      "opt_state": opt_state, "step": ts["step"] + 1,
                      "rng": ts["rng"], "bad_steps": bad_steps}
            if "ef" in ts:
                new_ts["ef"] = new_ef if new_ef is not None else ts["ef"]
            if "touched" in ts:
                new_ts["touched"] = (new_touched if new_touched is not None
                                     else ts["touched"])
            return new_ts, loss_val

        def eval_step(ts, batch):
            xb = batch["x"]
            if aug is not None:
                xb = aug(xb, None, training=False)
            out, _ = model.apply({"params": ts["params"],
                                  "state": ts["state"]}, xb,
                                 training=False)
            mask = batch.get("mask")
            if mask is None:
                mask = jnp.ones((_first_leaf(out).shape[0],), jnp.float32)
            # per-example loss (vmap over the mean-reducing loss) so padded
            # rows can be weighted out exactly; reductions over the global
            # sharded batch compile to psums — sums are GLOBAL, not
            # host-local, in multihost runs
            per_ex = _per_example_loss(loss_fn, out, batch["y"])
            stats = [jnp.stack([(per_ex * mask).sum(), mask.sum()])]
            for m in metrics:
                stats.append(_metric_update(m, out, batch["y"], mask))
            return stats

        def pred_step(ts, x):
            if aug is not None:
                x = aug(x, None, training=False)
            out, _ = model.apply({"params": ts["params"],
                                  "state": ts["state"]}, x, training=False)
            return out

        self._train_step = jax.jit(train_step, donate_argnums=0)
        self._eval_step = jax.jit(eval_step)
        self._pred_step = jax.jit(pred_step)
        if comp is not None:
            from analytics_zoo_tpu.parallel.util import grad_wire_bytes
            metered = self._ts["params"]
            if sparse_paths:
                # sparse row grads never ride the dense collective — the
                # wire meter covers the dense leaves only
                metered = emb_lib.split_sparse(metered)[0]
            self._grad_bytes_step = grad_wire_bytes(metered, comp)

    # -- training -------------------------------------------------------------

    def fit(self, data: Any, epochs: int = 1, batch_size: int = 32,
            validation_data: Any = None,
            checkpoint_trigger: Union[Trigger, str, None] = None,
            feature_cols: Optional[Sequence[str]] = None,
            label_cols: Optional[Sequence[str]] = None,
            auto_resume: bool = False,
            prefetch: Optional[int] = None,
            verbose: bool = True) -> Dict[str, List[float]]:
        """Train; returns history {"loss": [...], "val_<metric>": [...]}.

        ``data``: DataFeed, XShards, (x, y) tuple, or {"x","y"} dict.
        ``batch_size`` is global (split across the mesh's batch axes).
        ``auto_resume``: restore from ``model_dir`` if a checkpoint exists
        (the restart half of preemption-safe training).
        ``prefetch``: feed-lookahead depth (default
        ``ZooConfig.prefetch``, 2) — a background thread runs the feed's
        host batch indexing, ``shard_batch`` and the ``device_put``
        dispatch of step k+1 while the device computes step k, so
        ``train.data_wait_ms`` measures only genuinely feed-bound time.
        ``prefetch=0`` iterates the feed inline on the training thread
        (the pre-pipeline behavior, for bisection).
        """
        mesh = get_mesh()
        if prefetch is None:
            from analytics_zoo_tpu.core.context import config_default
            prefetch = config_default("prefetch",
                                      ZooConfig.prefetch)
        if (auto_resume and self._ts is None and self.model_dir
                and self._ckpt_exists(self.model_dir)):
            self.load(self.model_dir)
            logger.info("auto-resumed from %s at step %d (epoch %d)",
                        self.model_dir, self._py_step, self._epoch)
            # treat ``epochs`` as the TOTAL target: a restarted job runs
            # only the remaining epochs, and feed.epoch(self._epoch)
            # continues the shuffle-order sequence instead of replaying it
            epochs = max(0, epochs - self._epoch)
        data = _maybe_select_cols(data, feature_cols, label_cols)
        feed = as_feed(data, batch_size, seed=self.seed)
        trigger = Trigger.get(checkpoint_trigger)
        history: Dict[str, List[float]] = {"loss": []}
        start_epoch = self._epoch
        target_epoch = self._epoch + epochs
        faults = faults_lib.get_registry()
        host_nan_check = self.nan_policy in ("warn", "rollback", "raise")
        # step-loop telemetry (core/metrics.py): handles hoisted out of
        # the loop.  ``train.data_wait_ms`` is the time this loop spent
        # blocked on the feed (input-bound signal): a rising share of the
        # wall means the input pipeline, not the TPU, is the bottleneck.
        # The loop keeps no step clock of its own: the train step is
        # dispatched asynchronously, so a step's time is the wall of a
        # whole fit() over its steps, or the device's from a trace.
        reg = telemetry.get_registry()
        m_wait = reg.histogram("train.data_wait_ms")
        # the epoch boundary, once an epoch: the host time from a drained
        # device to the epoch's first dispatch (epoch-end bookkeeping, the
        # feed's new epoch and prefetcher, the first batch's wait), and
        # that first wait alone
        m_gap = reg.histogram("train.epoch_gap_ms")
        m_first_wait = reg.histogram("train.first_batch_wait_ms")
        m_carried = reg.counter("feed.epochs_carried")
        m_steps = reg.counter("train.steps")
        m_samples = reg.counter("train.samples")
        m_bad = reg.counter("train.bad_steps")
        m_prefetch = reg.gauge("train.prefetch_depth")
        # scale-out telemetry (docs/distributed-training.md): analytic
        # wire bytes of the gradient collective per step — zero-cost
        # unless grad_compression is configured (incl. "none", the
        # metered uncompressed baseline)
        m_grad_bytes = reg.counter("train.grad_bytes")
        # step profiler (profile=): compile events — the handle exists
        # only when the profiler is on, so the catalog guard and the
        # zero-overhead default both hold
        if self._profile_on:
            m_compiles = reg.counter("train.compiles")
        cache_prev: Optional[int] = None
        # span tree (core/trace.py): one trace per fit() — epochs under
        # the fit root, steps under their epoch — so the training loop's
        # step/data-wait phases land in the same causality substrate the
        # serving path uses.  Gated with the metrics kill switch: the
        # <5% overhead guard measures the fully-uninstrumented baseline.
        record_spans = trace_lib.enabled and reg.enabled
        fit_tid = trace_lib.new_trace_id() if record_spans else None
        fit_sid = trace_lib.new_span_id() if record_spans else None
        self.trace_id = fit_tid  # correlate this fit in the span ring
        fit_t0 = time.monotonic()
        # profiler phases (trace_lib.phase): the leaf regions of this loop
        # that can leave the device idle, as host spans on the profiler's
        # clock.  zoo:fit.epoch_end stays open from the batch loop's exit
        # (the prefetcher's shutdown inside it) to the top of the next
        # epoch, across a ``continue`` and several exits, so it is held
        # here and closed wherever the loop leaves it.
        epoch_end = contextlib.ExitStack()
        # ONE feed pipeline for the whole call (``feed.epochs``): opened
        # at the first epoch, reopened only after a rollback
        batch_iter = None

        if self._preempt is not None:
            self._preempt.active = True
        ZooEstimator._device_lock.acquire()
        try:
            first = True
            if self._profile_on and self._train_step is not None:
                # resumed fit: baseline the executable cache so only NEW
                # compiles in this fit count as compile events
                cache_prev = _jit_cache_size(self._train_step)
            # while (not for): nan_policy="rollback" rewinds self._epoch to
            # the restored checkpoint's epoch and re-runs from there
            t_drained = time.monotonic()  # nothing dispatched yet
            while self._epoch < target_epoch:
                epoch_end.close()
                epoch_sid = (trace_lib.new_span_id() if record_spans
                             else None)
                # monotonic: a wall-clock step (NTP) mid-epoch must not
                # produce negative or wildly wrong throughput numbers
                t0 = time.monotonic()
                losses = []
                epoch_wait = 0.0
                bad_before = self.bad_steps
                rolled_back = False
                if batch_iter is None:
                    batch_iter = _open_feed(
                        feed, mesh, self._epoch, target_epoch, prefetch,
                        m_prefetch)
                elif batch_iter.next_is_ready():
                    # carried: this epoch's first batch was decoded (or
                    # placed) while the last one's final steps ran
                    m_carried.inc()
                try:
                    while True:
                        t_fetch = time.monotonic()
                        with trace_lib.phase("fit.data_wait"):
                            batch = next(batch_iter, None)
                        if batch is None or isinstance(batch, EpochEnd):
                            break
                        wait = time.monotonic() - t_fetch
                        epoch_wait += wait
                        m_wait.observe(wait * 1000.0)
                        if "mask" in batch:
                            # a padded final batch from a stream feed:
                            # training on it would weight the duplicated
                            # pad rows fully (and retrace train_step on
                            # the extra key) — skip it, the
                            # drop_remainder semantics every training
                            # feed defaults to.  evaluate() still
                            # consumes these batches exactly.
                            continue
                        if first:
                            self._ensure_initialized(batch["x"])
                            first = False
                            if self._profile_on:
                                # freshly built steps: cache starts
                                # empty, so the first step's compile IS
                                # a counted event
                                cache_prev = _jit_cache_size(
                                    self._train_step)
                            # the step's own table of its device ops, for
                            # whoever asks (trace_lib.op_scopes): a
                            # closure here, nothing lowered or compiled
                            trace_lib.register_program(
                                "train_step", _hlo_text_of(
                                    self._train_step, mesh, self._ts,
                                    batch))
                        # liveness beat for the zoo-launch gang
                        # supervisor (no-op unless a heartbeat file is
                        # configured); the payload makes the heartbeat
                        # file a tiny status report the supervisor can
                        # aggregate
                        heartbeat(step=self._py_step)
                        # worker fault seams (core/faults.py): a hard
                        # worker death and a wedged step, both disarmed
                        # no-ops in production and armed by
                        # gang-supervision tests
                        if faults.fire("worker.crash"):
                            logger.error("injected worker.crash at step "
                                         "%d", self._py_step)
                            os._exit(1)
                        faults.fire("worker.hang")  # armed delay = hang
                        if faults.fire("step.nan"):
                            batch = _poison_batch(batch)
                        self._maybe_profile()
                        if t_drained is not None:  # the epoch's first
                            m_gap.observe(
                                (time.monotonic() - t_drained) * 1000.0)
                            m_first_wait.observe(wait * 1000.0)
                            t_drained = None
                        with trace_lib.phase("fit.dispatch"):
                            self._ts, loss_val = self._train_step(
                                self._ts, batch)
                        losses.append(loss_val)
                        # track the step in Python: reading
                        # self._ts["step"] would force a device sync on
                        # every iteration
                        self._py_step += 1
                        if self._profile_on:
                            # compile-event probe: the executable cache
                            # grew during THIS step ⇒ it paid a retrace
                            # (new input shape/dtype) — name the step
                            cs = _jit_cache_size(self._train_step)
                            if cs > cache_prev:
                                self.compile_count += cs - cache_prev
                                m_compiles.inc(cs - cache_prev)
                                trace_lib.record(
                                    fit_tid, "train.compile",
                                    {"step": self._py_step,
                                     "compiles": cs - cache_prev},
                                    parent=epoch_sid)
                            cache_prev = cs
                        if record_spans:
                            trace_lib.record(
                                fit_tid, "train.step",
                                {"step": self._py_step,
                                 "data_wait_ms": round(wait * 1000.0,
                                                       3)},
                                parent=epoch_sid,
                                dur_ms=(time.monotonic() - t_fetch)
                                * 1000.0)
                        m_steps.inc()
                        m_samples.inc(feed.global_batch)
                        if self._grad_bytes_step:
                            m_grad_bytes.inc(self._grad_bytes_step)
                        if host_nan_check and not math.isfinite(
                                float(loss_val)):
                            self.bad_steps += 1
                            m_bad.inc()
                            if self.nan_policy == "raise":
                                self._stop_profile()
                                raise NonFiniteLossError(self._py_step)
                            if self.nan_policy == "warn":
                                logger.warning(
                                    "non-finite loss at step %d "
                                    "(nan_policy='warn'): training "
                                    "continues on possibly poisoned "
                                    "parameters", self._py_step)
                            else:
                                self._rollback_to_checkpoint()
                                rolled_back = True
                                break
                        if (self._preempt is not None
                                and self._preempt.should_checkpoint(
                                    self._py_step)):
                            self._stop_profile()
                            from analytics_zoo_tpu.core.failover import \
                                Preempted
                            if self._ckpt_mgr is not None:
                                # bounded time-to-exit: reuse an
                                # in-flight snapshot when one exists
                                from analytics_zoo_tpu.core.failover \
                                    import checkpoint_for_exit
                                saved = checkpoint_for_exit(
                                    self._ckpt_mgr, self._save_tree(),
                                    self._py_step,
                                    extra={"epoch": int(self._epoch)},
                                    touched=self._collect_touched())
                                # saved=0 is a real durable step;
                                # saved=None means nothing landed in
                                # the grace window — report the current
                                # step but flag it as not durable
                                raise Preempted(
                                    saved if saved is not None
                                    else self._py_step,
                                    self.model_dir,
                                    durable=saved is not None)
                            path = self.save(self.model_dir)
                            raise Preempted(self._py_step, path)
                        if trigger and self.model_dir and trigger.fires(
                                step=self._py_step, epoch_end=False):
                            with trace_lib.phase("fit.save"):
                                self._trigger_save()
                finally:
                    epoch_end.enter_context(trace_lib.phase("fit.epoch_end"))
                if rolled_back:
                    # what the feed decoded ahead belongs to epochs that
                    # will be re-run from the restored one: dropped, never
                    # trained on; the pipeline reopens at that epoch
                    batch_iter.close()
                    batch_iter = None
                    # epoch/step rewound to the restored ckpt; drop history
                    # entries for epochs about to be re-run (a mid-epoch
                    # checkpoint rewinds into an already-recorded epoch) so
                    # len(history["loss"]) stays == epochs actually reported
                    keep = max(0, self._epoch - start_epoch)
                    for v in history.values():
                        del v[keep:]
                    t_drained = time.monotonic()  # the NaN check synced
                    continue
                if not losses:
                    raise ValueError(
                        "fit got no full batches (dataset smaller than one "
                        "batch after dropping the padded tail); reduce "
                        "batch_size")
                self._epoch += 1
                # one host sync per epoch, not per step: losses were left
                # on device.  Under skip_step, skipped steps report NaN
                # loss but did not touch params — exclude them from the
                # epoch mean and read back the on-device bad counter.
                stacked = jnp.stack(losses)
                epoch_loss = (jnp.nanmean(stacked)
                              if self.nan_policy == "skip_step"
                              else stacked.mean())
                # layers' device-side counters ride the loss's read-back
                epoch_loss, counted = jax.device_get(
                    (epoch_loss, _state_counters(self._ts["state"])))
                epoch_loss = float(epoch_loss)
                if counted:
                    self._counters_seen = _publish_counters(
                        reg, counted, self._counters_seen)
                if self.nan_policy == "skip_step":
                    self.bad_steps = int(self._ts["bad_steps"])
                    if self.bad_steps > bad_before:
                        # the in-jit guard counted on device; sync the
                        # registry mirror once per epoch
                        m_bad.inc(self.bad_steps - bad_before)
                t_drained = time.monotonic()  # the read-back returned
                history["loss"].append(epoch_loss)
                if self.nan_policy is not None:
                    history.setdefault("bad_steps", []).append(
                        self.bad_steps - bad_before)
                dt = time.monotonic() - t0
                n = len(losses) * feed.global_batch
                # epoch-granularity means from the epoch wall, for the
                # epoch span and the SummaryWriter scalars
                step_ms = 1000.0 * dt / len(losses)
                wait_ms = 1000.0 * epoch_wait / len(losses)
                compute_ms = max(0.0, step_ms - wait_ms)
                samples_per_sec = n / dt
                if record_spans:
                    trace_lib.record(
                        fit_tid, "train.epoch",
                        {"epoch": self._epoch,
                         "loss": round(epoch_loss, 6),
                         "steps": len(losses),
                         "step_ms": round(step_ms, 3),
                         "data_wait_ms": round(wait_ms, 3)},
                        span_id=epoch_sid, parent=fit_sid,
                        dur_ms=dt * 1000.0)
                hb_extra = {}
                if os.environ.get("ZOO_HEARTBEAT_METRICS"):
                    # gang telemetry: the supervisor asked for full
                    # registry snapshots in the heartbeat payload — it
                    # folds every rank's latest into the gang-level
                    # snapshot (metrics_w<rank>.jsonl → gang_metrics.
                    # jsonl / --metrics-port, core/launcher.py)
                    hb_extra["metrics"] = reg.snapshot()
                heartbeat(force=True, step=self._py_step, loss=epoch_loss,
                          samples_per_sec=round(samples_per_sec, 2),
                          **hb_extra)
                if self._writer:
                    self._writer.add_scalar("loss", epoch_loss, self._epoch)
                    self._writer.add_scalar("throughput", n / dt,
                                            self._epoch)
                    self._writer.add_scalar("samples_per_sec",
                                            samples_per_sec, self._epoch)
                    self._writer.add_scalar("step_time_ms", step_ms,
                                            self._epoch)
                    self._writer.add_scalar("data_wait_ms", wait_ms,
                                            self._epoch)
                    self._writer.add_scalar("compute_ms", compute_ms,
                                            self._epoch)
                    if self.nan_policy is not None:
                        self._writer.add_scalar(
                            "bad_steps", self.bad_steps - bad_before,
                            self._epoch)
                if verbose:
                    logger.info("epoch %d: loss=%.4f (%.1f examples/s)",
                                self._epoch, epoch_loss, n / dt)
                if validation_data is not None:
                    val = self.evaluate(validation_data, batch_size)
                    for k, v in val.items():
                        history.setdefault(f"val_{k}", []).append(v)
                        if self._writer:
                            self._writer.add_scalar(f"val_{k}", v,
                                                    self._epoch)
                if trigger and self.model_dir and trigger.fires(
                        step=self._py_step, epoch_end=True):
                    self._trigger_save()
            epoch_end.close()  # before the trace stops: it records on close
            self._stop_profile()  # short runs: close the trace at fit end
        except Exception as e:
            # flight recorder: an unhandled step exception (including a
            # terminal NonFiniteLossError) dumps the recent spans +
            # metric movement + warnings next to the checkpoints, so
            # the post-mortem starts with state, not guesses.
            # ``Preempted`` is a BaseException precisely so intentional
            # shutdown doesn't land here.
            from analytics_zoo_tpu.core import flightrec
            flightrec.dump(
                f"train.{type(e).__name__}", dump_dir=self.model_dir,
                extra={"step": self._py_step, "epoch": self._epoch,
                       "error": str(e)})
            raise
        finally:
            # every way out (the last epoch's end, an exception,
            # ``Preempted``) joins the feed's threads and drops what they
            # hold; nothing past ``target_epoch`` was ever loaded
            if batch_iter is not None:
                batch_iter.close()
            epoch_end.close()  # an exception must not leave the phase open
            ZooEstimator._device_lock.release()
            if self._preempt is not None:
                self._preempt.active = False
            if self._ckpt_mgr is not None:
                # drain the background writer so fit() returning means
                # every accepted generation is durable; a writer error
                # was already logged (and forced the next save full)
                self._ckpt_mgr.flush(raise_error=False)
            if record_spans:
                trace_lib.record(
                    fit_tid, "train.fit",
                    {"epochs": self._epoch - start_epoch,
                     "steps": self._py_step},
                    span_id=fit_sid,
                    dur_ms=(time.monotonic() - fit_t0) * 1000.0)
        return history

    def _rollback_to_checkpoint(self) -> None:
        """nan_policy="rollback": restore the latest ``model_dir``
        checkpoint (params, optimizer, step, epoch) and let fit() re-run
        from there.  Bounded by ``nan_max_rollbacks`` — a deterministic
        NaN (bad data, bad LR) would otherwise loop forever."""
        self._rollbacks += 1
        if self._rollbacks > self.nan_max_rollbacks:
            self._stop_profile()
            raise NonFiniteLossError(
                self._py_step,
                f"non-finite loss at step {self._py_step}: rollback budget "
                f"({self.nan_max_rollbacks}) exhausted — the fault is "
                f"deterministic, not transient")
        if self._ckpt_mgr is not None:
            # an accepted-but-unwritten snapshot is a valid rollback
            # target once it lands; drain the writer before probing
            self._ckpt_mgr.flush(raise_error=False)
        if not (self.model_dir and self._ckpt_exists(self.model_dir)):
            self._stop_profile()
            raise NonFiniteLossError(
                self._py_step,
                f"non-finite loss at step {self._py_step}: nan_policy="
                "'rollback' found no checkpoint in model_dir (configure "
                "model_dir and a checkpoint_trigger)")
        logger.warning(
            "non-finite loss at step %d: rolling back to the last "
            "checkpoint in %s (rollback %d/%d)", self._py_step,
            self.model_dir, self._rollbacks, self.nan_max_rollbacks)
        # under the device lock already (fit holds the RLock)
        self._load_locked(self.model_dir)

    def _maybe_profile(self) -> None:
        if self.profile_dir is None:
            return
        start, end = self.profile_steps
        if not self._profiling and start <= self._py_step < end:
            jax.profiler.start_trace(self.profile_dir)
            self._profiling = True
        elif self._profiling and self._py_step >= end:
            self._stop_profile()

    def _stop_profile(self) -> None:
        if self._profiling:
            # block so async dispatches land inside the trace
            jax.block_until_ready(self._ts)
            jax.profiler.stop_trace()
            self._profiling = False
            logger.info("wrote jax profiler trace to %s", self.profile_dir)

    # -- evaluation -----------------------------------------------------------

    def evaluate(self, data: Any, batch_size: int = 32,
                 feature_cols: Optional[Sequence[str]] = None,
                 label_cols: Optional[Sequence[str]] = None
                 ) -> Dict[str, float]:
        """Exact metrics over every row: the final partial batch is padded
        to the static batch shape and weighted out by a mask inside the jit
        step.  In multihost runs the batch (and mask) are global arrays, so
        the summed statistics are global — every process returns identical
        metrics."""
        mesh = get_mesh()
        data = _maybe_select_cols(data, feature_cols, label_cols)
        feed = as_feed(data, batch_size, shuffle=False, seed=self.seed,
                       drop_remainder=False)
        totals: Optional[List[Any]] = None

        def accumulate(totals, batch, step):
            self._ensure_initialized(batch["x"])
            if "mask" not in batch:  # feeds may pre-attach masks
                batch = dict(batch)
                batch["mask"] = shard_batch(feed.step_mask(step), mesh)
            stats = self._eval_step(self._ts, batch)
            return (list(stats) if totals is None
                    else [a + b for a, b in zip(totals, stats)])

        # shuffled feeds are fine: sums are permutation-invariant and
        # step_mask zero-weights the padded tail positions either way
        with ZooEstimator._device_lock:
            for step, batch in enumerate(feed.epoch(mesh, 0)):
                heartbeat()  # long validation sweeps must stay "alive" too
                totals = accumulate(totals, batch, step)
            if feed.drop_remainder:
                # user-constructed training feed: cover the dropped tail
                # with a padded + masked extra batch.  dropped_rows
                # respects the epoch-0 permutation, so shuffled feeds are
                # exact too.
                rem = (feed.dropped_rows(0) if hasattr(feed, "dropped_rows")
                       else feed.remainder())
                if rem is not None:
                    totals = accumulate(totals,
                                        _pad_remainder(rem, feed, mesh), -1)
                elif (getattr(feed, "shuffle", False)
                      and feed.num_rows % getattr(feed, "_local_batch", 1)):
                    # rows WERE dropped; this feed can't reconstruct them
                    logger.warning(
                        "evaluate on a shuffled drop_remainder feed that "
                        "cannot reconstruct its dropped rows: metrics "
                        "exclude the rows the shuffle dropped this epoch")
        if totals is None:
            raise ValueError("evaluate got no batches")
        out = {"loss": float(totals[0][0] / jnp.maximum(totals[0][1], 1.0))}
        for m, stat in zip(self.metrics, totals[1:]):
            out[m.name] = float(m.result(stat))
        return out

    # -- inference ------------------------------------------------------------

    def predict(self, data: Any, batch_size: int = 32,
                feature_cols: Optional[Sequence[str]] = None) -> np.ndarray:
        """Run forward over all rows (exact count, last batch padded+trimmed).

        Raw arrays/shards are wrapped unshuffled with the tail padded; a
        user-constructed feed must itself be unshuffled, and if it drops the
        remainder the tail rows are predicted via ``feed.remainder()``.
        """
        mesh = get_mesh()
        data = _maybe_select_cols(data, feature_cols, None)
        feed = as_feed(data, batch_size, shuffle=False, drop_remainder=False)
        if getattr(feed, "shuffle", False):
            raise ValueError(
                "predict needs row order preserved: construct the feed with "
                "shuffle=False")
        outs: List[np.ndarray] = []
        with ZooEstimator._device_lock:
            for batch in feed.epoch(mesh, 0):
                heartbeat()  # long prediction sweeps are progress too
                self._ensure_initialized(batch["x"])
                outs.append(_to_local_rows(self._pred_step(self._ts,
                                                           batch["x"])))
            if getattr(feed, "drop_remainder", False):
                rem = feed.remainder()
                if rem is not None:  # tail rows the epoch skipped
                    x = jax.tree_util.tree_map(jnp.asarray, rem["x"])
                    self._ensure_initialized(x)
                    outs.append(_to_local_rows(self._pred_step(self._ts,
                                                               x)))
        return np.concatenate(outs, axis=0)[: feed.num_rows]

    # -- persistence ----------------------------------------------------------

    def _save_tree(self) -> Dict[str, Any]:
        """The checkpointable train state: everything but the touched
        bitmasks (delta bookkeeping, rebuilt fresh on load)."""
        return {k: v for k, v in self._ts.items() if k != "touched"}

    def _ckpt_exists(self, path: str) -> bool:
        """A resumable checkpoint at ``path``: sync ckpt_io layout OR an
        async manager manifest with a visible generation."""
        if ckpt_io.exists(path):
            return True
        from analytics_zoo_tpu.core import ckpt_manager as ckpt_mgr_lib
        return ckpt_mgr_lib.has_manifest(path)

    def _trigger_save(self) -> None:
        """One checkpoint-trigger firing: async through the manager
        (touched rows reset only when the snapshot was ACCEPTED — a
        skip-policy drop keeps them marked for the next save), else the
        inline sync save."""
        if self._ckpt_mgr is None:
            self.save(self.model_dir)
            return
        with ZooEstimator._device_lock:
            accepted = self._ckpt_mgr.save_async(
                self._save_tree(), step=self._py_step,
                extra={"epoch": int(self._epoch)},
                touched=self._collect_touched())
            if accepted and self._track_touched:
                self._reset_touched()

    def save(self, path: Optional[str] = None) -> str:
        path = path or self.model_dir
        if path is None:
            raise ValueError("no path given and no model_dir configured")
        if self._ts is None:
            raise ValueError("nothing to save: model not initialized yet")
        with ZooEstimator._device_lock:  # device_get sweeps device state
            if self._ckpt_mgr is not None and path == self.model_dir:
                # the manager owns model_dir: a blocking full save keeps
                # MANIFEST.jsonl the single source of truth (mixing raw
                # ckpt_io saves into the same directory would fork it)
                self._ckpt_mgr.save(self._save_tree(),
                                    step=self._py_step,
                                    extra={"epoch": int(self._epoch)},
                                    touched=self._collect_touched())
                if self._track_touched:
                    self._reset_touched()
                return path
            tree = jax.tree_util.tree_map(lambda x: x, self._save_tree())
            return ckpt_io.save(path, tree, step=int(self._ts["step"]),
                                extra={"epoch": int(self._epoch)},
                                retries=self.checkpoint_retries)

    def load(self, path: Optional[str] = None) -> None:
        # under the device lock: restore dispatches device_put/jit work,
        # and a concurrent trial mid-fit must not overlap it (the same
        # XLA:CPU wedge _device_lock exists for; RLock, so fit's own
        # auto_resume load and trigger saves re-enter fine)
        with ZooEstimator._device_lock:
            self._load_locked(path)

    def _load_locked(self, path: Optional[str]) -> None:
        path = path or self.model_dir
        mesh = get_mesh()
        # mesh-aware restore: leaves that were sharded at save time come
        # back already placed under their recorded PartitionSpec — a
        # cross-host (ZeRO-3) checkpoint is never densely assembled
        if self._ckpt_mgr is not None and path == self.model_dir:
            from analytics_zoo_tpu.core import ckpt_manager as \
                ckpt_mgr_lib
            if (not ckpt_mgr_lib.has_manifest(path)
                    and ckpt_io.exists(path)):
                # legacy sync checkpoint predates checkpoint_async
                # being turned on for this model_dir: resume from it
                # directly; the next trigger save writes the first
                # manifest generation (a full — the manager's chain
                # tip is unset)
                tree = ckpt_io.restore(path, mesh=mesh)
                extra = ckpt_io.load_extra(path)
            else:
                # manifest-driven restore: newest VISIBLE generation,
                # with delta replay onto its base full
                # (core/ckpt_manager.py)
                tree = self._ckpt_mgr.restore(mesh=mesh)
                rec = self._ckpt_mgr.last_restored or {}
                extra = rec.get("extra") or {}
        else:
            tree = ckpt_io.restore(path, mesh=mesh)
            extra = ckpt_io.load_extra(path)
        self._py_step = int(np.asarray(tree["step"]))
        # counters restored with the state were published by the run that
        # saved them
        self._counters_seen = {
            k: np.asarray(v, np.int64) for k, v in jax.device_get(
                _state_counters(tree.get("state", {}))).items()} or None
        if self.nan_policy == "skip_step":
            # sync the host mirror with the restored on-device counter so
            # the first post-resume epoch reports only ITS bad steps, not
            # the checkpoint's historical total.  Host policies keep their
            # own mirror (ts never carries their count) — left untouched
            # so a mid-fit rollback load doesn't erase the triggering step.
            self.bad_steps = int(np.asarray(tree.get("bad_steps", 0)))
        self._epoch = int(extra.get("epoch", self._epoch))
        rules = _resolve_sharding_rules(self.sharding)
        replicated = NamedSharding(mesh, P())

        def place(leaf, spec):
            if isinstance(leaf, jax.Array):
                return leaf  # restored on-mesh under the saved layout
            return jax.device_put(leaf, NamedSharding(mesh, spec))

        if rules:
            # restore under the SAME layout training uses (a plain replicated
            # device_put would silently drop tp/fsdp sharding)
            from analytics_zoo_tpu.parallel import infer_param_specs
            specs = infer_param_specs(tree["params"], rules, mesh)
            params = jax.tree_util.tree_map(place, tree["params"], specs)
        else:
            params = jax.tree_util.tree_map(
                lambda l: place(l, P()), tree["params"])
        # checkpoint IO stores optax named-tuples as plain tuples; rebuild the
        # real structure (and its shardings) from tx.init and pour leaves in
        from analytics_zoo_tpu.parallel import embedding as emb_lib
        self._sparse_paths = emb_lib.sparse_paths(params)
        self._check_sparse_support()
        dense_of = (lambda p: emb_lib.split_sparse(p)[0]) \
            if self._sparse_paths else (lambda p: p)
        self._wrap_frozen_tx(dense_of(tree["params"]))
        ref_opt = _ensure_on_mesh(jax.jit(self.tx.init)(dense_of(params)),
                                  mesh)
        ref_leaves, ref_def = jax.tree_util.tree_flatten(ref_opt)
        saved_leaves = jax.tree_util.tree_leaves(tree["opt_state"])
        if len(saved_leaves) == len(ref_leaves):
            opt_state = jax.tree_util.tree_unflatten(ref_def, [
                jax.device_put(s, r.sharding) if hasattr(r, "sharding")
                else s for s, r in zip(saved_leaves, ref_leaves)])
        else:
            logger.warning("optimizer state in checkpoint does not match "
                           "the configured optimizer; reinitialized")
            opt_state = ref_opt
        self._ts = {"params": params,
                    "state": jax.device_put(tree["state"], replicated),
                    "opt_state": opt_state,
                    "step": jax.device_put(jnp.asarray(tree["step"]),
                                           replicated),
                    "rng": jax.device_put(jnp.asarray(tree["rng"]),
                                          replicated),
                    # pre-self-healing checkpoints have no bad_steps leaf
                    "bad_steps": jax.device_put(
                        jnp.asarray(tree.get("bad_steps", 0), jnp.int32),
                        replicated)}
        if self.grad_compression == "int8":
            self._ts["ef"] = self._restore_error_feedback(
                tree.get("ef"), params, mesh)
        # delta bookkeeping is NOT checkpointed: fresh zero masks are
        # exactly right after a restore — rows diverge from the restored
        # generation (the manager's new chain tip) only once training
        # touches them again
        self._track_touched = bool(self._track_touched
                                   and self._sparse_paths)
        if self._track_touched:
            self._ts["touched"] = self._init_touched(params)
        if self._train_step is None:
            self._build_steps(mesh)

    def _restore_error_feedback(self, saved: Any, params: Any, mesh) -> Any:
        """Checkpointed error-feedback residuals, re-placed under the
        batch-shard layout; zeros when the checkpoint predates int8
        compression or was written on a mesh with a different shard count
        (the residual is a convergence aid, not required state)."""
        from analytics_zoo_tpu.parallel.util import (batch_shard_count,
                                                     batch_shard_spec)
        s = batch_shard_count(mesh)
        if saved is not None:
            first = _first_leaf(saved)
            if (jax.tree_util.tree_structure(saved)
                    == jax.tree_util.tree_structure(params)
                    and np.ndim(first) >= 1 and first.shape[0] == s):
                return jax.tree_util.tree_map(
                    lambda l: l if isinstance(l, jax.Array)
                    else jax.device_put(np.asarray(l), NamedSharding(
                        mesh, batch_shard_spec(mesh, np.ndim(l)))), saved)
            logger.warning(
                "checkpointed error-feedback residuals do not match the "
                "current mesh (%d batch shards); resetting to zero", s)
        return self._init_error_feedback(params, mesh)

    def get_train_summary(self, tag: str = "loss"):
        """[(step, value)] scalars from the configured log_dir (reference:
        Estimator.get_train_summary — BigDL TrainSummary readback)."""
        if self._writer is None:
            raise ValueError("no log_dir configured")
        return self._writer.read_scalar(tag)

    def get_validation_summary(self, tag: str):
        return self.get_train_summary(f"val_{tag}"
                                      if not tag.startswith("val_") else tag)

    def get_model(self) -> Dict[str, Any]:
        """The current variables {"params", "state"} (host copies)."""
        if self._ts is None:
            raise ValueError("model not initialized yet")
        return jax.device_get({"params": self._ts["params"],
                               "state": self._ts["state"]})

    def load_orca_checkpoint(self, path: str) -> None:  # reference-parity name
        self.load(path)


def _first_leaf(tree: Any) -> jax.Array:
    return jax.tree_util.tree_leaves(tree)[0]


def _merge_shard_leaf(l: jax.Array) -> jax.Array:
    """Per-shard model state ``[n_shards, ...]`` → one state tree: mean
    for float leaves (BatchNorm running stats — the local-BN convention
    every dp framework uses), shard 0 for integer/flag leaves (they are
    shard-invariant)."""
    if jnp.issubdtype(l.dtype, jnp.inexact):
        return l.mean(0)
    return l[0]


def _open_feed(feed: Any, mesh, first: int, last: int, prefetch: int,
               gauge: Any):
    """The one iterator a ``fit()`` reads: ``feed.epochs(first, last)``
    behind a ``PrefetchIterator`` (``prefetch=0``: the same iterator,
    inline on the training thread)."""
    if not (prefetch and prefetch > 0):
        return feed.epochs(mesh, first, last)
    if _supports_host_epoch(feed):
        # stream feeds: iterate HOST batches and place them inside
        # the prefetch producer — double-buffered device_put: the
        # host→HBM copy of batch k+1 dispatches (and completes) while
        # the device computes batch k, and shared-memory pool slots
        # recycle the moment their transfer lands
        return PrefetchIterator(
            feed.epochs(mesh, first, last, place=False),
            depth=prefetch, gauge=gauge, place=make_placer(mesh))
    # depth-2 double buffering by default: the feed's host work for
    # step k+1 (slice/stack, shard_batch, device_put dispatch)
    # overlaps the device compute of step k on a background thread
    return PrefetchIterator(feed.epochs(mesh, first, last),
                            depth=prefetch, gauge=gauge)


def _supports_host_epoch(feed: Any) -> bool:
    """Can this feed yield host batches (``epochs(..., place=False)``)?
    True for StreamingDataFeed; in-RAM feeds keep their own placed-epoch
    double buffering."""
    try:
        import inspect
        return "place" in inspect.signature(feed.epochs).parameters
    except (TypeError, ValueError):
        return False


def _poison_batch(batch: Dict[str, Any]) -> Dict[str, Any]:
    """``step.nan`` injection: NaN-fill every float leaf of the batch so
    the non-finite propagates through the REAL forward/backward (loss AND
    gradients go bad), exercising the same guard path a numerical blowup
    would.  Integer leaves (token ids, labels) pass through — NaN is not
    representable there and embedding lookups must stay in range.  The
    multiply (not a rebuild) keeps each leaf's device placement/sharding
    exactly as the feed delivered it."""

    def nan_fill(a):
        if np.issubdtype(np.dtype(a.dtype), np.floating):
            return a * a.dtype.type(np.nan)
        return a

    return {k: jax.tree_util.tree_map(nan_fill, v)
            for k, v in batch.items()}


def _pad_remainder(rem: Dict[str, Any], feed: Any, mesh) -> Dict[str, Any]:
    """Remainder rows → a full static-shape batch with a 0-weighted pad."""
    r = len(_first_leaf(rem))
    lb = feed._local_batch

    def pad(a):
        a = np.asarray(a)
        return np.concatenate([a, np.repeat(a[-1:], lb - r, axis=0)], axis=0)

    batch = {k: jax.tree_util.tree_map(pad, v) for k, v in rem.items()}
    mask = np.zeros((lb,), np.float32)
    mask[:r] = 1.0
    batch["mask"] = mask
    return shard_batch(batch, mesh)


def _metric_update(m: Any, out: Any, y: Any, mask: jax.Array) -> jax.Array:
    """Call a metric's update, tolerating user metrics written to the old
    2-arg ``update(y_pred, y_true)`` contract (their stats then include
    padded rows; built-ins all take the mask)."""
    try:
        import inspect
        takes_mask = len(inspect.signature(m.update).parameters) >= 3
    except (TypeError, ValueError):
        takes_mask = True
    if takes_mask:
        return m.update(out, y, mask)
    return m.update(out, y)


def _per_example_loss(loss_fn: Callable, out: Any, y: Any) -> jax.Array:
    """[batch] losses from a mean-reducing loss: vmap each example through
    the loss with a singleton batch dim."""
    def one(o, y1):
        return loss_fn(jax.tree_util.tree_map(lambda a: a[None], o),
                       jax.tree_util.tree_map(lambda a: a[None], y1))

    return jax.vmap(one)(out, y)


def _to_local_rows(out: jax.Array) -> np.ndarray:
    """Device output → this process's rows as numpy.  Single-process: the
    whole batch.  Multihost: this process's rows already live in its
    addressable shards (shard_batch's contract: global batch = host-rows
    concatenated in process order), so assemble them locally — no
    cross-host transfer on the predict hot path."""
    if jax.process_count() == 1:
        return np.asarray(out)
    # dedupe replicas (tp/model axes replicate the batch rows over extra
    # local devices) by distinct dim-0 index
    pieces: Dict[int, np.ndarray] = {}
    for s in out.addressable_shards:
        start = 0 if not s.index or s.index[0].start is None \
            else int(s.index[0].start)
        if start not in pieces:
            pieces[start] = np.asarray(s.data)
    rows = np.concatenate([pieces[k] for k in sorted(pieces)], axis=0)
    local = out.shape[0] // jax.process_count()
    if rows.shape[0] > local:
        # output came back replicated (all rows on every host): slice ours
        return rows[jax.process_index() * local:
                    (jax.process_index() + 1) * local]
    return rows


def _state_counters(state: Any) -> Dict[str, Any]:
    """The leaves a layer keeps under a ``counters`` key of its state:
    ``{"<path>/<series>": leaf}``.  A layer counts on the device, adding to
    its own state every step (int32, which wraps); the host reads the totals
    once an epoch with the loss, so counting costs the batch loop no sync."""
    out: Dict[str, Any] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        keys = [getattr(p, "key", None) for p in path]
        if len(keys) >= 2 and keys[-2] == "counters":
            out["/".join(map(str, keys))] = leaf
    return out


def _publish_counters(reg: Any, now: Dict[str, Any],
                      seen: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """Growth of each device-side counter since the last read, into the
    registry series of the leaf's own name (the last key of its path;
    layers of one kind share a series).  A scalar is a counter.  A vector
    counts per slot (rows per expert); the registry has no per-slot series,
    so its growth's imbalance, largest slot over the mean, is observed in a
    histogram, once a layer and read.  A floating-point leaf is a level and
    not a count (a router's largest bias): observed as it stands."""
    for path, total in now.items():
        series = path.rsplit("/", 1)[1]
        if np.issubdtype(np.asarray(total).dtype, np.floating):
            reg.histogram(series).observe(float(total))
            continue
        before = (seen or {}).get(path, 0)
        grew = (np.asarray(total, np.int64) - before) % (1 << 32)
        if grew.ndim == 0:
            reg.counter(series).inc(int(grew))
        elif grew.sum() > 0:
            reg.histogram(series).observe(float(grew.max() / grew.mean()))
    return {k: np.asarray(v, np.int64) for k, v in now.items()}


def _collect_aux_losses(state: Any) -> jax.Array:
    """Sum every ``aux_loss`` leaf in a state pytree (MoE layers record
    their load-balancing loss there; pure-function discipline)."""
    total = jnp.zeros((), jnp.float32)
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        if path and getattr(path[-1], "key", None) == "aux_loss":
            total = total + leaf.astype(jnp.float32)
    return total


def _ensure_on_mesh(tree: Any, mesh) -> Any:
    """Re-place leaves whose sharding is not on ``mesh`` as mesh-replicated
    (jit can leave freshly created scalars on a single device)."""
    repl = NamedSharding(mesh, P())

    def fix(leaf):
        sh = getattr(leaf, "sharding", None)
        if isinstance(sh, NamedSharding) and sh.mesh == mesh:
            return leaf
        return jax.device_put(leaf, repl)

    return jax.tree_util.tree_map(fix, tree)


def _resolve_sharding_rules(sharding: Any):
    """"dp" → None; "tp"/"fsdp"/"tp+fsdp"/"2d" → rule presets; list →
    as-is.  "2d" resolves to the tensor-parallel rules — the data half of
    the 2D layout is batch sharding, which every strategy gets from the
    feed; the distinction from "tp" is the MESH (data × model, built by
    ``init_orca_context(mesh_shape="2d")``) and the stronger intent check
    in ``_warn_strategy_mesh_mismatch``."""
    if sharding is None or sharding == "dp":
        return None
    if isinstance(sharding, str):
        from analytics_zoo_tpu.parallel import (fsdp_rules,
                                                tensor_parallel_rules)
        rules = []
        parts = set(sharding.replace(" ", "").split("+"))
        unknown = parts - {"tp", "fsdp", "dp", "2d"}
        if unknown:
            raise ValueError(f"unknown sharding strategy {sharding!r}")
        if parts & {"tp", "2d"}:
            # composed tp+fsdp: the non-tp dim of each tp kernel goes to fsdp
            rules += tensor_parallel_rules(
                fsdp_axis="fsdp" if "fsdp" in parts else None)
        if "fsdp" in parts:
            rules += fsdp_rules()  # remaining kernels: plain ZeRO-3
        return rules or None
    return list(sharding)


def _maybe_select_cols(data: Any, feature_cols: Optional[Sequence[str]],
                       label_cols: Optional[Sequence[str]]) -> Any:
    """XShards of DataFrames + feature/label cols → numpy-dict XShards
    (reference: estimators accepted DataFrame-backed shards with
    feature_cols/label_cols kwargs)."""
    from analytics_zoo_tpu.data import XShards
    if feature_cols is None or not isinstance(data, XShards):
        return data
    first = data.collect()[0]
    if hasattr(first, "iloc"):
        return data.to_numpy_dict(feature_cols, label_cols)
    return data
