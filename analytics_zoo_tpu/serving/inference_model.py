"""InferenceModel (reference: zoo/.../pipeline/inference/InferenceModel.scala
+ pyzoo/zoo/pipeline/inference/inference_model.py).

The reference held ``concurrentNum`` JNI model replicas behind a blocking
queue.  On TPU one compiled executable is already reentrant for same-shape
calls, so "replicas" become per-batch-shape AOT-compiled executables
(compile once per bucket, lock-free dispatch); ``concurrent_num`` bounds
in-flight host threads instead.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np

from analytics_zoo_tpu.nn.module import Module

_Q_MARKER = "__int8_weight__"
_Q_MIN_SIZE = 4096  # leaves smaller than this stay float (negligible HBM)


def _is_int8_request(dtype: Any) -> bool:
    """True for any spelling of int8 serving ("int8"/"w8"/np.int8/
    jnp.int8) — casting float weights to an integer dtype is never what a
    caller wants, so every int8 spelling routes to weight-only
    quantization."""
    if isinstance(dtype, str):
        return dtype in ("int8", "w8")
    try:
        return np.dtype(dtype) == np.int8
    except TypeError:
        return False


def _quantize_tree(variables: Any, compute_dtype: Any) -> Any:
    """Weight-only int8: float leaves become {marker, q(int8), scale} with
    per-output-channel (last axis) symmetric scales — the reference's
    OpenVINO INT8 calibration analog.  4x less parameter HBM traffic per
    request; dequantization to the compute dtype happens on-chip and fuses
    into the consuming matmul."""
    import jax.numpy as jnp

    def q(leaf):
        if not (hasattr(leaf, "dtype")
                and jnp.issubdtype(np.asarray(leaf).dtype, np.floating)):
            return leaf
        arr = np.asarray(leaf, np.float32)
        if arr.size < _Q_MIN_SIZE:
            return jnp.asarray(arr, compute_dtype)
        axes = tuple(range(arr.ndim - 1)) or None
        scale = (np.max(np.abs(arr), axis=axes, keepdims=True)
                 / 127.0).astype(np.float32)
        scale = np.maximum(scale, 1e-12)
        qarr = np.clip(np.round(arr / scale), -127, 127).astype(np.int8)
        # marker is detected by KEY (the value would be traced under jit)
        return {_Q_MARKER: np.int8(1), "q": jnp.asarray(qarr),
                "scale": jnp.asarray(scale)}

    def walk(node):
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        return q(node)

    return walk(variables)


def _dequantize_tree(variables: Any, compute_dtype: Any,
                     calibrated_paths: Optional[frozenset] = None) -> Any:
    """Inverse of ``_quantize_tree`` — runs INSIDE the jitted forward, so
    XLA fuses the int8→float multiply into the consumer.  With
    ``calibrated_paths`` (calibrated-activation mode: the scope paths the
    Calibrator saw — nn.Dense and plain nn.Conv2D layers), those layers'
    kernels stay int8 dicts for their own int8 GEMM/conv paths; every
    other quantized leaf — kernels of layers that CANNOT consume the dict
    form (LSTM/GRU input kernels, Highway, ScaledWSConv2D) — dequantizes
    as usual."""
    def walk(node, path=()):
        if isinstance(node, dict):
            if _Q_MARKER in node:
                if (calibrated_paths is not None and path
                        and path[-1] == "kernel"
                        and "/".join(path[:-1]) in calibrated_paths):
                    return node
                return (node["q"].astype(compute_dtype)
                        * node["scale"].astype(compute_dtype))
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        return node

    # variables is {"params": ..., "state": ...}; scope paths are relative
    # to the params root
    return {k: walk(v) if k != "params" else
            {kk: walk(vv, (kk,)) for kk, vv in v.items()}
            for k, v in variables.items()}


def enable_aot_cache(path: Optional[str] = None) -> str:
    """Persist EVERY serving executable in JAX's compilation cache, however
    small or quick to compile, so serving executables compile once per
    machine, not once per process — with ``save_executables`` (skips
    tracing/lowering) this is the full OpenVINO-IR analog: a restart
    reuses the compiled artifact.  The directory is placed by
    ``core.context.configure_compile_cache``: ``JAX_COMPILATION_CACHE_DIR``
    when set (``path`` is then ignored), else ``path``, else
    ``<checkout>/.jax_cache`` — keep it FIXED across restarts, a directory
    that moves never hits.  Returns the directory in use.  Safe to call
    more than once; applies process-wide."""
    from analytics_zoo_tpu.core.context import configure_compile_cache
    path = configure_compile_cache(path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class InferenceModel:
    def __init__(self, concurrent_num: int = 4,
                 batch_buckets: Sequence[int] = (1, 4, 16, 64)):
        self.concurrent_num = concurrent_num
        self.batch_buckets = sorted(batch_buckets)
        self._model: Optional[Module] = None
        self._variables: Optional[Dict[str, Any]] = None
        self._quantized = False
        self._compiled: Dict[Tuple[Any, ...], Any] = {}
        self._sema = threading.Semaphore(concurrent_num)
        self._lock = threading.Lock()
        # fresh XLA compiles performed by THIS instance (artifact loads
        # via load_executables and persistent-cache hits do not count):
        # the serving hot-swap acceptance asserts this stays flat after
        # warm() — no request ever waits on a cold compile
        self.compile_count = 0

    # -- loaders (reference: doLoadBigDL/doLoadTF/doLoadOpenVINO...) ----------

    def load(self, model: Module, variables: Dict[str, Any],
             dtype: Any = None, calibrate: Any = None) -> "InferenceModel":
        """Load from an nn.Module + its variables.

        ``dtype``: optional serving precision —
        - ``jnp.bfloat16``: cast float parameters once at load (half the
          HBM traffic per request, the MXU-native dtype);
        - ``"int8"``: weight-only int8 with per-channel scales (4x less
          parameter traffic; on-chip dequant to bf16 fuses into the
          consuming matmul).
        ``calibrate``: with ``dtype="int8"``, a representative input batch
        — one float forward records every Dense and plain-Conv2D input's
        absolute maximum; serving then quantizes those ACTIVATIONS with
        the frozen static scales and runs the matmuls/convolutions as
        int8 x int8 -> int32 on the MXU (kernel-transforming convs, e.g.
        ScaledWSConv2D, stay weight-only).  The reference's OpenVINO INT8
        calibration analog (``OpenVinoInferenceSupportive`` calibrate +
        doLoadOpenVINOInt8); without ``calibrate`` the int8 path is
        weight-only, as before."""
        import jax.numpy as jnp
        self._quantized = False
        self._quant_ctx = None
        # executables are AOT-lowered against the previous load's variable
        # pytree/model — always invalid after a reload
        self._compiled.clear()
        if calibrate is not None and not (dtype is not None
                                          and _is_int8_request(dtype)):
            raise ValueError(
                "calibrate= only applies to dtype='int8' serving; got "
                f"dtype={dtype!r} — a silently ignored calibration batch "
                "would leave you believing you deployed calibrated int8")
        if dtype is not None and _is_int8_request(dtype):
            if calibrate is not None:
                from analytics_zoo_tpu.nn.quant import Calibrator, QuantApply
                collector = Calibrator()
                model.apply(variables, np.asarray(calibrate),
                            training=False, quant=collector)
                self._quant_ctx = QuantApply(collector.amax, jnp.bfloat16)
            variables = _quantize_tree(variables, jnp.bfloat16)
            self._quantized = True
            self._compute_dtype = jnp.bfloat16
        elif dtype is not None:
            def cast(leaf):
                if hasattr(leaf, "dtype") and \
                        jnp.issubdtype(leaf.dtype, jnp.floating):
                    return leaf.astype(dtype)
                return leaf

            variables = jax.tree_util.tree_map(cast, variables)
        self._model = model
        self._variables = variables
        return self

    def load_zoo_model(self, path: str, dtype: Any = None
                       ) -> "InferenceModel":
        """Load a ZooModel.save_model directory."""
        from analytics_zoo_tpu.models import ZooModel
        m = ZooModel.load_model(path)
        return self.load(m, m._loaded_variables, dtype=dtype)

    def load_estimator(self, est: Any, dtype: Any = None
                       ) -> "InferenceModel":
        return self.load(est.model, est.get_model(), dtype=dtype)

    # -- predict --------------------------------------------------------------

    def _bucket(self, n: int) -> int:
        for b in self.batch_buckets:
            if n <= b:
                return b
        return self.batch_buckets[-1]

    def _fn_for(self, shape: Tuple[int, ...], dtype: Any):
        key = (shape, str(dtype))
        fn = self._compiled.get(key)
        if fn is None:
            with self._lock:
                fn = self._compiled.get(key)
                if fn is None:
                    # AOT compile for this exact shape (reference: OpenVINO
                    # compiled per input shape too)
                    fn = (jax.jit(self._fwd_for_export())
                          .lower(self._variables,
                                 jax.ShapeDtypeStruct(shape, dtype))
                          .compile())
                    self._compiled[key] = fn
                    self.compile_count += 1
        return fn

    # -- warmup (the hot-swap seam: compile BEFORE traffic arrives) ----------

    def warm(self, shapes: Sequence[Tuple[int, ...]],
             dtype: Any = np.float32,
             buckets: Optional[Sequence[int]] = None) -> int:
        """AOT-precompile the serving executables for each per-ROW
        shape × batch bucket, so no request ever waits on a fresh XLA
        compile — call at startup (before opening the port) and before
        hot-swapping a model version into service.  ``shapes`` are
        per-row shapes (no batch dim); ``buckets`` defaults to every
        ``batch_buckets`` entry.  Returns the number of (shape, bucket)
        executables now resident."""
        use = self.batch_buckets if buckets is None else sorted(
            int(b) for b in buckets)
        n = 0
        for shape in shapes:
            for b in use:
                self._fn_for((int(b),) + tuple(int(s) for s in shape),
                             np.dtype(dtype))
                n += 1
        return n

    def warm_from(self, other: "InferenceModel") -> int:
        """Warm this model for the traffic ``other`` has realized — the
        version hot-swap path: the incoming version warms against the
        outgoing version's compiled (shape, dtype) set before the
        registry flips, so the swap costs zero cold compiles.

        The old keys' batch dims are the OUTGOING model's buckets;
        copying them verbatim would warm shapes this model never pads
        to when the two versions' ``batch_buckets`` differ.  Each old
        key is re-bucketed here: its realized row counts were anywhere
        in (0, old_bucket], so every one of OUR buckets such a count
        could pad to gets warmed.  Returns the number of executables
        warmed."""
        n = 0
        seen = set()
        for (shape, dtype_str) in list(getattr(other, "_compiled", {})):
            row = tuple(shape[1:])
            cap = self._bucket(int(shape[0]))
            for b in self.batch_buckets:
                if b > cap:
                    break
                key = ((b,) + row, dtype_str)
                if key in seen:
                    continue
                seen.add(key)
                self._fn_for((b,) + row, np.dtype(dtype_str))
                n += 1
        return n

    # -- AOT executable serialization (reference: OpenVINO IR — a compiled
    # artifact loadable without re-running the model optimizer) -------------

    def _config_fingerprint(self) -> str:
        """Identity of the serving configuration an exported executable
        is only valid for: precision mode + calibration scales + the
        variable tree's structure/dtypes/shapes (a bf16-cast or
        quantized load produces a different tree than f32)."""
        import hashlib
        qctx = getattr(self, "_quant_ctx", None)
        leaves = [
            (jax.tree_util.keystr(p), str(getattr(l, "dtype", type(l))),
             str(getattr(l, "shape", ())))
            for p, l in jax.tree_util.tree_leaves_with_path(
                self._variables)]
        parts = [str(getattr(self, "_compute_dtype", None)),
                 str(self._quantized),
                 repr(sorted(qctx.amax.items())) if qctx else "none",
                 repr(sorted(leaves))]
        return hashlib.sha256("|".join(parts).encode()).hexdigest()[:16]

    def _computation_hash(self, shape, dtype) -> str:
        """Hash of the serving computation's JAXPR for one input bucket —
        catches MODEL CODE changes (activation swap, stride edit, new
        layer) that leave the variable tree identical.  Costs one trace
        (no lowering, no XLA compile): the cheap third of a cold start."""
        import hashlib

        var_struct = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(getattr(l, "shape", ()),
                                           getattr(l, "dtype", np.float32)),
            self._variables)
        jaxpr = jax.make_jaxpr(self._fwd_for_export())(
            var_struct, jax.ShapeDtypeStruct(shape, np.dtype(dtype)))
        # the printed jaxpr embeds repr()s of closure params (e.g.
        # custom_jvp's jvp_jaxpr_thunk=<function ... at 0x...>) whose
        # MEMORY ADDRESSES differ every trace — strip them or the hash
        # never matches across processes and every artifact is "stale"
        import re
        text = re.sub(r" at 0x[0-9a-fA-F]+", "", str(jaxpr))
        return hashlib.sha256(text.encode()).hexdigest()[:16]

    def save_executables(self, path: str) -> int:
        """Serialize the per-shape serving computations (jax.export
        StableHLO artifacts) so a later process can skip tracing/lowering
        — pair with ``enable_aot_cache`` to also skip the XLA compile.
        Saves one blob per (shape, dtype) bucket compiled so far, plus a
        manifest; returns the number saved.  Typically called next to
        ``ZooModel.save_model`` output."""
        import json
        import os

        from jax import export as jexport

        os.makedirs(path, exist_ok=True)
        manifest = {"fingerprint": self._config_fingerprint(), "keys": []}
        n = 0
        for (shape, dtype_str) in list(self._compiled):
            fwd = self._fwd_for_export()
            exp = jexport.export(jax.jit(fwd))(
                self._variables,
                jax.ShapeDtypeStruct(shape, np.dtype(dtype_str)))
            fname = f"exec_{n}.bin"
            with open(os.path.join(path, fname), "wb") as f:
                f.write(exp.serialize())
            manifest["keys"].append({"shape": list(shape),
                                     "dtype": dtype_str, "file": fname,
                                     "jaxpr": self._computation_hash(
                                         shape, dtype_str)})
            n += 1
        with open(os.path.join(path, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        return n

    def load_executables(self, path: str, verify: bool = True) -> int:
        """Load serialized serving computations saved by
        ``save_executables``: deserialized artifacts skip lowering and —
        when the persistent compilation cache (``enable_aot_cache``) is
        warm — the XLA compile.  An artifact is ignored (falls back to a
        fresh compile) when the serving configuration differs from save
        time, or, with ``verify=True`` (default), when the CURRENT model
        code's traced computation no longer matches the saved one —
        catching silent staleness after a model edit at the cost of one
        trace per bucket (no lowering/compile).  ``verify=False`` is the
        trust-the-artifact fast path."""
        import json
        import os

        from jax import export as jexport

        mf = os.path.join(path, "manifest.json")
        if not os.path.exists(mf):
            return 0
        with open(mf) as f:
            manifest = json.load(f)
        if manifest.get("fingerprint") != self._config_fingerprint():
            return 0
        n = 0
        for item in manifest["keys"]:
            try:
                key = (tuple(item["shape"]), item["dtype"])
                if verify and item.get("jaxpr") != self._computation_hash(
                        key[0], key[1]):
                    continue  # model code changed: recompile this bucket
                with open(os.path.join(path, item["file"]), "rb") as f:
                    exp = jexport.deserialize(f.read())
                # ``exp.call`` re-traces the deserialized StableHLO on
                # EVERY invocation (~8.5x per-call overhead); compile it
                # once here so warm-reload predicts dispatch a cached
                # jax.stages.Compiled exactly like _fn_for's executables.
                # compile_count stays untouched — the XLA compile comes
                # from the persistent cache when enable_aot_cache is on,
                # and the hot-swap acceptance counts only fresh traces.
                var_struct = jax.tree_util.tree_map(
                    lambda l: jax.ShapeDtypeStruct(
                        getattr(l, "shape", ()),
                        getattr(l, "dtype", np.float32)),
                    self._variables)
                fn = (jax.jit(exp.call)
                      .lower(var_struct,
                             jax.ShapeDtypeStruct(key[0],
                                                  np.dtype(key[1])))
                      .compile())
                with self._lock:
                    self._compiled[key] = fn
                n += 1
            except Exception:  # topology/version mismatch: recompile
                continue
        return n

    def _fwd_for_export(self):
        """The serving forward as a pure fn of (variables, x) — the same
        computation ``_fn_for`` AOT-compiles."""
        model = self._model
        quantized = self._quantized
        cdtype = getattr(self, "_compute_dtype", None)
        qctx = getattr(self, "_quant_ctx", None)
        calibrated = frozenset(qctx.amax) if qctx is not None else None

        def fwd(variables, x):
            if quantized:
                variables = _dequantize_tree(
                    variables, cdtype, calibrated_paths=calibrated)
            out, _ = model.apply(variables, x, training=False, quant=qctx)
            return out

        return fwd

    def predict(self, x: np.ndarray) -> np.ndarray:
        """Batched forward; pads to the nearest bucket so compiles are
        bounded (one per bucket), trims the result."""
        if self._model is None:
            raise ValueError("no model loaded")
        x = np.asarray(x)
        n = x.shape[0]
        bucket = self._bucket(n)
        if n > bucket:  # larger than the largest bucket: chunk
            outs = [self.predict(x[i:i + bucket])
                    for i in range(0, n, bucket)]
            return np.concatenate(outs, axis=0)
        if n < bucket:
            pad = np.repeat(x[-1:], bucket - n, axis=0)
            xp = np.concatenate([x, pad], axis=0)
        else:
            xp = x
        xp = np.ascontiguousarray(xp)
        fn = self._fn_for(xp.shape, xp.dtype)
        with self._sema:  # bound in-flight host threads (replica semantics)
            out = fn(self._variables, xp)
        return np.asarray(out)[:n]

    # reference-parity aliases
    do_predict = predict
    do_load = load
