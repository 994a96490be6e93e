"""Cluster bootstrap: the TPU-native replacement for init_orca_context.

Reference behavior being replaced (SURVEY.md §2.1, §3.1):
``init_orca_context`` (pyzoo/zoo/orca/common.py) built a SparkContext
(pyzoo/zoo/common/nncontext.py, pyzoo/zoo/util/spark.py) and optionally booted
a Ray cluster inside the Spark executors (pyzoo/zoo/ray/raycontext.py), giving
two overlapping clusters on the same nodes.  On TPU the idiomatic shape is one
Python process per TPU host: ``jax.distributed.initialize`` for multi-host
coordination over DCN, and a ``jax.sharding.Mesh`` over all chips with XLA
collectives over ICI.  The five transports of the reference (BlockManager,
Gloo, gRPC, plasma, py4j) collapse into this single compiled plane.
"""

from __future__ import annotations

import atexit
import contextlib
import json
import logging
import os
import threading
import time
from typing import Any, Dict, Optional, Sequence

import jax
import numpy as np

from .config import MeshConfig, ZooConfig

logger = logging.getLogger("analytics_zoo_tpu")

#: Where compiled programs persist when ``JAX_COMPILATION_CACHE_DIR`` does
#: not say: one fixed directory beside the package (``<checkout>/.jax_cache``).
#: Fixed on purpose — a cache whose directory moves between runs never hits.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache(path: Optional[str] = None) -> str:
    """Place JAX's persistent compilation cache; returns the directory in
    use.  ``JAX_COMPILATION_CACHE_DIR`` wins: JAX reads it itself, so when
    it is set no directory is set in code and ``path`` is ignored.
    Otherwise the cache lives at ``path``, by default
    :data:`DEFAULT_COMPILE_CACHE_DIR`.  Called by ``init_orca_context`` (and
    ``serving.enable_aot_cache``), so the trainer, the server and the CLIs
    all compile into the same place."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    placed = jax.config.jax_compilation_cache_dir
    if path is None and placed:
        return placed  # an earlier call in this process chose it
    path = path or DEFAULT_COMPILE_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class _Heartbeat:
    """Progress-based worker liveness: ``beat()`` rewrites the heartbeat
    file at most once per ``interval``.  Deliberately NOT a free-running
    daemon thread — a daemon would keep beating while the training loop is
    wedged, which is exactly the failure the supervisor must detect.  The
    training loop calls ``beat()`` every step; a worker whose steps stop
    (hang, deadlock, lost collective) stops beating and the zoo-launch
    supervisor kills and restarts the gang on heartbeat loss.

    The file is not just an mtime: each beat writes a small JSON status
    payload (``step``, ``loss``, ``samples_per_sec``, ``wall`` — whatever
    the caller last reported via keyword args) atomically (tmp + rename,
    so the supervisor never reads a torn write).  The supervisor
    aggregates these into a periodic gang-status log line and a
    per-worker ``metrics.jsonl`` (core/launcher.py); the rename keeps the
    mtime-based staleness check working unchanged."""

    def __init__(self, path: str, interval: float):
        self.path = path
        self.interval = max(0.05, float(interval))
        self._last = 0.0
        self._payload: Dict[str, Any] = {}

    def update(self, **fields: Any) -> None:
        """Merge status fields into the payload the next beat writes."""
        self._payload.update(fields)

    def beat(self, force: bool = False) -> None:
        now = time.monotonic()
        if not force and now - self._last < self.interval:
            return
        self._last = now
        try:
            payload = dict(self._payload, wall=time.time())
            tmp = self.path + ".tmp"
            with open(tmp, "w") as f:
                f.write(json.dumps(payload))
            os.replace(tmp, self.path)  # atomic: no torn reads, fresh mtime
        except OSError:  # liveness reporting must never kill training
            logger.debug("heartbeat touch failed for %s", self.path)


_HEARTBEAT: Optional[_Heartbeat] = None


def heartbeat(force: bool = False, **status: Any) -> None:
    """Report training progress to the gang supervisor (no-op unless a
    heartbeat file is configured).  Called from the Estimator step loop;
    long-running custom loops should call it too.  Keyword args (e.g.
    ``step=``, ``loss=``, ``samples_per_sec=``) become the JSON status
    payload the supervisor aggregates into its gang-status line.
    ``force=True`` bypasses the rate limit — used for milestone beats
    (epoch end) whose payload must land even on a fast loop."""
    hb = _HEARTBEAT
    if hb is not None:
        if status:
            hb.update(**status)
        hb.beat(force=force)


class _ZooContextMeta(type):
    """Metaclass exposing process-global knobs as class attributes, mirroring
    the reference's OrcaContext metaclass pattern (pyzoo/zoo/orca/common.py)."""

    _config: Optional[ZooConfig] = None
    _mesh: Optional[jax.sharding.Mesh] = None
    _lock = threading.RLock()

    @property
    def config(cls) -> ZooConfig:
        if cls._config is None:
            raise RuntimeError(
                "context not initialized — call init_orca_context() first")
        return cls._config

    @property
    def initialized(cls) -> bool:
        return cls._config is not None

    @property
    def mesh(cls) -> jax.sharding.Mesh:
        if cls._mesh is None:
            raise RuntimeError(
                "context not initialized — call init_orca_context() first")
        return cls._mesh

    # reference-parity knobs
    @property
    def pandas_read_backend(cls) -> str:
        return cls.config.pandas_read_backend

    @pandas_read_backend.setter
    def pandas_read_backend(cls, value: str) -> None:
        cls.config.pandas_read_backend = value


class OrcaContext(metaclass=_ZooContextMeta):
    """Process-global context singleton (reference: pyzoo/zoo/orca/common.py)."""


def config_default(field: str, fallback: Any) -> Any:
    """``ZooConfig.<field>`` when a context is initialized, else
    ``fallback`` — the one lookup every knob with a config-file default
    (serving ``inference_workers``/``staging_pool``, estimator
    ``prefetch``) shares, so a future ZooConfig default change cannot
    silently diverge from a hardcoded copy."""
    if OrcaContext.initialized:
        return getattr(OrcaContext.config, field, fallback)
    return fallback


def make_mesh(mesh_shape: Optional[str | Dict[str, int] | MeshConfig] = None,
              devices: Optional[Sequence[jax.Device]] = None,
              ) -> jax.sharding.Mesh:
    """Build a Mesh over the given devices.

    ``mesh_shape`` is a MeshConfig, a {axis: size} dict (see MeshConfig),
    or a sharding-strategy name (``"dp"``/``"fsdp"``/``"tp"``/``"2d"`` —
    ``MeshConfig.for_strategy``) so ``init_orca_context(mesh_shape="2d")``
    builds the data × model layout without hand-picking axis sizes.  One
    dict axis may be 0 to absorb the remaining devices.  Defaults to pure
    data parallelism over all devices — the only parallelism the reference
    had (SURVEY.md §2.9).
    """
    devices = list(devices if devices is not None else jax.devices())
    if isinstance(mesh_shape, MeshConfig):
        cfg = mesh_shape
    elif isinstance(mesh_shape, str):
        cfg = MeshConfig.for_strategy(mesh_shape, n_devices=len(devices))
    else:
        cfg = MeshConfig(**(mesh_shape or {"data": 0}))
    sizes = cfg.resolved(len(devices))
    axes = [a for a in MeshConfig.AXIS_ORDER if sizes[a] > 1]
    if not axes:  # single device: keep a 1-sized data axis so psum still works
        axes = ["data"]
    shape = tuple(sizes[a] for a in axes)
    used = int(np.prod(shape))
    if used < len(devices):
        if jax.process_count() > 1:
            # A subset mesh in multihost SPMD would leave some processes with
            # no addressable devices in the mesh — collectives would hang.
            raise ValueError(
                f"mesh covers {used} of {len(devices)} devices; subset meshes "
                "are not allowed in multihost mode (every process must own "
                "mesh devices). Use a wildcard axis (size 0) to cover all.")
        logger.warning("mesh covers %d of %d available devices; the rest "
                       "are idle", used, len(devices))
    dev_array = np.asarray(devices[:used]).reshape(shape)
    return jax.sharding.Mesh(dev_array, tuple(axes))


def init_orca_context(cluster_mode: str = "local",
                      mesh_shape: Optional[str | Dict[str, int]
                                           | MeshConfig] = None,
                      config: Optional[ZooConfig] = None,
                      coordinator_address: Optional[str] = None,
                      num_processes: Optional[int] = None,
                      process_id: Optional[int] = None,
                      log_level: Optional[str] = None,
                      **extra: Any) -> jax.sharding.Mesh:
    """Initialize the process-global context and device mesh.

    API parity with the reference's ``init_orca_context`` (pyzoo/zoo/orca/
    common.py) — ``cluster_mode`` selects local vs multi-host, everything else
    that used to configure Spark/Ray is subsumed by the mesh + ZooConfig.

    cluster_mode:
      - "local":      this process's devices only (1 TPU host or CPU sim).
      - "multihost":  call ``jax.distributed.initialize`` first so
                      ``jax.devices()`` spans all hosts (DCN coordination,
                      ICI/DCN collectives compiled by XLA).
    Returns the global Mesh.
    """
    with _ZooContextMeta._lock:
        if OrcaContext.initialized:
            logger.warning("init_orca_context called twice; reusing context")
            return OrcaContext.mesh

        cfg = config or ZooConfig()
        cfg.cluster_mode = cluster_mode
        if mesh_shape and not isinstance(mesh_shape, str):
            # strategy STRINGS resolve later, after jax.distributed is up:
            # len(jax.devices()) here would (a) initialize the local
            # backend before distributed.initialize — which JAX forbids —
            # and (b) size the mesh from one host's chips, not the pod's
            cfg.mesh = (mesh_shape if isinstance(mesh_shape, MeshConfig)
                        else MeshConfig(**mesh_shape))
        if coordinator_address:
            cfg.coordinator_address = coordinator_address
        if num_processes is not None:
            cfg.num_processes = num_processes
        if process_id is not None:
            cfg.process_id = process_id
        if log_level:
            cfg.log_level = log_level
        cfg.extra.update(extra)

        logging.basicConfig(level=getattr(logging, cfg.log_level, logging.INFO))
        logger.setLevel(getattr(logging, cfg.log_level, logging.INFO))
        configure_compile_cache()

        if cluster_mode == "multihost":
            # zoo-launch (core/launcher.py) passes the topology via env vars,
            # the same contract as the reference's spark-submit scripts
            # stuffing master/executor counts into the environment
            import os as _os
            if cfg.coordinator_address is None:
                cfg.coordinator_address = _os.environ.get("ZOO_COORDINATOR")
            if cfg.num_processes is None and "ZOO_NUM_PROCESSES" in _os.environ:
                cfg.num_processes = int(_os.environ["ZOO_NUM_PROCESSES"])
            if cfg.process_id is None and "ZOO_PROCESS_ID" in _os.environ:
                cfg.process_id = int(_os.environ["ZOO_PROCESS_ID"])
            jax.distributed.initialize(
                coordinator_address=cfg.coordinator_address,
                num_processes=cfg.num_processes,
                process_id=cfg.process_id)
        elif cluster_mode != "local":
            raise ValueError(
                f"unknown cluster_mode {cluster_mode!r}; the reference's "
                "yarn/k8s/standalone modes map to 'multihost' here (resource "
                "management is the TPU platform's job, not the framework's)")

        if cfg.faults:
            from .faults import get_registry
            get_registry().configure(cfg.faults)
            logger.warning("fault injection armed from config: %s",
                           sorted(cfg.faults))

        # telemetry knobs (core/trace.py + core/flightrec.py): the
        # slow-request threshold and span-ring capacity were
        # module-attribute-only; the config file is now the one place a
        # deployment tunes them.  The flight recorder arms when a dump
        # directory is configured (or the supervisor exported one).
        if cfg.trace_slow_ms is not None or cfg.trace_ring is not None:
            from . import trace as trace_lib
            trace_lib.configure(slow_ms=cfg.trace_slow_ms,
                                max_records=cfg.trace_ring)
        if cfg.flightrec_dir or os.environ.get("ZOO_FLIGHTREC_DIR"):
            from . import flightrec
            if cfg.flightrec_dir:
                flightrec.configure(cfg.flightrec_dir)
            flightrec.install_signal_dump()

        # supervisor liveness contract (core/launcher.py): touch the
        # heartbeat file now — "import + init finished" is the first beat —
        # then let the training loop beat on progress
        global _HEARTBEAT
        if cfg.heartbeat_file is None:
            cfg.heartbeat_file = os.environ.get("ZOO_HEARTBEAT_FILE")
        if cfg.heartbeat_interval is None:
            cfg.heartbeat_interval = float(
                os.environ.get("ZOO_HEARTBEAT_INTERVAL", "1.0"))
        if cfg.heartbeat_file:
            _HEARTBEAT = _Heartbeat(cfg.heartbeat_file,
                                    cfg.heartbeat_interval)
            _HEARTBEAT.beat(force=True)

        if isinstance(mesh_shape, str):  # now jax.devices() spans the pod
            cfg.mesh = MeshConfig.for_strategy(
                mesh_shape, n_devices=len(jax.devices()))
        _ZooContextMeta._mesh = make_mesh(cfg.mesh)
        _ZooContextMeta._config = cfg
        logger.info("initialized context: %d device(s), mesh %s",
                    len(jax.devices()),
                    dict(zip(OrcaContext.mesh.axis_names,
                             OrcaContext.mesh.devices.shape)))
        atexit.register(stop_orca_context)
        return OrcaContext.mesh


def stop_orca_context() -> None:
    """Tear down the global context (reference: stop_orca_context — which had
    to kill Ray raylets and the SparkContext; here there is nothing to kill
    beyond forgetting the globals, since collectives are compiled, not
    daemonized)."""
    global _HEARTBEAT
    with _ZooContextMeta._lock:
        _ZooContextMeta._config = None
        _ZooContextMeta._mesh = None
        _HEARTBEAT = None


_held = threading.local()  # mesh_scope's mesh, on the thread that holds it


@contextlib.contextmanager
def mesh_scope(mesh: jax.sharding.Mesh):
    """For the length of the block ``get_mesh()`` answers ``mesh`` on this
    thread and starts no context: for tracing a program again for the mesh
    it was built for, after the context it ran under was stopped (the
    Estimator's ``trace.register_program`` callable)."""
    before = getattr(_held, "mesh", None)
    _held.mesh = mesh
    try:
        yield mesh
    finally:
        _held.mesh = before


def get_mesh() -> jax.sharding.Mesh:
    """The global mesh, initializing a local default context if needed."""
    held = getattr(_held, "mesh", None)
    if held is not None:
        return held
    if not OrcaContext.initialized:
        init_orca_context("local")
    return OrcaContext.mesh


# Reference-parity aliases (pyzoo/zoo/common/nncontext.py exposed several
# spellings of "give me a context").
init_nncontext = init_orca_context
