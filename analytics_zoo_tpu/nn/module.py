"""Minimal functional module system: the base of the Keras-style layer API.

Reference (SURVEY.md §2.3): the Keras-1.2-style API was ~25k LoC of Scala
layers over BigDL's imperative module graph (zoo/src/main/scala/com/intel/
analytics/zoo/pipeline/api/keras/) plus 10k LoC of py4j mirrors
(pyzoo/zoo/pipeline/api/keras/).  Layers held mutable weights; training
mutated them in place inside the JVM.

TPU-native redesign: layers are *pure functions* of an explicit variables
pytree, the form XLA wants — ``init`` builds {"params", "state"} by tracing
the layer once over example inputs; ``apply`` is referentially transparent
(jit/grad/vmap/shard_map compose over it).  A small ``Scope`` object threads
parameter creation, RNG splitting, and BatchNorm-style mutable state through
nested submodules, so layer code reads like Keras but compiles like JAX.

No flax dependency: the whole mechanism is this file.
"""

from __future__ import annotations

import zlib
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


class Scope:
    """Threads variable access through one ``init`` or ``apply`` trace."""

    def __init__(self, params: Params, state: Params, rng: Optional[jax.Array],
                 training: bool, init_mode: bool, path: Tuple[str, ...] = (),
                 taps: Optional[Dict[str, Any]] = None,
                 quant: Optional[Any] = None):
        self.params = params
        self.state = state
        self.rng = rng
        self.training = training
        self.init_mode = init_mode
        self.path = path
        self.taps = taps  # shared dict: child outputs recorded by path
        self.quant = quant  # int8 serving context (nn.quant), or None
        self._rng_count = 0
        self._child_counts: Dict[str, int] = {}
        # name → module object.  The object itself (not id()) is kept so the
        # identity check can't false-positive when CPython reuses a freed
        # module's address for a new one.
        self._child_seen: Dict[str, "Module"] = {}
        self._reuse = False  # re-executing a shared layer: params exist

    # -- variables ------------------------------------------------------------

    def param(self, name: str, initializer: Callable, shape: Sequence[int],
              dtype: Any = jnp.float32) -> jax.Array:
        if self.init_mode:
            if name in self.params:
                if self._reuse:  # shared layer re-executed: same weights
                    return self.params[name]
                raise ValueError(f"duplicate param {name!r} at {self.path}")
            self.params[name] = initializer(self.make_rng(), tuple(shape), dtype)
        if name not in self.params:
            raise KeyError(f"missing param {name!r} at {'/'.join(self.path)}")
        return self.params[name]

    def variable(self, name: str, init_fn: Callable[[], jax.Array]) -> jax.Array:
        """Non-trainable state (e.g. BatchNorm running stats)."""
        if self.init_mode and name not in self.state:
            self.state[name] = init_fn()
        return self.state[name]

    def put_variable(self, name: str, value: jax.Array) -> None:
        """Record a state update (visible in the new_state returned by apply).
        No-op during init: init captures initial values, not updates."""
        if not self.init_mode:
            self.state[name] = value

    # -- rng ------------------------------------------------------------------

    def make_rng(self) -> jax.Array:
        if self.rng is None:
            raise ValueError(
                f"layer at {'/'.join(self.path)} needs an rng (pass rng= to "
                "init/apply, required for dropout in training mode)")
        self._rng_count += 1
        return jax.random.fold_in(self.rng, self._rng_count)

    # -- submodules -----------------------------------------------------------

    def child(self, module: "Module", *args: Any, name: Optional[str] = None,
              **kwargs: Any) -> Any:
        """Run a submodule under a nested scope."""
        if name is None:
            base = module.name or _snake(type(module).__name__)
            idx = self._child_counts.get(base, 0)
            self._child_counts[base] = idx + 1
            name = base if idx == 0 else f"{base}_{idx}"
        sub_params = self.params.setdefault(name, {}) if self.init_mode else \
            self.params.get(name, {})
        sub_state_in = self.state.get(name, {})
        sub_state = dict(sub_state_in) if not self.init_mode else \
            self.state.setdefault(name, {})
        # zlib.crc32 (not hash()): stable across processes so every SPMD host
        # derives identical init RNGs for identically-named layers.
        sub = Scope(sub_params, sub_state,
                    jax.random.fold_in(self.rng, zlib.crc32(name.encode()))
                    if self.rng is not None else None,
                    self.training, self.init_mode, self.path + (name,),
                    taps=self.taps, quant=self.quant)
        # weight sharing: re-executing the SAME layer object under the same
        # name (a shared layer in a functional graph) reuses its params; a
        # DIFFERENT module under an already-used name is a naming bug and
        # keeps the duplicate-param guard
        prev = self._child_seen.get(name)
        if prev is not None and prev is not module and self.init_mode \
                and not self._reuse:
            raise ValueError(
                f"two different modules share the child name {name!r} at "
                f"{'/'.join(self.path) or '<root>'}; give them distinct "
                "names (weight sharing requires the same layer object)")
        sub._reuse = self._reuse or prev is module
        self._child_seen[name] = module
        # the child's name on every op traced under it, so that a profile
        # reads .../bert/layer_3/attention/... (backward too: JAX names the
        # transposed ops after the forward's scope)
        with jax.named_scope(name):
            out = module.forward(sub, *args, **kwargs)
        if not self.init_mode and (sub.state or sub_state_in):
            self.state[name] = sub.state
        if self.taps is not None:
            key = base_key = "/".join(self.path + (name,))
            i = 1
            while key in self.taps:  # shared layer: one tap per application
                key = f"{base_key}#{i}"
                i += 1
            self.taps[key] = out
        return out


class Module:
    """Base class for all layers.  Subclasses implement ``forward(scope, ...)``."""

    #: values this module's kernels tag (``jax.ad_checkpoint.checkpoint_name``)
    #: for their own backward, which an enclosing ``nn.Remat`` should keep
    #: rather than recompute (``models.decoder.stack`` asks it)
    remat_keep: Tuple[str, ...] = ()

    def __init__(self, name: Optional[str] = None):
        self.name = name

    def forward(self, scope: Scope, *args: Any, **kwargs: Any) -> Any:
        raise NotImplementedError

    # -- public API -----------------------------------------------------------

    def init(self, rng: jax.Array, *args: Any, training: bool = False,
             **kwargs: Any) -> Params:
        """Trace once over example inputs; returns {"params", "state"}."""
        args = tuple(_as_jax(a) for a in args)
        scope = Scope({}, {}, rng, training, init_mode=True)
        self.forward(scope, *args, **kwargs)
        return {"params": scope.params, "state": scope.state}

    def apply(self, variables: Params, *args: Any, training: bool = False,
              rng: Optional[jax.Array] = None, quant: Optional[Any] = None,
              **kwargs: Any) -> Tuple[Any, Params]:
        """Pure application: returns (output, new_state).  ``quant``: an
        nn.quant context for int8 serving (calibration or apply mode)."""
        state_in = variables.get("state", {})
        scope = Scope(variables.get("params", {}), dict(state_in), rng,
                      training, init_mode=False, quant=quant)
        out = self.forward(scope, *args, **kwargs)
        return out, scope.state

    def apply_with_taps(self, variables: Params, *args: Any,
                        training: bool = False,
                        rng: Optional[jax.Array] = None, **kwargs: Any
                        ) -> Tuple[Any, Params, Dict[str, Any]]:
        """Like ``apply`` but also returns every submodule's output keyed by
        its scope path ("block0/mha", ...) — the functional analog of the
        reference's GraphNet intermediate-output surgery
        (zoo/.../pipeline/api/net/GraphNet.scala ``newGraph``)."""
        state_in = variables.get("state", {})
        taps: Dict[str, Any] = {}
        scope = Scope(variables.get("params", {}), dict(state_in), rng,
                      training, init_mode=False, taps=taps)
        out = self.forward(scope, *args, **kwargs)
        return out, scope.state, taps

    def __call__(self, scope_or_vars: Any, *args: Any, **kwargs: Any) -> Any:
        """Inside another module's forward: ``layer(scope, x)`` delegates via
        the parent scope (auto-named child).  On SymbolicTensors: records a
        functional-graph node (nn.functional).  Outside: alias for apply."""
        if isinstance(scope_or_vars, Scope):  # the hot path: no import
            return scope_or_vars.child(self, *args, **kwargs)
        # a symbolic arg can only be the input itself or a (nested) list of
        # inputs — never inside a variables dict, so dicts are not walked
        from .functional import _contains_symbolic, symbolic_call
        maybe = (scope_or_vars,) + args
        if any(not isinstance(m, dict) and _contains_symbolic(m)
               for m in maybe):
            return symbolic_call(self, scope_or_vars, *args, **kwargs)
        return self.apply(scope_or_vars, *args, **kwargs)

    # convenience
    def init_apply(self, rng: jax.Array, *args: Any, **kwargs: Any
                   ) -> Tuple[Params, Any]:
        variables = self.init(rng, *args, **kwargs)
        out, _ = self.apply(variables, *args, **kwargs)
        return variables, out

    def summary(self, variables: Params, *args: Any,
                print_fn: Optional[Callable[[str], None]] = print,
                **kwargs: Any) -> str:
        """Keras-style layer table: path, output shape, param count
        (reference: KerasNet.summary — Topology.scala).  Shapes come from
        an abstract trace (jax.eval_shape) — no compute, no activation
        memory."""
        exec_order: List[str] = []

        def traced(v, *a):
            out, state, taps = self.apply_with_taps(v, *a, **kwargs)
            # pytree round-trips sort dict keys; execution order must be
            # captured as a trace side effect (the trace runs exactly once)
            exec_order.extend(taps.keys())
            return out, state, taps

        _, _, taps = jax.eval_shape(traced, variables, *args)

        def count(tree: Any) -> int:
            return sum(int(np.prod(l.shape)) for l in
                       jax.tree_util.tree_leaves(tree)
                       if hasattr(l, "shape"))

        def shape_of(out: Any) -> str:
            leaves = [l for l in jax.tree_util.tree_leaves(out)
                      if hasattr(l, "shape")]
            if not leaves:
                return "-"
            s = ", ".join(str(tuple(l.shape)) for l in leaves[:3])
            return s + (", ..." if len(leaves) > 3 else "")

        params = variables.get("params", {})
        rows = [("layer (path)", "output shape", "params")]
        for path in exec_order:
            # param counts are reported on top-level rows only (nested rows
            # would double-count their parent's subtree)
            top_level = "/" not in path and "#" not in path
            sub = params.get(path, {}) if top_level else None
            rows.append((path, shape_of(taps[path]),
                         str(count(sub)) if sub is not None else ""))
        total = count(params)
        widths = [max(len(r[i]) for r in rows) for i in range(3)]
        lines = ["  ".join(c.ljust(w) for c, w in zip(r, widths))
                 for r in rows]
        lines.insert(1, "-" * (sum(widths) + 4))
        lines.append("-" * (sum(widths) + 4))
        lines.append(f"total params: {total:,}")
        text = "\n".join(lines)
        if print_fn:
            print_fn(text)
        return text


def _snake(s: str) -> str:
    out = []
    for i, c in enumerate(s):
        if c.isupper() and i and (not s[i - 1].isupper()):
            out.append("_")
        out.append(c.lower())
    return "".join(out)


def _as_jax(a: Any) -> Any:
    if isinstance(a, (np.ndarray, np.generic, float, int)):
        return jnp.asarray(a)
    return a


def param_count(variables: Params) -> int:
    leaves = jax.tree_util.tree_leaves(variables.get("params", variables))
    return sum(int(np.prod(l.shape)) for l in leaves if hasattr(l, "shape"))
