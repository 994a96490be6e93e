"""GPipe-style pipeline parallelism over the ``pipe`` mesh axis.

Absent from the reference (SURVEY.md §2.9: 'Pipeline parallel — ❌ absent').
TPU-native design: stage parameters are stacked on a leading dim sharded over
``pipe`` (each device owns one stage); inside ``shard_map`` a ``lax.scan``
runs the classic GPipe schedule — at step t, stage i processes microbatch
``t - i`` while activations rotate stage→stage+1 via ``lax.ppermute`` (ICI
neighbor hop).  The bubble is the usual (S-1)/(M+S-1); everything, including
the rotation, is differentiable, so the same code path trains.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def stacked_stage_init(stage_init: Callable[[jax.Array], Any],
                       n_stages: int, rng: jax.Array) -> Any:
    """Init one param tree per stage and stack leaves on a leading dim
    (shard it over ``pipe``)."""
    rngs = jax.random.split(rng, n_stages)
    trees = [stage_init(r) for r in rngs]
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *trees)


def _local_pipeline(stage_params, x_mb, *, apply_fn, axis_name, n_micro):
    """Runs inside shard_map.  stage_params leaves: [L, ...] — the L =
    n_stages/pipe_size stages this device owns, applied sequentially (one
    compound pipeline stage); x_mb: [M, mb, ...] microbatches (replicated
    across the pipe axis)."""
    size = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    n_local = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
    perm = [(i, (i + 1) % size) for i in range(size)]
    mb_shape = x_mb.shape[1:]

    def apply_local(xb):
        for j in range(n_local):
            params_j = jax.tree_util.tree_map(lambda l: l[j], stage_params)
            xb = apply_fn(params_j, xb)
        return xb

    def step(carry, t):
        incoming, outputs = carry
        # stage 0 injects microbatch t (clip: garbage cycles compute pad data)
        mb_idx = jnp.clip(t, 0, n_micro - 1)
        inp = jnp.where(my == 0, x_mb[mb_idx], incoming)
        out = apply_local(inp)
        # the last stage has produced microbatch t-(S-1) at step t
        done_idx = jnp.clip(t - (size - 1), 0, n_micro - 1)
        write = (my == size - 1) & (t >= size - 1)
        outputs = jax.lax.dynamic_update_index_in_dim(
            outputs,
            jnp.where(write, out,
                      jax.lax.dynamic_index_in_dim(outputs, done_idx, 0,
                                                   keepdims=False)),
            done_idx, 0)
        incoming = jax.lax.ppermute(out, axis_name, perm)
        return (incoming, outputs), None

    from .util import pvary_like
    outputs0 = pvary_like(jnp.zeros((n_micro,) + mb_shape, x_mb.dtype),
                          x_mb, stage_params)
    incoming0 = pvary_like(jnp.zeros(mb_shape, x_mb.dtype),
                           x_mb, stage_params)
    (_, outputs), _ = jax.lax.scan(step, (incoming0, outputs0),
                                   jnp.arange(n_micro + size - 1))
    # expose the per-stage outputs through a leading pipe-sharded dim; only
    # the last stage's block holds real data — the caller selects it
    return outputs[None]                                   # [1, M, mb, ...]


def pipeline_apply(apply_fn: Callable[[Any, jax.Array], jax.Array],
                   stage_params: Any, x: jax.Array, n_microbatches: int,
                   mesh: Optional[Mesh] = None, axis_name: str = "pipe"
                   ) -> jax.Array:
    """Run ``apply_fn(stage_params_i, x)`` as a pipeline over the mesh.

    stage_params: pytree with leading stage dim (from stacked_stage_init),
    sharded P('pipe', ...).  x: [B, ...] global batch; B must divide into
    n_microbatches.  Output shape == x shape (stages preserve shape, the
    GPipe constraint).
    """
    if mesh is None:
        from analytics_zoo_tpu.core import get_mesh
        mesh = get_mesh()
    if axis_name not in mesh.axis_names or mesh.shape[axis_name] == 1:
        # no pipe axis: run stages sequentially (same math, no comms)
        n = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
        out = x
        for i in range(n):
            params_i = jax.tree_util.tree_map(lambda l: l[i], stage_params)
            out = apply_fn(params_i, out)
        return out
    b = x.shape[0]
    if b % n_microbatches:
        raise ValueError(f"batch {b} not divisible into {n_microbatches} "
                         "microbatches")
    n_stages = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
    pipe_size = mesh.shape[axis_name]
    if n_stages % pipe_size:
        raise ValueError(
            f"{n_stages} stages do not divide over pipe axis of size "
            f"{pipe_size}; each device must own an equal number of stages")
    x_mb = x.reshape((n_microbatches, b // n_microbatches) + x.shape[1:])
    param_specs = jax.tree_util.tree_map(
        lambda l: P(axis_name, *([None] * (l.ndim - 1))), stage_params)
    # microbatch dim replicated over pipe; the batch dim inside each
    # microbatch stays sharded over the data axes (dp × pp composes)
    batch_axes = tuple(a for a in ("data", "fsdp")
                       if a in mesh.axis_names and mesh.shape[a] > 1)
    x_spec = P(None, batch_axes if batch_axes else None)
    out_spec = P(axis_name, None, batch_axes if batch_axes else None)
    fn = shard_map(
        functools.partial(_local_pipeline, apply_fn=apply_fn,
                          axis_name=axis_name, n_micro=n_microbatches),
        mesh=mesh, in_specs=(param_specs, x_spec), out_specs=out_spec)
    out = fn(stage_params, x_mb)          # [S, M, mb, ...]
    out = out[-1]                         # the last stage's collected outputs
    return out.reshape((b,) + out.shape[2:])
