"""shard_map helpers + quantized gradient-collective building blocks.

The compression half models EQuARX-style quantized AllReduce (PAPERS.md):
the gradient all-reduce is the dominant communication cost of data-parallel
scale-out, and its payload tolerates aggressive width reduction.  The train
step decomposes its batch into one slice per mesh batch shard, computes
per-shard gradients, and reduces them through :func:`compressed_allreduce` —
each shard's contribution is quantized exactly as it would be on the wire,
so the numerics here ARE the numerics of a quantized collective (per-device
scales, error-feedback residuals), not a post-hoc approximation of one.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

#: Valid values for ``ZooEstimator(grad_compression=...)`` (beyond None).
GRAD_COMPRESSION = ("none", "bf16", "int8")

#: Guard against divide-by-zero on all-zero gradient leaves.
_SCALE_FLOOR = 1e-30


def pvary_like(x, *refs):
    """Mark ``x`` as varying over every manual mesh axis any of ``refs`` is
    varying over.  Needed for lax.scan carries inside shard_map: a
    freshly-created zeros init is 'unvarying', but the scan body produces
    'varying' values, and JAX rejects the mismatch.  No-op outside
    shard_map."""
    vma = set()
    for r in refs:
        for leaf in jax.tree_util.tree_leaves(r):
            vma |= set(jax.typeof(leaf).vma)
    if not vma:
        return x
    return jax.tree_util.tree_map(
        lambda l: jax.lax.pcast(l, tuple(sorted(vma)), to="varying"), x)


# -- mesh batch-shard geometry ------------------------------------------------
# Delegates to data/feed.py's BATCH_AXES/batch_axis_size — ONE source of
# truth for "which mesh axes carry the batch", so grad-compression shard
# counts can never diverge from how the feed actually shards batches.

def batch_shard_count(mesh: Mesh) -> int:
    """Number of batch shards = number of per-device gradient contributions
    the data-parallel all-reduce combines (== the feed's batch axis size)."""
    from analytics_zoo_tpu.data.feed import batch_axis_size
    return batch_axis_size(mesh)


def batch_shard_spec(mesh: Mesh, rank: int) -> P:
    """PartitionSpec placing a ``[n_shards, ...]`` stacked tensor with one
    slice per batch shard (dim 0 over the feed's batch axes, rest
    replicated).  ``make_mesh`` drops size-1 axes, so every present axis
    is sized."""
    from analytics_zoo_tpu.data.feed import BATCH_AXES
    axes = tuple(a for a in BATCH_AXES if a in mesh.axis_names)
    if not axes:
        return P()
    dim0 = axes if len(axes) > 1 else axes[0]
    return P(dim0, *([None] * max(0, rank - 1)))


# -- quantized all-reduce -----------------------------------------------------

def quantize_int8(g: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-(shard, leaf) int8 quantization of a ``[S, ...]``
    stacked gradient: one max-abs scale per leading slice (each shard
    quantizes its OWN contribution, as it would before hitting the wire).
    Returns ``(q int8, scale f32 broadcastable against g)``."""
    reduce_axes = tuple(range(1, g.ndim))
    scale = jnp.max(jnp.abs(g), axis=reduce_axes, keepdims=True) / 127.0
    scale = jnp.maximum(scale, _SCALE_FLOOR)
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compressed_allreduce(stacked: Any, method: str, ef: Optional[Any] = None
                         ) -> Tuple[Any, Optional[Any]]:
    """Reduce per-shard gradients ``[S, ...]`` to their mean, through the
    configured wire width.  Pure jax — compiles into the train step.

    - ``"none"``: f32 sum (the uncompressed baseline, for probes; the
      estimator's ``grad_compression="none"`` keeps the implicit-psum path
      and never calls this on the step).
    - ``"bf16"``: each shard's contribution rounds to bfloat16 before the
      reduce (wire = 2 bytes/param); accumulation is f32, the favorable
      EQuARX configuration.
    - ``"int8"``: each shard quantizes ``g + residual`` with a per-(shard,
      leaf) symmetric scale, the dequantized contributions sum in f32, and
      the quantization error becomes the next step's residual
      (error feedback — the bias corrector that makes 1-byte gradients
      converge).  Requires ``ef``: a pytree matching ``stacked``.

    Returns ``(mean_grads, new_ef)`` — ``new_ef`` is None unless int8.
    """
    leaves = jax.tree_util.tree_leaves(stacked)
    if not leaves:
        return stacked, ef
    s = leaves[0].shape[0]

    if method in ("none", None):
        red = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32).sum(0) / s, stacked)
        return red, None
    if method == "bf16":
        red = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.bfloat16).astype(jnp.float32).sum(0) / s,
            stacked)
        return red, None
    if method == "int8":
        if ef is None:
            ef = jax.tree_util.tree_map(
                lambda g: jnp.zeros_like(g, jnp.float32), stacked)

        def red(g, r):
            gin = g.astype(jnp.float32) + r
            q, scale = quantize_int8(gin)
            deq = q.astype(jnp.float32) * scale
            return deq.sum(0) / s, gin - deq

        pairs = jax.tree_util.tree_map(red, stacked, ef)
        outer = jax.tree_util.tree_structure(stacked)
        inner = jax.tree_util.tree_structure((0, 0))
        return jax.tree_util.tree_transpose(outer, inner, pairs)
    raise ValueError(f"unknown grad compression {method!r}; "
                     f"known: {GRAD_COMPRESSION}")


def grad_wire_bytes(params: Any, method: Optional[str]) -> int:
    """Bytes of gradient payload ONE device contributes to the all-reduce
    per step, at the configured wire width (the ``train.grad_bytes``
    series).  Counts the tensor payload only: int8's per-leaf f32 scales
    (4 bytes per parameter LEAF, < 0.01% for real models) ride the
    collective's metadata and are excluded from both sides of the ratio."""
    n = sum(int(jnp.size(leaf)) for leaf in jax.tree_util.tree_leaves(params))
    per = {"none": 4, None: 4, "bf16": 2, "int8": 1}.get(method)
    if per is None:
        raise ValueError(f"unknown grad compression {method!r}; "
                         f"known: {GRAD_COMPRESSION}")
    return per * n
