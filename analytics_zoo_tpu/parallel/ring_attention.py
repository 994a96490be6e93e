"""Ring attention: sequence/context parallelism over the ``seq`` mesh axis.

Capability the reference lacked entirely (SURVEY.md §5.7: max sequence length
was bounded by one CPU node's memory).  TPU-native design: the sequence dim is
sharded across devices; each device computes attention of its local queries
against the key/value chunk it currently holds, accumulating an online
softmax, while K/V chunks rotate around the ring via ``lax.ppermute`` — ICI
neighbor traffic fully overlapped by XLA with the per-chunk matmuls.  Memory
per device is O(T/n · D); total sequence length scales linearly with the ring
size.

Differentiable end-to-end (ppermute and the scan are differentiable), so it
drops into the Estimator's train step.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str = "seq", causal: bool = False) -> jax.Array:
    """Attention over a ring: call INSIDE shard_map with q,k,v local blocks.

    q, k, v: [B, T_local, H, D] — the local sequence chunk of this device.
    Returns [B, T_local, H, D].  Softmax scale = 1/sqrt(D).
    """
    size = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    b, t_loc, h, d = q.shape
    scale = 1.0 / (d ** 0.5)
    qf = q.astype(jnp.float32)
    perm = [(i, (i + 1) % size) for i in range(size)]

    # global positions of my queries
    qpos = my * t_loc + jnp.arange(t_loc)                      # [T_local]

    def step(carry, step_idx):
        m_prev, l_prev, acc, k_cur, v_cur = carry
        # after `step_idx` rotations I hold the chunk of device (my - step)
        owner = (my - step_idx) % size
        kpos = owner * t_loc + jnp.arange(t_loc)               # [T_local]
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_cur.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * scale
        if causal:
            mask = qpos[:, None] >= kpos[None, :]              # [Tq, Tk]
            s = jnp.where(mask[None, None], s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)             # [B,H,Tq,1]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        upd = jnp.einsum("bhqk,bkhd->bhqd", p, v_cur.astype(jnp.float32))
        acc = acc * alpha + upd
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (m_new, l_new, acc, k_nxt, v_nxt), None

    from .util import pvary_like
    init = (pvary_like(jnp.full((b, h, t_loc, 1), _NEG_INF, jnp.float32),
                       q, k, v),
            pvary_like(jnp.zeros((b, h, t_loc, 1), jnp.float32), q, k, v),
            pvary_like(jnp.zeros((b, h, t_loc, d), jnp.float32), q, k, v),
            k, v)
    (m, l, acc, _, _), _ = jax.lax.scan(step, init, jnp.arange(size))
    out = acc / jnp.maximum(l, 1e-30)                          # [B,H,Tq,D]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def ring_self_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        mesh: Optional[Mesh] = None, causal: bool = False,
                        seq_axis: str = "seq") -> jax.Array:
    """shard_map wrapper: q,k,v are GLOBAL [B, T, H, D] arrays (T sharded over
    the ``seq`` axis by GSPMD); falls back to plain attention when the mesh
    has no seq axis."""
    if mesh is None:
        from analytics_zoo_tpu.core import get_mesh
        mesh = get_mesh()
    if seq_axis not in mesh.axis_names or mesh.shape[seq_axis] == 1:
        from analytics_zoo_tpu.nn.attention import (causal_mask,
                                                    dot_product_attention)
        mask = causal_mask(q.shape[1]) if causal else None
        return dot_product_attention(q, k, v, mask)
    batch_axes = tuple(a for a in ("data", "fsdp") if a in mesh.axis_names)
    spec = P(batch_axes if batch_axes else None, seq_axis, None, None)
    fn = shard_map(
        functools.partial(ring_attention, axis_name=seq_axis, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
