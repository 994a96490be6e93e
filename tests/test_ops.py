"""Differential tests: flash attention vs materialized reference.

Mirrors the reference's TFNet/TorchNet differential-test pattern (SURVEY.md
§4.4): run both implementations on the same inputs, compare within tolerance.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops import flash_attention, mha_reference

# the MODULE: ``import analytics_zoo_tpu.ops.flash_attention as m`` binds the
# function of the same name that ops/__init__ re-exports, and setting
# INTERPRET on that would leave the kernel unexercised
fa_mod = importlib.import_module("analytics_zoo_tpu.ops.flash_attention")


def _qkv(rng, b=2, t=64, h=2, d=16):
    shape = (b, t, h, d)
    return (jnp.asarray(rng.normal(size=shape), jnp.float32),
            jnp.asarray(rng.normal(size=shape), jnp.float32),
            jnp.asarray(rng.normal(size=shape), jnp.float32))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(rng, causal):
    q, k, v = _qkv(rng)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_reference(rng, causal):
    q, k, v = _qkv(rng, b=1, t=32, h=2, d=8)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=8,
                               block_k=8).sum()

    def loss_ref(q, k, v):
        return mha_reference(q, k, v, causal=causal).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


def test_pallas_kernel_interpret_mode(rng):
    """Run the actual Pallas kernel (interpret mode) against the reference,
    including a T that does not divide the block size (padding path)."""
    q, k, v = _qkv(rng, b=1, t=24, h=1, d=8)
    fa_mod.INTERPRET = True
    try:
        out = flash_attention(q, k, v, block_q=16, block_k=16)
    finally:
        fa_mod.INTERPRET = False
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_pallas_kernel_interpret_causal(rng):
    q, k, v = _qkv(rng, b=1, t=32, h=1, d=8)
    fa_mod.INTERPRET = True
    try:
        out = flash_attention(q, k, v, causal=True, block_q=8, block_k=8)
    finally:
        fa_mod.INTERPRET = False
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_under_jit_and_mha_layer(rng):
    """use_flash=True path of nn.MultiHeadAttention compiles and runs."""
    import analytics_zoo_tpu.nn as nn
    x = jnp.asarray(rng.normal(size=(2, 16, 32)), jnp.float32)
    mha = nn.MultiHeadAttention(num_heads=4, use_flash=True)
    variables = mha.init(jax.random.PRNGKey(0), x)
    out, _ = jax.jit(lambda v, x: mha.apply(v, x))(variables, x)
    assert out.shape == (2, 16, 32)


def test_fused_softmax_xent_matches_naive():
    """Loss value AND all three gradients must match the materialized
    logits path (chunked recompute is numerics-preserving in f32)."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import fused_softmax_xent
    rng = np.random.default_rng(0)
    B, S, D, V = 2, 8, 16, 50
    h = rng.normal(size=(B, S, D)).astype(np.float32)
    w = (rng.normal(size=(D, V)) * 0.1).astype(np.float32)
    labels = rng.integers(0, V, (B, S))

    bias = (rng.normal(size=(V,)) * 0.1).astype(np.float32)

    def naive(h, w, bias):
        logits = (h @ w).astype(jnp.float32) + bias
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        corr = jnp.take_along_axis(
            logits, jnp.asarray(labels)[..., None], axis=-1)[..., 0]
        return (lse - corr).mean()

    def fused(h, w, bias):
        return fused_softmax_xent(h, w, jnp.asarray(labels), 4, bias=bias)

    ln, gn = jax.value_and_grad(naive, argnums=(0, 1, 2))(h, w, bias)
    lf, gf = jax.value_and_grad(fused, argnums=(0, 1, 2))(h, w, bias)
    np.testing.assert_allclose(float(lf), float(ln), rtol=1e-6)
    for a, b in zip(gf, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_fused_softmax_xent_bf16_close():
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import fused_softmax_xent
    rng = np.random.default_rng(1)
    h = rng.normal(size=(1, 16, 8)).astype(np.float32)
    w = (rng.normal(size=(8, 30)) * 0.2).astype(np.float32)
    labels = jnp.asarray(rng.integers(0, 30, (1, 16)))
    lf32 = fused_softmax_xent(jnp.asarray(h), jnp.asarray(w), labels, 8)
    lbf = fused_softmax_xent(jnp.asarray(h, jnp.bfloat16),
                             jnp.asarray(w, jnp.bfloat16), labels, 8)
    np.testing.assert_allclose(float(lbf), float(lf32), rtol=3e-2)


def test_fused_softmax_xent_rejects_bad_chunk():
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import fused_softmax_xent
    with pytest.raises(ValueError, match="divisible"):
        fused_softmax_xent(jnp.zeros((2, 5, 4)), jnp.zeros((4, 7)),
                           jnp.zeros((2, 5), jnp.int32), 3)
