"""The chunked gated delta rule (``nn/linear_attention.py``
``gated_delta_rule``): the ``jax.numpy`` form and the Pallas kernels of
``ops/gated_delta_rule.py`` in interpret mode, against the recurrence
position by position kept with the benchmark
(``benchmark/families/qwen3_next.py`` ``delta_rule_reference``), and the
kernels' blocked inverse against numpy's.  Moved here whole from
``tests/test_qwen3_next.py`` (PR 36), so that a second worker can take them."""

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from analytics_zoo_tpu.nn.linear_attention import gated_delta_rule  # noqa: E402
from benchmark.families import qwen3_next as fam  # noqa: E402

gdr = importlib.import_module("analytics_zoo_tpu.ops.gated_delta_rule")


def _rel(a, b):
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())



def _rule_inputs(t, h=3, dk=16, dv=24, seed=0, decay=0.3, hk=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    hk = hk or h
    return (unit(jax.random.normal(ks[0], (2, t, hk, dk))) * dk ** -0.5,
            unit(jax.random.normal(ks[1], (2, t, hk, dk))),
            jax.random.normal(ks[2], (2, t, h, dv)),
            -decay * jax.nn.softplus(jax.random.normal(ks[3], (2, t, h))),
            jax.nn.sigmoid(jax.random.normal(ks[4], (2, t, h))))


def _recurrence(q, k, v, g, beta):
    """The family's position-by-position reference, every value head given
    its key head's q and k."""
    group = v.shape[2] // k.shape[2]
    return fam.delta_rule_reference(jnp.repeat(q, group, 2),
                                    jnp.repeat(k, group, 2), v, g, beta)


# "kernels": the Pallas kernels of ops/gated_delta_rule.py in interpret mode,
# two value heads to a key head; "jax": the chunked jax.numpy form, which a
# CPU takes by default
@pytest.fixture(params=["jax", "kernels"])
def path(request, monkeypatch):
    if request.param == "kernels":
        monkeypatch.setattr(gdr, "INTERPRET", True)
    return request.param


def _heads(path):
    return dict(h=4, hk=2) if path == "kernels" else dict(h=3)


# lengths that are and are not multiples of the chunk; a decay strong enough
# that exp(g_i - g_j) above the diagonal would overflow if it were computed
@pytest.mark.parametrize("t,chunk,decay", [
    (64, 16, 0.3), (128, 64, 0.3), (50, 16, 0.3), (100, 64, 0.01),
    (7, 16, 0.3), (96, 32, 40.0)])
def test_chunked_delta_rule_matches_the_recurrence(t, chunk, decay, path,
                                                   monkeypatch):
    args = _rule_inputs(t, decay=decay, **_heads(path))
    heads = args[2].shape[2]
    want = _recurrence(*args)
    got, state = gated_delta_rule(*args, chunk=chunk)
    assert got.shape == want.shape and state.shape == (2, heads, 16, 24)
    assert _rel(got, want) < 2e-5

    def loss(rule):
        return lambda *a: jnp.sum(jnp.square(rule(*a)))
    g_got = jax.grad(loss(lambda *a: gated_delta_rule(*a, chunk=chunk)[0]),
                     argnums=range(5))(*args)
    g_want = jax.grad(loss(_recurrence), argnums=range(5))(*args)
    for a, b in zip(g_got, g_want):
        assert np.isfinite(np.asarray(a)).all()
        assert _rel(a, b) < 2e-4
    if path == "kernels":  # ... and against the chunked jax.numpy form
        monkeypatch.setattr(gdr, "INTERPRET", False)
        same, s_same = gated_delta_rule(*args, chunk=chunk)
        g_same = jax.grad(
            loss(lambda *a: gated_delta_rule(*a, chunk=chunk)[0]),
            argnums=range(5))(*args)
        assert _rel(got, same) < 2e-5 and _rel(state, s_same) < 2e-5
        for a, b in zip(g_got, g_same):
            assert _rel(a, b) < 2e-4


def test_delta_rule_carries_a_state_between_calls(path):
    q, k, v, g, beta = _rule_inputs(96, **_heads(path))
    whole, s_whole = gated_delta_rule(q, k, v, g, beta, chunk=16)
    head, s = gated_delta_rule(*(a[:, :40] for a in (q, k, v, g, beta)),
                               chunk=16)
    tail, s_tail = gated_delta_rule(*(a[:, 40:] for a in (q, k, v, g, beta)),
                                    chunk=16, initial_state=s)
    assert _rel(jnp.concatenate([head, tail], 1), whole) < 2e-5
    assert _rel(s_tail, s_whole) < 2e-5


def test_delta_rule_in_bfloat16_stays_near_the_recurrence(path):
    q, k, v, g, beta = _rule_inputs(256, dk=64, dv=64, **_heads(path))
    want = _recurrence(q, k, v, g, beta)
    got, _ = gated_delta_rule(*(a.astype(jnp.bfloat16) for a in (q, k, v)),
                              g, beta, chunk=64)
    assert got.dtype == jnp.bfloat16
    assert _rel(got.astype(jnp.float32), want) < 2e-2


@pytest.mark.parametrize("t,chunk", [(100, 64), (300, 128), (40, 8)])
def test_kernels_give_the_jax_forms_gradients_from_a_state_to_a_state(
        t, chunk, monkeypatch):
    """What no training step asks for but the function promises: a non-zero
    ``initial_state`` in, the final state out, cotangents on both outputs,
    the gradient of the initial state — with T no multiple of the chunk
    and two value heads to a key head."""
    args = _rule_inputs(t, h=4, hk=2, seed=3)
    s0 = jax.random.normal(jax.random.PRNGKey(5), (2, 4, 16, 24))
    w_o = jax.random.normal(jax.random.PRNGKey(6), args[2].shape)
    w_s = jax.random.normal(jax.random.PRNGKey(7), s0.shape)

    def loss(q, k, v, g, beta, s0):
        o, s = gated_delta_rule(q, k, v, g, beta, chunk=chunk,
                                initial_state=s0)
        return jnp.sum(o * w_o) + jnp.sum(s * w_s), (o, s)

    grads = jax.grad(loss, argnums=range(6), has_aux=True)
    want, (o_want, s_want) = grads(*args, s0)
    monkeypatch.setattr(gdr, "INTERPRET", True)
    got, (o, s) = grads(*args, s0)
    assert _rel(o, o_want) < 2e-5 and _rel(s, s_want) < 2e-5
    for a, b in zip(got, want):
        assert a.shape == b.shape and _rel(a, b) < 2e-4


@pytest.mark.parametrize("n,chunk,block", [
    (128, 64, 16), (128, 64, 32), (64, 64, 64), (128, 16, 16), (24, 24, 16)])
def test_blocked_inverse_is_the_float32_inverse(n, chunk, block):
    """``(I + A)^-1`` of every chunk of a tile, from diagonal blocks of
    ``block`` merged pair by pair: the inverse numpy computes in float64,
    to float32's rounding (the kernels' ``HIGHEST`` products)."""
    rng = np.random.default_rng(n + block)
    a = np.zeros((n, n), np.float32)
    for c in range(0, n, chunk):
        a[c:c + chunk, c:c + chunk] = np.tril(
            rng.normal(size=(chunk, chunk)) * 0.3, -1)
    packed = gdr._unit_lower_inverse(jnp.asarray(a), chunk, block)
    assert packed.shape == (chunk, n)
    got = np.asarray(gdr._spread(packed))
    want = np.linalg.inv(np.eye(n) + a.astype(np.float64))
    assert np.abs(got - want).max() < 2e-6 * np.abs(want).max()
