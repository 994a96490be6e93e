"""The expert layer's row kernels (``ops/moe_rows.py``) under ``INTERPRET``
on the CPU, at small shapes of whole 128-lane rows: ``held_experts`` through
the kernels against the same function through ``jax.numpy`` (what every
other backend runs), output and the gradients of the tokens, both expert
matrices and the pair weights, at a balanced load, with one expert taking
every held pair (several windows), with tokens of no and of every held pick,
and with a live count off the row block; each kernel's transpose against
the other kernel and against ``jax.numpy``; who moves which shapes, the
counter that says so, and no dropped pair."""

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from analytics_zoo_tpu.core import metrics  # noqa: E402
from analytics_zoo_tpu.parallel import DroplessMoE  # noqa: E402
from analytics_zoo_tpu.parallel import moe  # noqa: E402

rows = importlib.import_module("analytics_zoo_tpu.ops.moe_rows")

S, D, UNITS = 512, 128, 64


def _rel(a, b):
    return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-30))


def _picks(load_kind, k, held, e, rng):
    """``[S, K]`` experts (less the first held one; outside ``0 .. held``
    means absent) for a kind of load."""
    if load_kind == "balanced":
        return np.stack([rng.choice(e, k, replace=False) for _ in range(S)])
    absent = held + np.arange(k)            # distinct experts none holds
    picks = np.tile(absent, (S, 1))
    if load_kind == "one_expert_takes_all":  # expert 0 in every token
        picks[:, 0] = 0
    elif load_kind == "none_or_every_pick":  # 200 tokens hold all of theirs
        picks[:200, :min(k, held)] = np.arange(min(k, held))
    return picks


# (load, top_k, experts held, experts): windows of n = 2 * S * k * held / e
# rows; live rows against the gather's blocks of 256
LOADS = {
    "balanced": ("balanced", 4, 4, 16),              # one window, ~512 live
    "several_windows": ("one_expert_takes_all", 2, 2, 16),   # 512 in 256s
    # held under top-k: 200 tokens with their two held picks, 400 of 1,024
    "none_or_every_pick": ("none_or_every_pick", 4, 2, 8),
}


@pytest.mark.parametrize("case", sorted(LOADS))
def test_held_experts_through_the_kernels_match_jax_numpy(case,
                                                          monkeypatch):
    load_kind, k, held, e = LOADS[case]
    rng = np.random.default_rng(0)
    expert = jnp.asarray(_picks(load_kind, k, held, e, rng), jnp.int32)
    key = jax.random.split(jax.random.PRNGKey(1), 5)
    xs = jax.random.normal(key[0], (S, D))
    w_in = 0.1 * jax.random.normal(key[1], (held, D, 2 * UNITS))
    w_out = 0.1 * jax.random.normal(key[2], (held, UNITS, D))
    top_w = jax.random.uniform(key[3], (S, k), minval=0.1)
    cot = jax.random.normal(key[4], (S, D))
    load = jnp.sum(expert[..., None] == jnp.arange(held), (0, 1),
                   dtype=jnp.int32)

    def run(*args):
        out, placed = moe.held_experts(*args, expert, load, e)
        return jnp.sum(out * cot), (out, placed)
    step = jax.value_and_grad(run, argnums=(0, 1, 2, 3), has_aux=True)
    (_, (want, want_placed)), want_grads = jax.jit(step)(
        xs, w_in, w_out, top_w)
    monkeypatch.setattr(rows, "INTERPRET", True)
    (_, (got, placed)), grads = jax.jit(step)(xs, w_in, w_out, top_w)
    assert int(placed) == int(want_placed) == int(load.sum())
    assert _rel(got, want) < 1e-5
    for name, a, b in zip(("xs", "w_gate_up", "w_down", "pair_w"), grads,
                          want_grads):
        assert _rel(a, b) < 1e-5, name


def _route(n=512, live=400, kk=2, seed=0):
    """A window of ``n`` rows (``live`` held) over S tokens, each token's
    held picks first in ``pos``: a permutation of rows to (token, pick)."""
    rng = np.random.default_rng(seed)
    slots = rng.permutation(S * kk)[:live]          # (token, pick) of a row
    token, pick = slots // kk, slots % kk
    pos = -np.ones((S, kk), np.int64)
    order = np.lexsort((pick, token))               # a token's picks first
    counts = np.zeros(S, np.int64)
    for p in order:
        pos[token[p], counts[token[p]]] = p + 7     # places start at 7
        counts[token[p]] += 1
    idx = np.zeros(n, np.int32)
    idx[:live] = token
    return rows.Route(jnp.asarray(idx), jnp.asarray(pos.reshape(-1),
                                                    jnp.int32),
                      jnp.asarray([7, live], jnp.int32)), token


def test_each_kernel_takes_its_cotangent_back_as_the_other():
    """gather_rows' transpose is combine_rows and the other way round, the
    weights' gradient from the same gather pass; both against jax.numpy."""
    n, live = 512, 400
    route, token = _route(n, live)
    key = jax.random.split(jax.random.PRNGKey(2), 4)
    src = jax.random.normal(key[0], (S, D))
    rows_in = jax.random.normal(key[1], (n, D))
    scale = jax.random.uniform(key[2], (n,))
    cot = jax.random.normal(key[3], (n, D))
    live_rows = jnp.arange(n) < live
    tok = jnp.asarray(np.concatenate([token, np.zeros(n - live, np.int64)]))

    def gather_ref(a, w):
        return jnp.where(live_rows[:, None], a[tok] * w[:, None], 0)

    def combine_ref(r, w):
        return jnp.zeros((S, D)).at[tok].add(
            jnp.where(live_rows[:, None], r * w[:, None], 0))

    got, vjp = jax.vjp(
        lambda a, w: rows.gather_rows(a, w, route, S, True), src, scale)
    d_src, d_scale = vjp(cot)
    want, vjp_ref = jax.vjp(gather_ref, src, scale)
    want_src, want_scale = vjp_ref(cot)
    assert _rel(jnp.where(live_rows[:, None], got, 0), want) < 1e-6
    assert _rel(d_src, want_src) < 1e-6 and _rel(d_scale, want_scale) < 1e-6
    # the transpose of the gather IS the combine kernel
    assert _rel(d_src, rows.combine_call(cot, route, S, weight=scale,
                                         interpret=True)) == 0

    g = jax.random.normal(key[0], (S, D))
    got, vjp = jax.vjp(
        lambda r, w: rows.combine_rows(r, w, route, S, True), rows_in, scale)
    d_rows, d_w = vjp(g)
    want, vjp_ref = jax.vjp(combine_ref, rows_in, scale)
    want_rows, want_w = vjp_ref(g)
    assert _rel(got, want) < 1e-6 and _rel(d_w, want_w) < 1e-6
    assert _rel(jnp.where(live_rows[:, None], d_rows, 0), want_rows) < 1e-6
    again, dots = rows.gather_call(g, route, scale, dot=rows_in,
                                   interpret=True)
    # the transpose of the combine IS the gather kernel, and the weights'
    # gradient comes from that same pass
    assert bool(jnp.all(jnp.where(live_rows[:, None], again == d_rows,
                                  True)))
    assert _rel(jnp.where(live_rows, dots, 0), d_w) == 0


def test_the_combine_carries_per_row_values_to_the_picks():
    """``pick_vals``: the value of a pick's row at the pick's place in
    ``pos`` (what the backward maps the weights' gradient with), beside the
    carry folded into the sum."""
    route, token = _route()
    vals = jnp.arange(512, dtype=jnp.float32) + 1
    carry = jnp.ones((S, D))
    out, at = rows.combine_call(jnp.zeros((512, D)), route, S, carry=carry,
                                pick_vals=vals, interpret=True)
    pos = np.asarray(route.pos) - 7
    inside = (pos >= 0) & (pos < 400)
    assert np.array_equal(np.asarray(at)[inside], pos[inside] + 1.0)
    assert np.array_equal(np.asarray(out), np.ones((S, D)))


def test_dispatch_and_the_counter_of_the_path_a_layer_took(monkeypatch):
    """Whole 128-lane rows and whole blocks take the kernels on a TPU, and
    under ``INTERPRET`` here; other shapes (the tests' hidden 64) the
    jax.numpy form; every trace of a layer counts its path, and no held
    pair is dropped on either."""
    assert rows.dispatch(S, 512, D, 2) is None        # the CPU: jax.numpy
    assert rows.dispatch(S, 512, 64, 2) is None
    monkeypatch.setattr(rows, "INTERPRET", True)
    assert rows.dispatch(S, 512, D, 2) is True
    assert rows.dispatch(S, 512, 64, 2) is None       # half a lane tile
    assert rows.dispatch(S, 520, D, 2) is None        # off the row blocks
    assert rows.dispatch(S - 128, 512, D, 2) is None  # off the token blocks
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert rows.dispatch(16384, 20480, 2048, 10) is False   # the cells'
    assert rows.dispatch(16384, 32768, 2048, 8) is False
    assert rows.dispatch(8192, 8192, 2048, 4) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")

    registry = metrics.get_registry()
    count = lambda path: registry.counter("moe.row_path", path=path).value
    was = count("jnp"), count("kernel")
    for d, path in ((D, "kernel"), (64, "jnp")):
        layer = DroplessMoE(8, 2, 32, experts_held=4)
        x = jnp.abs(jax.random.normal(jax.random.PRNGKey(0), (1, S, d)))
        variables = layer.init(jax.random.PRNGKey(1), x)
        params, state = dict(variables["params"]), variables["state"]
        # every token to experts 1 and 3, both held: 1,024 of 1,024 pairs
        params["router"] = {"kernel": jnp.zeros((d, 8)).at[:, 1].set(1.0)
                            .at[:, 3].set(0.9)}
        _, state = jax.jit(layer.apply)({"params": params, "state": state},
                                        x)
        c = state["counters"]
        assert int(c["moe.pairs_local"]) == 2 * S
        assert int(c["moe.pairs_dropped"]) == 0
    # init and apply trace the layer once each, on either side
    assert (count("jnp"), count("kernel")) == (was[0] + 2, was[1] + 2)
