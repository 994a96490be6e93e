"""Weight initializers (reference: BigDL InitializationMethod zoo exposed
through the Keras layers' ``init=`` argument)."""

from __future__ import annotations

from typing import Callable, Union

import jax
import jax.numpy as jnp
import numpy as np

INITIALIZERS = {
    "glorot_uniform": jax.nn.initializers.glorot_uniform(),
    "glorot_normal": jax.nn.initializers.glorot_normal(),
    "he_uniform": jax.nn.initializers.he_uniform(),
    "he_normal": jax.nn.initializers.he_normal(),
    "lecun_uniform": jax.nn.initializers.lecun_uniform(),
    "lecun_normal": jax.nn.initializers.lecun_normal(),
    "zeros": jax.nn.initializers.zeros,
    "ones": jax.nn.initializers.ones,
    "uniform": jax.nn.initializers.uniform(0.05),
    "normal": jax.nn.initializers.normal(0.05),
    "orthogonal": jax.nn.initializers.orthogonal(),
}


def get(init: Union[str, Callable]) -> Callable:
    if callable(init):
        return init
    try:
        return INITIALIZERS[init]
    except KeyError:
        raise ValueError(f"unknown initializer {init!r}; known: "
                         f"{sorted(INITIALIZERS)}") from None


# -- recurrent mixers' decay parameters (nn/linear_attention.py,
# nn/state_space.py): a head forgets ``exp(A_log) * softplus(. + dt_bias)``
# a position

def dt_bias_init(low: float = 1e-3, high: float = 0.1) -> Callable:
    """``softplus^-1(dt)`` with ``dt`` log-uniform in [low, high]: a head
    forgets ``exp(A_log) * dt`` a position, from almost nothing to a few
    tenths (the initialiser of the Gated DeltaNet and Mamba-2 reference
    codes)."""
    def init(rng, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(rng, shape, jnp.float32,
                                        np.log(low), np.log(high)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return init


def a_log_init(high: float = 16.0) -> Callable:
    """``log(A)`` with ``A`` uniform in (1e-4, high)."""
    def init(rng, shape, dtype=jnp.float32):
        return jnp.log(jax.random.uniform(rng, shape, jnp.float32, 1e-4,
                                          high)).astype(dtype)
    return init
