"""Loss functions (reference: Keras-zoo objectives,
zoo/.../pipeline/api/keras/objectives/ — SparseCategoricalCrossEntropy,
CategoricalCrossEntropy, BinaryCrossEntropy, MSE/MAE, Hinge, …).

Every loss is ``fn(y_pred, y_true) -> scalar`` (mean over the batch), pure
and jit-safe.  ``get`` resolves Keras-style string names.
"""

from __future__ import annotations

from typing import Callable, Union

import jax
import jax.numpy as jnp


def sparse_categorical_crossentropy(y_pred: jax.Array, y_true: jax.Array,
                                    from_logits: bool = True) -> jax.Array:
    y_true = y_true.astype(jnp.int32)
    if from_logits:
        # mixed-precision recipe: matmuls in bf16, softmax math in f32.
        # logsumexp - gather instead of log_softmax + gather: identical
        # math, but never materializes the full [.., vocab] f32 log-prob
        # array — one HBM round trip saved on large-vocab LM heads
        # (measured ~+1% MFU on the BERT-base bench).
        logits = y_pred.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, y_true[..., None], axis=-1)[..., 0]
        return (lse - tgt).mean()
    logp = jnp.log(jnp.clip(y_pred, 1e-7, 1.0))
    nll = -jnp.take_along_axis(logp, y_true[..., None], axis=-1)[..., 0]
    return nll.mean()


def multi_token_crossentropy(y_pred: jax.Array, y_true: jax.Array,
                             depth_weight: float = 0.3) -> jax.Array:
    """Cross-entropy of a model that predicts several tokens ahead
    (``models.GlmMoeLite``): ``y_pred`` ``[B, K, T, V]`` holds one row of
    logits a prediction depth, ``y_true`` ``[B, T]`` the next-token ids.
    Depth k's logits at position i are scored against ``y_true[i + k]``; the
    k positions whose target lies past the row are left out of that depth's
    mean.  Depth 0 counts once, every deeper one ``depth_weight`` times
    (DeepSeek-V3's lambda, arXiv:2412.19437 section 2.2).  The same
    logsumexp-minus-target form as ``sparse_categorical_crossentropy``, in
    float32."""
    y_true = y_true.astype(jnp.int32)
    depths, t = y_pred.shape[1], y_pred.shape[2]
    logits = y_pred.astype(jnp.float32)
    # depth k's targets, the k past the row's end pointed at id 0 and masked
    target = jnp.stack([jnp.pad(y_true[:, k:], ((0, 0), (0, k)))
                        for k in range(depths)], axis=1)         # [B, K, T]
    scored = jnp.arange(t) < t - jnp.arange(depths)[:, None]     # [K, T]
    nll = jax.scipy.special.logsumexp(logits, axis=-1) \
        - jnp.take_along_axis(logits, target[..., None], axis=-1)[..., 0]
    per_depth = jnp.where(scored, nll, 0.0).sum(axis=(0, 2)) \
        / (y_true.shape[0] * scored.sum(axis=-1))
    weight = jnp.where(jnp.arange(depths) == 0, 1.0, depth_weight)
    return (per_depth * weight).sum()


def categorical_crossentropy(y_pred: jax.Array, y_true: jax.Array,
                             from_logits: bool = True) -> jax.Array:
    if from_logits:
        logp = jax.nn.log_softmax(y_pred.astype(jnp.float32), axis=-1)
    else:
        logp = jnp.log(jnp.clip(y_pred, 1e-7, 1.0))
    return -(y_true * logp).sum(axis=-1).mean()


def binary_crossentropy(y_pred: jax.Array, y_true: jax.Array,
                        from_logits: bool = True) -> jax.Array:
    y_true = y_true.astype(y_pred.dtype)
    if from_logits:
        # numerically stable log-sigmoid form
        return jnp.mean(jnp.clip(y_pred, 0) - y_pred * y_true +
                        jnp.log1p(jnp.exp(-jnp.abs(y_pred))))
    p = jnp.clip(y_pred, 1e-7, 1 - 1e-7)
    return -(y_true * jnp.log(p) + (1 - y_true) * jnp.log(1 - p)).mean()


def mean_squared_error(y_pred: jax.Array, y_true: jax.Array) -> jax.Array:
    return jnp.square(y_pred - y_true).mean()


def mean_absolute_error(y_pred: jax.Array, y_true: jax.Array) -> jax.Array:
    return jnp.abs(y_pred - y_true).mean()


def huber(y_pred: jax.Array, y_true: jax.Array, delta: float = 1.0
          ) -> jax.Array:
    err = jnp.abs(y_pred - y_true)
    quad = jnp.minimum(err, delta)
    return (0.5 * quad**2 + delta * (err - quad)).mean()


def hinge(y_pred: jax.Array, y_true: jax.Array) -> jax.Array:
    return jnp.maximum(0.0, 1.0 - y_true * y_pred).mean()


def squared_hinge(y_pred: jax.Array, y_true: jax.Array) -> jax.Array:
    return jnp.square(jnp.maximum(0.0, 1.0 - y_true * y_pred)).mean()


def mean_absolute_percentage_error(y_pred: jax.Array, y_true: jax.Array
                                   ) -> jax.Array:
    diff = jnp.abs((y_true - y_pred) /
                   jnp.clip(jnp.abs(y_true), 1e-7, None))
    return 100.0 * diff.mean()


def mean_squared_logarithmic_error(y_pred: jax.Array, y_true: jax.Array
                                   ) -> jax.Array:
    a = jnp.log1p(jnp.clip(y_pred, 0.0, None))
    b = jnp.log1p(jnp.clip(y_true, 0.0, None))
    return jnp.square(a - b).mean()


def poisson(y_pred: jax.Array, y_true: jax.Array) -> jax.Array:
    return (y_pred - y_true * jnp.log(jnp.clip(y_pred, 1e-7, None))).mean()


def kld(y_pred: jax.Array, y_true: jax.Array) -> jax.Array:
    p = jnp.clip(y_true, 1e-7, 1.0)
    q = jnp.clip(y_pred, 1e-7, 1.0)
    return (p * jnp.log(p / q)).sum(axis=-1).mean()


def cosine_proximity(y_pred: jax.Array, y_true: jax.Array) -> jax.Array:
    yp = y_pred / (jnp.linalg.norm(y_pred, axis=-1, keepdims=True) + 1e-8)
    yt = y_true / (jnp.linalg.norm(y_true, axis=-1, keepdims=True) + 1e-8)
    return -(yp * yt).sum(axis=-1).mean()


LOSSES = {
    "sparse_categorical_crossentropy": sparse_categorical_crossentropy,
    "multi_token_crossentropy": multi_token_crossentropy,
    "categorical_crossentropy": categorical_crossentropy,
    "binary_crossentropy": binary_crossentropy,
    "mse": mean_squared_error,
    "mean_squared_error": mean_squared_error,
    "mae": mean_absolute_error,
    "mean_absolute_error": mean_absolute_error,
    "huber": huber,
    "hinge": hinge,
    "squared_hinge": squared_hinge,
    "mape": mean_absolute_percentage_error,
    "mean_absolute_percentage_error": mean_absolute_percentage_error,
    "msle": mean_squared_logarithmic_error,
    "mean_squared_logarithmic_error": mean_squared_logarithmic_error,
    "poisson": poisson,
    "kld": kld,
    "cosine_proximity": cosine_proximity,
}


def get(loss: Union[str, Callable]) -> Callable:
    if callable(loss):
        return loss
    try:
        return LOSSES[loss]
    except KeyError:
        raise ValueError(
            f"unknown loss {loss!r}; known: {sorted(LOSSES)}") from None
