"""Core + convolution + normalization layers (Keras-style, TPU-native).

Reference (SURVEY.md §2.3): the Keras-1.2 layer zoo in
zoo/src/main/scala/com/intel/analytics/zoo/pipeline/api/keras/layers/ with
py4j mirrors in pyzoo/zoo/pipeline/api/keras/layers/.  Scoped here to the
subset used by zoo.models + the BASELINE configs (SURVEY.md §7 "Keras-1.2 API
breadth"), with TPU-idiomatic choices:

- NHWC image layout (TPU conv layout; the reference used NCHW for MKL-DNN),
- optional bfloat16 compute dtype on matmul/conv (MXU native) with float32
  params and accumulation,
- everything jit/vmap/shard_map-composable (pure functions of variables).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import activations, initializers
from .module import Module, Scope


def _pair(v: Union[int, Sequence[int]]) -> Tuple[int, int]:
    return (v, v) if isinstance(v, int) else tuple(v)  # type: ignore


def _cast_for_compute(x: jax.Array, dtype: Optional[Any]) -> jax.Array:
    return x.astype(dtype) if dtype is not None else x


def _norm_padding(p: Any) -> Any:
    """'same'/'valid' → upper string; int / (h, w) / ((lo,hi),(lo,hi)) →
    explicit per-dimension pad pairs (torch-style numeric padding)."""
    if isinstance(p, str):
        return p.upper()
    if isinstance(p, int):
        return ((p, p), (p, p))
    p = tuple(p)
    if all(isinstance(e, int) for e in p):
        return tuple((e, e) for e in p)
    return tuple((int(a), int(b)) for a, b in p)


class Dense(Module):
    """Fully connected layer (reference: keras/layers Dense)."""

    def __init__(self, units: int, activation: Any = None, use_bias: bool = True,
                 kernel_init: Any = "glorot_uniform", bias_init: Any = "zeros",
                 dtype: Optional[Any] = None, name: Optional[str] = None):
        super().__init__(name)
        self.units = units
        self.activation = activations.get(activation)
        self.use_bias = use_bias
        self.kernel_init = initializers.get(kernel_init)
        self.bias_init = initializers.get(bias_init)
        self.dtype = dtype

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        w = scope.param("kernel", self.kernel_init, (x.shape[-1], self.units))
        q = scope.quant
        if q is not None and q.mode == "collect":
            q.observe(scope.path, x)
        y = None
        if isinstance(w, dict):  # int8 serving: {marker, q, scale} kernel
            from . import quant as _quant
            if q is not None and q.mode == "apply":
                y = _quant.dense_quantized(q, scope.path, x, w["q"],
                                           w["scale"], q.compute_dtype)
                if y is not None:
                    y = y.astype(x.dtype)
            if y is None:  # weight-only: dequant fuses into the matmul
                w = (w["q"].astype(x.dtype)
                     * w["scale"].astype(x.dtype))
        if y is None:
            xc = _cast_for_compute(x, self.dtype)
            # No preferred_element_type=f32: the MXU accumulates bf16
            # matmuls in f32 internally, and an f32-typed output whose
            # only consumer downcasts would poison the WHOLE backward —
            # the f32 cotangent turns both vjp matmuls into mixed
            # f32 x bf16 dots (measured: the dominant BERT bwd cost).
            y = jnp.dot(xc, _cast_for_compute(w, self.dtype).astype(xc.dtype))
            y = y.astype(x.dtype) if x.dtype != y.dtype else y
        if self.use_bias:
            b = scope.param("bias", self.bias_init, (self.units,))
            y = y + b.astype(y.dtype)  # don't promote bf16 back to f32
        return self.activation(y)


class Embedding(Module):
    """Token embedding (reference: keras/layers Embedding)."""

    def __init__(self, input_dim: int, output_dim: int,
                 embeddings_init: Any = "normal", name: Optional[str] = None):
        super().__init__(name)
        self.input_dim = input_dim
        self.output_dim = output_dim
        self.embeddings_init = initializers.get(embeddings_init)

    def forward(self, scope: Scope, ids: jax.Array) -> jax.Array:
        table = scope.param("embeddings", self.embeddings_init,
                            (self.input_dim, self.output_dim))
        return jnp.take(table, ids, axis=0)


class Dropout(Module):
    def __init__(self, rate: float, name: Optional[str] = None):
        super().__init__(name)
        self.rate = float(rate)

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        if not scope.training or self.rate <= 0.0:
            return x
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(scope.make_rng(), keep, x.shape)
        return jnp.where(mask, x / keep, 0.0)


class Flatten(Module):
    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        return x.reshape(x.shape[0], -1)


class Reshape(Module):
    def __init__(self, target_shape: Sequence[int], name: Optional[str] = None):
        super().__init__(name)
        self.target_shape = tuple(target_shape)

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        return x.reshape((x.shape[0],) + self.target_shape)


class Activation(Module):
    def __init__(self, activation: Any, name: Optional[str] = None):
        super().__init__(name)
        self.fn = activations.get(activation)

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        return self.fn(x)


class Lambda(Module):
    """Wrap an arbitrary pure function as a layer (reference: autograd Lambda,
    pyzoo/zoo/pipeline/api/autograd.py)."""

    def __init__(self, fn: Callable, name: Optional[str] = None):
        super().__init__(name)
        self.fn = fn

    def forward(self, scope: Scope, *args: Any) -> Any:
        return self.fn(*args)


# -- convolution / pooling (NHWC) ---------------------------------------------

class Conv2D(Module):
    """2-D convolution, NHWC/HWIO (reference: keras/layers Convolution2D —
    which was NCHW for MKL-DNN; NHWC is the TPU-native layout)."""

    def __init__(self, filters: int, kernel_size: Union[int, Sequence[int]],
                 strides: Union[int, Sequence[int]] = 1,
                 padding: Any = "same", activation: Any = None,
                 use_bias: bool = True, kernel_init: Any = "he_normal",
                 dilation: Union[int, Sequence[int]] = 1,
                 groups: int = 1, dtype: Optional[Any] = None,
                 name: Optional[str] = None):
        super().__init__(name)
        self.filters = filters
        self.kernel_size = _pair(kernel_size)
        self.strides = _pair(strides)
        # "same"/"valid", or torch-style numeric padding (int / pair /
        # explicit (lo, hi) pairs) for exact foreign-model parity
        self.padding = _norm_padding(padding)
        self.activation = activations.get(activation)
        self.use_bias = use_bias
        self.kernel_init = initializers.get(kernel_init)
        self.dilation = _pair(dilation)
        self.groups = groups
        self.dtype = dtype

    # plain Conv2D participates in calibrated int8 activation
    # quantization (serving); kernel-transforming subclasses
    # (ScaledWSConv2D) opt out — their weight math needs the float kernel
    _act_quant = True

    def _kernel(self, scope: Scope, shape: Tuple[int, ...]) -> jax.Array:
        """Weight fetch hook — subclasses may transform (e.g. weight
        standardization) before the conv consumes it."""
        return scope.param("kernel", self.kernel_init, shape)

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        kh, kw = self.kernel_size
        in_ch = x.shape[-1]
        w = self._kernel(scope, (kh, kw, in_ch // self.groups,
                                 self.filters))
        q = scope.quant
        if q is not None and q.mode == "collect" and self._act_quant:
            q.observe(scope.path, x)
        y = None
        if isinstance(w, dict):  # int8 serving: {marker, q, scale} kernel
            from . import quant as _quant
            if q is not None and q.mode == "apply":
                y = _quant.conv_quantized(
                    q, scope.path, x, w["q"], w["scale"], self.strides,
                    self.padding, self.dilation, self.groups,
                    q.compute_dtype)
                if y is not None:
                    y = y.astype(x.dtype)
            if y is None:
                # weight-only fallback: dequant fuses into the conv
                w = w["q"].astype(x.dtype) * w["scale"].astype(x.dtype)
        if y is None:
            y = self._float_conv(x, w)
        if self.use_bias:
            b = scope.param("bias", initializers.get("zeros"),
                            (self.filters,))
            y = y + b.astype(y.dtype)
        return self.activation(y)

    def _float_conv(self, x: jax.Array, w: jax.Array) -> jax.Array:
        kh, kw = self.kernel_size
        in_ch = x.shape[-1]
        xc = _cast_for_compute(x, self.dtype)
        wc = _cast_for_compute(w, self.dtype).astype(xc.dtype)
        pad_free = (self.padding in ("SAME", "VALID")
                    or all(p == (0, 0) for p in self.padding))
        if (kh == kw == 1 and self.strides == (1, 1) and pad_free
                and self.dilation == (1, 1) and self.groups == 1):
            # 1x1/s1 conv as an explicit matmul over flattened positions.
            # Same math, but the vjp becomes two dot_generals — profiled:
            # XLA lowered these convs' WEIGHT gradients to VPU
            # multiply-reduce fusions (~0.5 ms each across ResNet's ~30
            # 1x1 convs) instead of MXU matmuls (~0.03 ms).
            y = jnp.dot(xc.reshape(-1, in_ch), wc.reshape(in_ch,
                                                          self.filters))
            y = y.reshape(x.shape[:-1] + (self.filters,))
        else:
            # No preferred_element_type: the conv vjp in this JAX version
            # rejects mixed (bf16 cotangent, f32-preferred) operands, and
            # the TPU MXU accumulates bf16 convs in f32 natively anyway.
            y = jax.lax.conv_general_dilated(
                xc, wc,
                window_strides=self.strides, padding=self.padding,
                rhs_dilation=self.dilation,
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=self.groups)
        return y.astype(x.dtype) if x.dtype != y.dtype else y


def scaled_ws_kernel(w: jax.Array, gain: jax.Array) -> jax.Array:
    """Scaled Weight Standardization of a HWIO conv kernel:
    ``gain_o * (W - mean_o) / (std_o * sqrt(fan_in))`` with per-output-
    channel statistics over the fan-in dims.  Shared by ScaledWSConv2D
    and the space-to-depth stem so the formula cannot drift."""
    fan_in = w.shape[0] * w.shape[1] * w.shape[2]
    mean = jnp.mean(w, axis=(0, 1, 2), keepdims=True)
    var = jnp.var(w, axis=(0, 1, 2), keepdims=True)
    scale = jax.lax.rsqrt(jnp.maximum(var * fan_in, 1e-4))
    return (w - mean) * (scale * gain)


class ScaledWSConv2D(Conv2D):
    """Conv2D with Scaled Weight Standardization (public technique:
    Brock et al., "Characterizing signal propagation ...", 2021 — the
    NF-ResNet building block): the kernel used in the conv is
    ``g_o * (W - mean_o) / (std_o * sqrt(fan_in))`` with per-output-
    channel statistics over the fan-in and a learnable per-channel gain.

    TPU rationale: batch norm's activation statistics cost full
    feature-map reductions every step (bandwidth-bound); weight
    statistics touch only the ~KB-scale kernels, so normalization moves
    off the hot path entirely.  Gradients flow through the
    standardization (that is what controls signal propagation).

    ``skip_init=True`` additionally folds a zero-initialised learnable
    scalar (SkipInit, times ``branch_scale``) into the kernel.  Because
    a conv is linear in its weights, ``s * conv(x, W) == conv(x, s*W)``
    — same math, but the SkipInit gradient ``dL/ds`` is computed by the
    adjoint in WEIGHT space (a kernel-sized contraction that rides the
    dW conv already being computed) instead of a full feature-map
    scalar reduction.  Measured on NF-RN50/B128: the explicit
    ``shortcut + s*h`` form cost ~1.3 ms/step of map->scalar VPU
    reduces per big block; the folded form removes them entirely.
    """

    _act_quant = False  # weight standardization needs the float kernel

    def __init__(self, *args, skip_init: bool = False,
                 branch_scale: float = 1.0, **kwargs):
        super().__init__(*args, **kwargs)
        self.skip_init = skip_init
        self.branch_scale = branch_scale

    def _kernel(self, scope: Scope, shape: Tuple[int, ...]) -> jax.Array:
        w = scope.param("kernel", self.kernel_init, shape)
        gain = scope.param("ws_gain", initializers.get("ones"),
                           (shape[-1],))
        if self.skip_init:
            s = scope.param("skip_gain", initializers.get("zeros"), ())
            gain = gain * (s * self.branch_scale)
        return scaled_ws_kernel(w, gain)


class Conv1D(Module):
    def __init__(self, filters: int, kernel_size: int, strides: int = 1,
                 padding: str = "same", activation: Any = None,
                 use_bias: bool = True, kernel_init: Any = "he_normal",
                 dilation: int = 1, name: Optional[str] = None):
        super().__init__(name)
        self.conv = Conv2D(filters, (1, kernel_size), (1, strides), padding,
                           activation, use_bias, kernel_init, (1, dilation),
                           name="conv2d")

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        y = scope.child(self.conv, x[:, None, :, :], name="conv")
        return y[:, 0]


def _pool(x: jax.Array, kind: str, window: Tuple[int, int],
          strides: Tuple[int, int], padding: Any) -> jax.Array:
    dims = (1, window[0], window[1], 1)
    strd = (1, strides[0], strides[1], 1)
    explicit = not isinstance(padding, str)
    if explicit:  # per-spatial-dim (lo, hi) pairs -> full 4-dim spec
        padding = ((0, 0),) + tuple(padding) + ((0, 0),)
    if kind == "max":
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, dims, strd,
                                     padding)
    s = jax.lax.reduce_window(x, 0.0, jax.lax.add, dims, strd, padding)
    if padding == "VALID" or explicit:
        # explicit numeric padding follows torch AvgPool2d semantics
        # (count_include_pad=True): pads are zeros AND count in the divisor
        return s / (window[0] * window[1])
    ones = jnp.ones(x.shape[:1] + x.shape[1:3] + (1,), x.dtype)
    cnt = jax.lax.reduce_window(ones, 0.0, jax.lax.add, dims, strd, padding)
    return s / cnt


class MaxPooling2D(Module):
    def __init__(self, pool_size: Union[int, Sequence[int]] = 2,
                 strides: Optional[Union[int, Sequence[int]]] = None,
                 padding: Any = "valid", name: Optional[str] = None):
        super().__init__(name)
        self.pool_size = _pair(pool_size)
        self.strides = _pair(strides) if strides is not None else self.pool_size
        self.padding = _norm_padding(padding)

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        return _pool(x, "max", self.pool_size, self.strides, self.padding)


class AveragePooling2D(Module):
    def __init__(self, pool_size: Union[int, Sequence[int]] = 2,
                 strides: Optional[Union[int, Sequence[int]]] = None,
                 padding: Any = "valid", name: Optional[str] = None):
        super().__init__(name)
        self.pool_size = _pair(pool_size)
        self.strides = _pair(strides) if strides is not None else self.pool_size
        self.padding = _norm_padding(padding)

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        return _pool(x, "avg", self.pool_size, self.strides, self.padding)


class GlobalAveragePooling2D(Module):
    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        return x.mean(axis=(1, 2))


class GlobalMaxPooling2D(Module):
    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        return x.max(axis=(1, 2))


class GlobalAveragePooling1D(Module):
    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        return x.mean(axis=1)


class GlobalMaxPooling1D(Module):
    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        return x.max(axis=1)


class ZeroPadding2D(Module):
    def __init__(self, padding: Union[int, Sequence[int]] = 1,
                 name: Optional[str] = None):
        super().__init__(name)
        self.padding = _pair(padding)

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        ph, pw = self.padding
        return jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))


# -- normalization -------------------------------------------------------------

class BatchNormalization(Module):
    """Batch norm with running statistics carried in the state collection
    (reference: keras/layers BatchNormalization; BigDL mutated them in-place,
    here apply() returns the updated state)."""

    def __init__(self, momentum: float = 0.99, epsilon: float = 1e-3,
                 center: bool = True, scale: bool = True,
                 axis: int = -1, name: Optional[str] = None):
        super().__init__(name)
        self.momentum = momentum
        self.epsilon = epsilon
        self.center = center
        self.scale = scale
        self.axis = axis

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        dim = x.shape[self.axis]
        reduce_axes = tuple(i for i in range(x.ndim)
                            if i != (self.axis % x.ndim))
        mean_run = scope.variable("mean", lambda: jnp.zeros((dim,)))
        var_run = scope.variable("var", lambda: jnp.ones((dim,)))
        if scope.training and (self.axis % x.ndim) == x.ndim - 1:
            # Channel-last training: the fused custom-VJP path
            # (ops/fused_bn.py) — identical statistics and normalize
            # math, but a hand-written backward that keeps every
            # feature-map read/write in the activation dtype.  Autodiff
            # of the inline formulation below makes XLA materialize f32
            # copies of every BN input map (measured ~40% of an RN50
            # step in reduce+conv-fusion overhead).
            from ..ops import fused_bn
            gamma = (scope.param("gamma", initializers.get("ones"),
                                 (dim,))
                     if self.scale else jnp.ones((dim,), jnp.float32))
            beta = (scope.param("beta", initializers.get("zeros"),
                                (dim,))
                    if self.center else jnp.zeros((dim,), jnp.float32))
            y, mean, var = fused_bn.bn_train(x, gamma, beta,
                                             self.epsilon)
            m = self.momentum
            scope.put_variable("mean", m * mean_run + (1 - m) * mean)
            scope.put_variable("var", m * var_run + (1 - m) * var)
            return y
        if scope.training:
            # statistics in f32 (bf16 accumulation over B*H*W loses too
            # much), state stays f32.  E[xc^2] - E[xc]^2 instead of the
            # two-pass var: both reductions share one fused read of the
            # activation (multi-output fusion) — BN is bandwidth-bound, so
            # a second full pass over every feature map is measurable.
            # xc is shifted by one stop-gradded SAMPLE per channel:
            # moments are shift-invariant (so values and gradients are
            # analytically unchanged), but the shift keeps the
            # mean-of-squares subtraction from cancelling catastrophically
            # for badly centered channels (|mean| >> std), where the raw
            # E[x^2]-E[x]^2 in f32 collapses to garbage.
            xf = x.astype(jnp.float32)
            idx = tuple(0 if i in reduce_axes else slice(None)
                        for i in range(x.ndim))
            shift = jax.lax.stop_gradient(xf[idx]).reshape(
                [1 if i in reduce_axes else x.shape[i]
                 for i in range(x.ndim)])
            xc = xf - shift
            mean_c = xc.mean(axis=reduce_axes)
            var = jnp.maximum(
                jnp.mean(jnp.square(xc), axis=reduce_axes)
                - jnp.square(mean_c), 0.0)
            mean = mean_c + shift.reshape(-1)
            m = self.momentum
            scope.put_variable("mean", m * mean_run + (1 - m) * mean)
            scope.put_variable("var", m * var_run + (1 - m) * var)
        else:
            mean, var = mean_run, var_run
        shape = [1] * x.ndim
        shape[self.axis] = dim
        # Mean-centered form with a rounding-compensated shift, all
        # per-ELEMENT math in the activation dtype.  (x - mean) of nearby
        # bf16 values is cancellation-safe (Sterbenz), and keeping the
        # elementwise chain bf16 keeps every BN fwd/bwd kernel at bf16
        # HBM bytes — an f32 upcast here measures ~6% of a whole RN50
        # train step.  The one hazard of a bf16 mean — rounding it
        # injects a per-channel bias of up to (|mean|/std)*2^-9 sigma —
        # is cancelled exactly by folding the f32 rounding residual
        # (mean_rounded - mean) * inv into the per-CHANNEL shift, which
        # costs C scalar flops.  Statistics stay f32 throughout.
        inv = jax.lax.rsqrt(var + self.epsilon)
        if self.scale:
            inv = inv * scope.param("gamma", initializers.get("ones"),
                                    (dim,))
        mean_c = mean.astype(x.dtype)
        shift = (mean_c.astype(jnp.float32) - mean) * inv
        if self.center:
            shift = shift + scope.param("beta", initializers.get("zeros"),
                                        (dim,))
        inv_c = inv.astype(x.dtype).reshape(shape)
        y = (x - mean_c.reshape(shape)) * inv_c
        return y + shift.astype(x.dtype).reshape(shape)


class LayerNormalization(Module):
    def __init__(self, epsilon: float = 1e-6, name: Optional[str] = None):
        super().__init__(name)
        self.epsilon = epsilon

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        dim = x.shape[-1]
        xf = x.astype(jnp.float32)  # stats in f32 even for bf16 activations
        mean = xf.mean(axis=-1, keepdims=True)
        var = jnp.square(xf - mean).mean(axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + self.epsilon)
        g = scope.param("gamma", initializers.get("ones"), (dim,))
        b = scope.param("beta", initializers.get("zeros"), (dim,))
        return (y * g + b).astype(x.dtype)  # keep the compute dtype


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms_norm(x: jax.Array, scale: jax.Array, epsilon: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.square(xf).mean(axis=-1, keepdims=True)
                           + epsilon)
    return (y * scale).astype(x.dtype)


def _rms_norm_fwd(x, scale, epsilon):
    return _rms_norm(x, scale, epsilon), (x, scale)


def _rms_norm_bwd(epsilon, res, g):
    """The input's gradient is row by row; the scale's is a sum over every
    row.  Left alone the two land in one fusion, whose column sum a TPU
    does slowly (8 ms for a [16384, 2048] input, forty times the traffic's
    worth); as a product with a row of ones the sum goes to the MXU.  The
    ones are computed at run time: XLA turns a product with a CONSTANT row
    of ones back into the reduction."""
    x, scale = res
    xf, gf = x.astype(jnp.float32), g.astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.square(xf).mean(axis=-1, keepdims=True)
                         + epsilon)
    y = xf * rstd
    gy = gf * scale
    dx = rstd * (gy - y * (gy * y).mean(axis=-1, keepdims=True))
    ones = (1.0 + 0.0 * rstd).reshape(1, -1)
    dscale = jnp.dot(ones, (gf * y).reshape(-1, x.shape[-1]),
                     precision=jax.lax.Precision.HIGHEST)[0]
    return dx.astype(x.dtype), dscale.astype(scale.dtype)


_rms_norm.defvjp(_rms_norm_fwd, _rms_norm_bwd)


class RMSNorm(Module):
    """``x / sqrt(mean(x^2) + eps) * w`` over the last axis, statistics in
    float32.  ``zero_centered=True`` stores ``w - 1`` (initialised 0) and
    scales by ``1 + w``, the form today's decoder blocks publish: weight
    decay then pulls the scale towards 1, not 0."""

    def __init__(self, epsilon: float = 1e-6, zero_centered: bool = False,
                 name: Optional[str] = None):
        super().__init__(name)
        self.epsilon = epsilon
        self.zero_centered = zero_centered

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        if self.zero_centered:
            w = 1.0 + scope.param("weight", initializers.get("zeros"),
                                  (x.shape[-1],))
        else:
            w = scope.param("weight", initializers.get("ones"),
                            (x.shape[-1],))
        return _rms_norm(x, w, self.epsilon)


class SwiGLU(Module):
    """Gated feed-forward ``down(silu(gate(x)) * up(x))``, no biases.
    ``units`` is the inner width; the output has the input's width."""

    def __init__(self, units: int, kernel_init: Any = "glorot_uniform",
                 dtype: Optional[Any] = None, name: Optional[str] = None):
        super().__init__(name)
        self.units = units
        self.kernel_init = kernel_init
        self.dtype = dtype

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        def dense(units):
            return Dense(units, use_bias=False, kernel_init=self.kernel_init,
                         dtype=self.dtype)
        gate = scope.child(dense(self.units), x, name="gate")
        up = scope.child(dense(self.units), x, name="up")
        return scope.child(dense(x.shape[-1]), jax.nn.silu(gate) * up,
                           name="down")


class CausalConv1D(Module):
    """Depthwise causal convolution over time: ``[B, T, C] -> [B, T, C]``,
    ``y[t, c] = sum_j w[j, c] * x[t - (K-1) + j, c]`` with zeros before the
    sequence's start, plus a ``[C]`` bias under ``use_bias``, then the
    activation.  Written as K shifted multiply-adds, which XLA fuses into
    one pass over ``x`` (a grouped convolution with one channel a group has
    nothing for the MXU to do), summed in float32."""

    def __init__(self, kernel_size: int, activation: Any = None,
                 kernel_init: Any = "lecun_uniform", use_bias: bool = False,
                 name: Optional[str] = None):
        super().__init__(name)
        self.kernel_size = kernel_size
        self.use_bias = use_bias
        self.activation = activations.get(activation)
        self.kernel_init = initializers.get(kernel_init)

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        k, t = self.kernel_size, x.shape[1]
        w = scope.param("kernel", self.kernel_init, (k, x.shape[-1]))
        xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
        # float32 inside the fusion: costs no traffic, saves K roundings
        y = sum(xp[:, j:j + t].astype(jnp.float32) * w[j] for j in range(k))
        if self.use_bias:
            y = y + scope.param("bias", initializers.get("zeros"),
                                (x.shape[-1],))
        return self.activation(y).astype(x.dtype)


# -- merge layers (reference: keras merge.Concat/Add/Mul) ----------------------

class Concatenate(Module):
    def __init__(self, axis: int = -1, name: Optional[str] = None):
        super().__init__(name)
        self.axis = axis

    def forward(self, scope: Scope, xs: Sequence[jax.Array]) -> jax.Array:
        return jnp.concatenate(list(xs), axis=self.axis)


class Add(Module):
    def forward(self, scope: Scope, xs: Sequence[jax.Array]) -> jax.Array:
        out = xs[0]
        for x in xs[1:]:
            out = out + x
        return out


class Multiply(Module):
    def forward(self, scope: Scope, xs: Sequence[jax.Array]) -> jax.Array:
        out = xs[0]
        for x in xs[1:]:
            out = out * x
        return out


# -- containers ----------------------------------------------------------------

class Sequential(Module):
    """Linear stack of layers (reference: keras/models Sequential)."""

    def __init__(self, layers: Optional[Sequence[Module]] = None,
                 name: Optional[str] = None):
        super().__init__(name)
        self.layers = list(layers or [])

    def add(self, layer: Module) -> "Sequential":
        self.layers.append(layer)
        return self

    def forward(self, scope: Scope, x: Any, **kwargs: Any) -> Any:
        for i, layer in enumerate(self.layers):
            base = layer.name or f"layer{i}"
            x = scope.child(layer, x, name=f"{i:02d}_{base}"
                            if layer.name is None else layer.name)
        return x
