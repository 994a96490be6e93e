"""Extended Keras-1.2 layer zoo (reference parity breadth).

Reference (SURVEY.md §2.3): zoo/.../pipeline/api/keras/layers/ carried the
full Keras-1.2 layer set (~120 classes) plus BigDL extras (Highway,
MaxoutDense, SReLU, ...).  layers.py holds the core set the model zoo
uses; this module widens coverage to the rest of the commonly-used API so
reference models port without rewrites.  All NHWC / NDHWC (TPU-native
layouts), pure functions of variables, jit/shard_map-composable.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from . import activations, initializers
from .layers import Conv2D, _pair
from .module import Module, Scope


def _triple(v: Union[int, Sequence[int]]) -> Tuple[int, int, int]:
    return (v, v, v) if isinstance(v, int) else tuple(v)  # type: ignore


def _norm_cropping(cropping: Union[int, Sequence[Any]], ndim: int
                   ) -> Tuple[Tuple[int, int], ...]:
    """int → symmetric per-dim; per-dim entries may be int or (lo, hi)."""
    if isinstance(cropping, int):
        return ((cropping, cropping),) * ndim
    return tuple((c, c) if isinstance(c, int) else tuple(c)
                 for c in cropping)


# -- convolution variants ------------------------------------------------------

class Conv3D(Module):
    """3-D convolution, NDHWC (reference: Convolution3D)."""

    def __init__(self, filters: int, kernel_size: Union[int, Sequence[int]],
                 strides: Union[int, Sequence[int]] = 1,
                 padding: str = "same", activation: Any = None,
                 use_bias: bool = True, kernel_init: Any = "he_normal",
                 name: Optional[str] = None):
        super().__init__(name)
        self.filters = filters
        self.kernel_size = _triple(kernel_size)
        self.strides = _triple(strides)
        self.padding = padding.upper()
        self.activation = activations.get(activation)
        self.use_bias = use_bias
        self.kernel_init = initializers.get(kernel_init)

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        kd, kh, kw = self.kernel_size
        w = scope.param("kernel", self.kernel_init,
                        (kd, kh, kw, x.shape[-1], self.filters))
        y = jax.lax.conv_general_dilated(
            x, w.astype(x.dtype), window_strides=self.strides,
            padding=self.padding,
            dimension_numbers=("NDHWC", "DHWIO", "NDHWC")).astype(x.dtype)
        if self.use_bias:
            b = scope.param("bias", initializers.get("zeros"),
                            (self.filters,))
            y = y + b.astype(y.dtype)
        return self.activation(y)


class Conv2DTranspose(Module):
    """Transposed conv (reference: Deconvolution2D), NHWC."""

    def __init__(self, filters: int, kernel_size: Union[int, Sequence[int]],
                 strides: Union[int, Sequence[int]] = 1,
                 padding: str = "same", activation: Any = None,
                 use_bias: bool = True, kernel_init: Any = "he_normal",
                 name: Optional[str] = None):
        super().__init__(name)
        self.filters = filters
        self.kernel_size = _pair(kernel_size)
        self.strides = _pair(strides)
        self.padding = padding.upper()
        self.activation = activations.get(activation)
        self.use_bias = use_bias
        self.kernel_init = initializers.get(kernel_init)

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        kh, kw = self.kernel_size
        w = scope.param("kernel", self.kernel_init,
                        (kh, kw, x.shape[-1], self.filters))
        # exact keras Conv2DTranspose semantics (gradient-of-conv); lax's
        # own conv_transpose distributes SAME padding differently
        from .layers_zoo import _deconv
        y = _deconv(x, w, self.strides, self.padding,
                    ("NHWC", "HWIO", "NHWC"))
        if self.use_bias:
            b = scope.param("bias", initializers.get("zeros"),
                            (self.filters,))
            y = y + b.astype(y.dtype)
        return self.activation(y)


class DepthwiseConv2D(Module):
    """Per-channel conv (reference: the depthwise half of
    SeparableConvolution2D); feature_group_count = in_channels maps straight
    onto the XLA grouped-conv path."""

    def __init__(self, kernel_size: Union[int, Sequence[int]],
                 strides: Union[int, Sequence[int]] = 1,
                 padding: str = "same", depth_multiplier: int = 1,
                 use_bias: bool = True, kernel_init: Any = "he_normal",
                 activation: Any = None, name: Optional[str] = None):
        super().__init__(name)
        self.kernel_size = _pair(kernel_size)
        self.strides = _pair(strides)
        self.padding = padding.upper()
        self.depth_multiplier = depth_multiplier
        self.use_bias = use_bias
        self.kernel_init = initializers.get(kernel_init)
        self.activation = activations.get(activation)

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        kh, kw = self.kernel_size
        ch = x.shape[-1]
        out_ch = ch * self.depth_multiplier
        w = scope.param("kernel", self.kernel_init, (kh, kw, 1, out_ch))
        y = jax.lax.conv_general_dilated(
            x, w.astype(x.dtype), window_strides=self.strides,
            padding=self.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=ch).astype(x.dtype)
        if self.use_bias:
            b = scope.param("bias", initializers.get("zeros"), (out_ch,))
            y = y + b.astype(y.dtype)
        return self.activation(y)


class SeparableConv2D(Module):
    """Depthwise + pointwise (reference: SeparableConvolution2D)."""

    def __init__(self, filters: int, kernel_size: Union[int, Sequence[int]],
                 strides: Union[int, Sequence[int]] = 1,
                 padding: str = "same", depth_multiplier: int = 1,
                 activation: Any = None, use_bias: bool = True,
                 name: Optional[str] = None):
        super().__init__(name)
        self.depthwise = DepthwiseConv2D(kernel_size, strides, padding,
                                         depth_multiplier, use_bias=False)
        self.pointwise = Conv2D(filters, 1, 1, "same", activation, use_bias)

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        h = scope.child(self.depthwise, x, name="depthwise")
        return scope.child(self.pointwise, h, name="pointwise")


class LocallyConnected1D(Module):
    """Unshared-weights 1-D conv (reference: LocallyConnected1D): one
    kernel per output position, expressed as a single batched einsum so
    the MXU sees one big contraction instead of a position loop."""

    def __init__(self, filters: int, kernel_size: int, strides: int = 1,
                 activation: Any = None, use_bias: bool = True,
                 kernel_init: Any = "glorot_uniform",
                 name: Optional[str] = None):
        super().__init__(name)
        self.filters = filters
        self.kernel_size = kernel_size
        self.strides = strides
        self.activation = activations.get(activation)
        self.use_bias = use_bias
        self.kernel_init = initializers.get(kernel_init)

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        b, t, c = x.shape
        out_t = (t - self.kernel_size) // self.strides + 1
        # windows [B, out_t, k*c] via gather of a static index grid
        starts = jnp.arange(out_t) * self.strides
        idx = starts[:, None] + jnp.arange(self.kernel_size)[None, :]
        win = x[:, idx]                           # [B, out_t, k, C]
        win = win.reshape(b, out_t, self.kernel_size * c)
        w = scope.param("kernel", self.kernel_init,
                        (out_t, self.kernel_size * c, self.filters))
        y = jnp.einsum("btk,tkf->btf", win, w.astype(win.dtype))
        if self.use_bias:
            bias = scope.param("bias", initializers.get("zeros"),
                               (out_t, self.filters))
            y = y + bias.astype(y.dtype)
        return self.activation(y)


# -- pooling variants ----------------------------------------------------------

class _Pool1D(Module):
    kind = "max"

    def __init__(self, pool_size: int = 2, strides: Optional[int] = None,
                 padding: str = "valid", name: Optional[str] = None):
        super().__init__(name)
        from .layers import MaxPooling2D, AveragePooling2D
        cls = MaxPooling2D if self.kind == "max" else AveragePooling2D
        self.pool = cls((1, pool_size),
                        (1, strides if strides is not None else pool_size),
                        padding)

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        return scope.child(self.pool, x[:, None], name="pool")[:, 0]


class MaxPooling1D(_Pool1D):
    kind = "max"


class AveragePooling1D(_Pool1D):
    kind = "avg"


class _Pool3D(Module):
    kind = "max"

    def __init__(self, pool_size: Union[int, Sequence[int]] = 2,
                 strides: Optional[Union[int, Sequence[int]]] = None,
                 padding: str = "valid", name: Optional[str] = None):
        super().__init__(name)
        self.pool_size = _triple(pool_size)
        self.strides = (_triple(strides) if strides is not None
                        else self.pool_size)
        self.padding = padding.upper()

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        dims = (1,) + self.pool_size + (1,)
        strd = (1,) + self.strides + (1,)
        if self.kind == "max":
            return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, dims,
                                         strd, self.padding)
        s = jax.lax.reduce_window(x, 0.0, jax.lax.add, dims, strd,
                                  self.padding)
        ones = jnp.ones_like(x[..., :1])
        cnt = jax.lax.reduce_window(ones, 0.0, jax.lax.add, dims, strd,
                                    self.padding)
        return s / cnt


class MaxPooling3D(_Pool3D):
    kind = "max"


class AveragePooling3D(_Pool3D):
    kind = "avg"


class GlobalAveragePooling3D(Module):
    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        return x.mean(axis=(1, 2, 3))


class GlobalMaxPooling3D(Module):
    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        return x.max(axis=(1, 2, 3))


# -- resizing / padding / cropping ---------------------------------------------

class UpSampling1D(Module):
    def __init__(self, size: int = 2, name: Optional[str] = None):
        super().__init__(name)
        self.size = size

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        return jnp.repeat(x, self.size, axis=1)


class UpSampling2D(Module):
    def __init__(self, size: Union[int, Sequence[int]] = 2,
                 name: Optional[str] = None):
        super().__init__(name)
        self.size = _pair(size)

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        y = jnp.repeat(x, self.size[0], axis=1)
        return jnp.repeat(y, self.size[1], axis=2)


class UpSampling3D(Module):
    def __init__(self, size: Union[int, Sequence[int]] = 2,
                 name: Optional[str] = None):
        super().__init__(name)
        self.size = _triple(size)

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        y = jnp.repeat(x, self.size[0], axis=1)
        y = jnp.repeat(y, self.size[1], axis=2)
        return jnp.repeat(y, self.size[2], axis=3)


class ZeroPadding1D(Module):
    def __init__(self, padding: Union[int, Sequence[int]] = 1,
                 name: Optional[str] = None):
        super().__init__(name)
        p = (padding, padding) if isinstance(padding, int) else tuple(padding)
        self.padding = p

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        return jnp.pad(x, ((0, 0), self.padding, (0, 0)))


class ZeroPadding3D(Module):
    def __init__(self, padding: Union[int, Sequence[int]] = 1,
                 name: Optional[str] = None):
        super().__init__(name)
        self.padding = _triple(padding)

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        pd, ph, pw = self.padding
        return jnp.pad(x, ((0, 0), (pd, pd), (ph, ph), (pw, pw), (0, 0)))


class Cropping1D(Module):
    def __init__(self, cropping: Union[int, Sequence[int]] = 1,
                 name: Optional[str] = None):
        super().__init__(name)
        c = ((cropping, cropping) if isinstance(cropping, int)
             else tuple(cropping))
        self.cropping = c

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        a, b = self.cropping
        return x[:, a:x.shape[1] - b]


class Cropping3D(Module):
    def __init__(self, cropping: Union[int, Sequence[Any]] = 1,
                 name: Optional[str] = None):
        super().__init__(name)
        self.cropping = _norm_cropping(cropping, 3)

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        (d0, d1), (h0, h1), (w0, w1) = self.cropping
        return x[:, d0:x.shape[1] - d1, h0:x.shape[2] - h1,
                 w0:x.shape[3] - w1]


class Cropping2D(Module):
    def __init__(self, cropping: Union[int, Sequence[Any]] = 1,
                 name: Optional[str] = None):
        super().__init__(name)
        self.cropping = _norm_cropping(cropping, 2)

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        (t, b), (l, r) = self.cropping
        return x[:, t:x.shape[1] - b, l:x.shape[2] - r]


# -- shape / sequence utilities ------------------------------------------------

class RepeatVector(Module):
    """[B, D] → [B, n, D] (reference: RepeatVector)."""

    def __init__(self, n: int, name: Optional[str] = None):
        super().__init__(name)
        self.n = n

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        return jnp.repeat(x[:, None, :], self.n, axis=1)


class Permute(Module):
    """Permute non-batch dims, 1-indexed like Keras (reference: Permute)."""

    def __init__(self, dims: Sequence[int], name: Optional[str] = None):
        super().__init__(name)
        self.dims = tuple(dims)

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        return jnp.transpose(x, (0,) + tuple(d for d in self.dims))


class Masking(Module):
    """Zero out timesteps equal to mask_value (reference: Masking; the
    downstream consumer sees zeros — explicit mask tensors travel
    separately in this framework)."""

    def __init__(self, mask_value: float = 0.0, name: Optional[str] = None):
        super().__init__(name)
        self.mask_value = mask_value

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        keep = jnp.any(x != self.mask_value, axis=-1, keepdims=True)
        return jnp.where(keep, x, 0.0)


# -- stochastic regularization -------------------------------------------------

class SpatialDropout1D(Module):
    """Drop whole channels (reference: SpatialDropout1D)."""

    def __init__(self, rate: float, name: Optional[str] = None):
        super().__init__(name)
        self.rate = float(rate)

    def _mask_shape(self, x: jax.Array) -> Tuple[int, ...]:
        return (x.shape[0], 1, x.shape[-1])

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        if not scope.training or self.rate <= 0.0:
            return x
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(scope.make_rng(), keep,
                                    self._mask_shape(x))
        return jnp.where(mask, x / keep, 0.0)


class SpatialDropout2D(SpatialDropout1D):
    def _mask_shape(self, x: jax.Array) -> Tuple[int, ...]:
        return (x.shape[0], 1, 1, x.shape[-1])


class SpatialDropout3D(SpatialDropout1D):
    def _mask_shape(self, x: jax.Array) -> Tuple[int, ...]:
        return (x.shape[0], 1, 1, 1, x.shape[-1])


class GaussianNoise(Module):
    """Additive zero-mean noise at train time (reference: GaussianNoise)."""

    def __init__(self, stddev: float, name: Optional[str] = None):
        super().__init__(name)
        self.stddev = float(stddev)

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        if not scope.training or self.stddev <= 0.0:
            return x
        return x + self.stddev * jax.random.normal(scope.make_rng(),
                                                   x.shape, x.dtype)


class GaussianDropout(Module):
    """Multiplicative 1-mean noise (reference: GaussianDropout)."""

    def __init__(self, rate: float, name: Optional[str] = None):
        super().__init__(name)
        self.rate = float(rate)

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        if not scope.training or self.rate <= 0.0:
            return x
        std = (self.rate / (1.0 - self.rate)) ** 0.5
        noise = 1.0 + std * jax.random.normal(scope.make_rng(), x.shape,
                                              x.dtype)
        return x * noise


# -- parametric activations ----------------------------------------------------

class LeakyReLU(Module):
    def __init__(self, alpha: float = 0.3, name: Optional[str] = None):
        super().__init__(name)
        self.alpha = alpha

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        return jax.nn.leaky_relu(x, self.alpha)


class ELU(Module):
    def __init__(self, alpha: float = 1.0, name: Optional[str] = None):
        super().__init__(name)
        self.alpha = alpha

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        return jax.nn.elu(x, self.alpha)


class ThresholdedReLU(Module):
    def __init__(self, theta: float = 1.0, name: Optional[str] = None):
        super().__init__(name)
        self.theta = theta

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        return jnp.where(x > self.theta, x, 0.0)


class SReLU(Module):
    """S-shaped ReLU with four learnable params per channel (reference:
    BigDL/keras-1 SReLU): piecewise-linear with learned thresholds/slopes
    at both tails."""

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        c = (x.shape[-1],)
        zeros = initializers.get("zeros")
        ones = initializers.get("ones")
        tl = scope.param("t_left", zeros, c).astype(x.dtype)
        al = scope.param("a_left", zeros, c).astype(x.dtype)
        tr = scope.param("t_right", ones, c).astype(x.dtype)
        ar = scope.param("a_right", ones, c).astype(x.dtype)
        below = tl + al * (x - tl)
        above = tr + ar * (x - tr)
        mid = x
        return jnp.where(x < tl, below, jnp.where(x > tr, above, mid))


# -- BigDL tensor-op layers (reference: zoo keras layers wrapping BigDL
#    Select/Narrow/Squeeze/Permute-style tensor utilities) -------------------

class Select(Module):
    """Pick index ``index`` along ``dim`` (reference: BigDL Select)."""

    def __init__(self, dim: int, index: int, name: Optional[str] = None):
        super().__init__(name)
        self.dim = dim
        self.index = index

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        size = x.shape[self.dim]
        if not -size <= self.index < size:
            # fail fast: jnp.take's default OOB mode fills NaN silently
            raise ValueError(
                f"Select index {self.index} out of range for dim "
                f"{self.dim} of size {size}")
        return jnp.take(x, self.index, axis=self.dim)


class Narrow(Module):
    """Slice ``length`` elements from ``offset`` along ``dim``;
    ``length=-1`` means "to the end" (reference: BigDL Narrow)."""

    def __init__(self, dim: int, offset: int, length: int = 1,
                 name: Optional[str] = None):
        super().__init__(name)
        self.dim = dim
        self.offset = offset
        self.length = length

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        stop = (x.shape[self.dim] if self.length == -1
                else self.offset + self.length)
        return jax.lax.slice_in_dim(x, self.offset, stop, axis=self.dim)


class Squeeze(Module):
    """Drop size-1 dims; the batch dim (axis 0) is never squeezed —
    a batch of one must stay a batch (reference: BigDL Squeeze, which
    operated on per-sample tensors without a batch axis)."""

    def __init__(self, dim: Optional[Union[int, Sequence[int]]] = None,
                 name: Optional[str] = None):
        super().__init__(name)
        self.dim = dim

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        if self.dim is not None:
            return jnp.squeeze(x, axis=self.dim)
        axes = tuple(i for i in range(1, x.ndim) if x.shape[i] == 1)
        return jnp.squeeze(x, axis=axes) if axes else x


class PReLU(Module):
    """Learnable leaky slope, shared over all but the channel dim
    (reference: PReLU)."""

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        alpha = scope.param("alpha", initializers.get("zeros"),
                            (x.shape[-1],))
        a = alpha.astype(x.dtype)
        return jnp.where(x >= 0, x, a * x)


# -- merge layers --------------------------------------------------------------

class Average(Module):
    def forward(self, scope: Scope, xs: Sequence[jax.Array]) -> jax.Array:
        return sum(xs) / len(xs)


class Maximum(Module):
    def forward(self, scope: Scope, xs: Sequence[jax.Array]) -> jax.Array:
        out = xs[0]
        for x in xs[1:]:
            out = jnp.maximum(out, x)
        return out


class Minimum(Module):
    def forward(self, scope: Scope, xs: Sequence[jax.Array]) -> jax.Array:
        out = xs[0]
        for x in xs[1:]:
            out = jnp.minimum(out, x)
        return out


class Subtract(Module):
    def forward(self, scope: Scope, xs: Sequence[jax.Array]) -> jax.Array:
        if len(xs) != 2:
            raise ValueError("Subtract takes exactly 2 inputs")
        return xs[0] - xs[1]


class Dot(Module):
    """Batched dot over given axes (reference: keras-1 merge mode='dot' /
    batch_dot): contract a's axis i with b's axis j, dim 0 stays the shared
    batch dim, remaining dims concatenate (a's first, then b's)."""

    def __init__(self, axes: Union[int, Sequence[int]] = -1,
                 normalize: bool = False, name: Optional[str] = None):
        super().__init__(name)
        self.axes = (axes, axes) if isinstance(axes, int) else tuple(axes)
        self.normalize = normalize

    def forward(self, scope: Scope, xs: Sequence[jax.Array]) -> jax.Array:
        a, b = xs
        ia = self.axes[0] % a.ndim
        ib = self.axes[1] % b.ndim
        if ia == 0 or ib == 0:
            raise ValueError("Dot cannot contract the batch dim (axis 0)")
        if self.normalize:
            a = a / (jnp.linalg.norm(a, axis=ia, keepdims=True) + 1e-12)
            b = b / (jnp.linalg.norm(b, axis=ib, keepdims=True) + 1e-12)
        # einsum: batch letter shared, one contraction letter, the rest pass
        letters = "abcdefghijklmnopqrstuvwxy"
        sub_a = ["z"] + [letters[i - 1] for i in range(1, a.ndim)]
        sub_b = ["z"] + [letters[a.ndim - 1 + i - 1]
                         for i in range(1, b.ndim)]
        sub_a[ia] = "K"
        sub_b[ib] = "K"
        out = [c for c in sub_a[1:] if c != "K"] + \
              [c for c in sub_b[1:] if c != "K"]
        spec = f"z{''.join(sub_a[1:])},z{''.join(sub_b[1:])}->z" \
               f"{''.join(out)}"
        return jnp.einsum(spec, a, b)


# -- BigDL/zoo extras ----------------------------------------------------------

class Highway(Module):
    """y = T(x) * H(x) + (1 - T(x)) * x (reference: keras-1 Highway, also a
    BigDL extra)."""

    def __init__(self, activation: Any = "relu",
                 name: Optional[str] = None):
        super().__init__(name)
        self.activation = activations.get(activation)

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        d = x.shape[-1]
        wh = scope.param("kernel", initializers.get("glorot_uniform"),
                         (d, d))
        bh = scope.param("bias", initializers.get("zeros"), (d,))
        wt = scope.param("gate_kernel", initializers.get("glorot_uniform"),
                         (d, d))
        # negative gate bias: start mostly carry, the standard highway init
        bt = scope.param("gate_bias",
                         lambda key, shape, dtype=jnp.float32:
                         jnp.full(shape, -1.0, dtype), (d,))
        h = self.activation(x @ wh.astype(x.dtype) + bh.astype(x.dtype))
        t = jax.nn.sigmoid(x @ wt.astype(x.dtype) + bt.astype(x.dtype))
        return t * h + (1.0 - t) * x


class Remat(Module):
    """Gradient checkpointing wrapper: recompute the wrapped module's
    forward during backward instead of storing its activations
    (jax.checkpoint).  The TPU memory/FLOPs trade for long-sequence or
    deep models — HBM is the usual bottleneck (SURVEY.md §7); the
    reference had no analog because BigDL kept all activations.

    ``Remat(TransformerLayer(8))`` drops the block's activation footprint
    to its inputs + outputs at ~1.3x compute.  ``save_names``: values the
    inner module tagged with ``jax.ad_checkpoint.checkpoint_name`` that are
    kept all the same (``ops.flash_attention`` tags its output and
    log-sum-exp: small beside what recomputing the kernel costs)."""

    def __init__(self, inner: Module, name: Optional[str] = None,
                 save_names: Sequence[str] = ()):
        super().__init__(name or (inner.name and f"remat_{inner.name}"))
        self.inner = inner
        self.save_names = tuple(save_names)

    def forward(self, scope: Scope, x: jax.Array, **kwargs: Any) -> jax.Array:
        name = self.inner.name or "inner"
        if scope.init_mode:
            return scope.child(self.inner, x, name=name, **kwargs)
        import zlib as _zlib
        params = scope.params.get(name, {})
        state_in = scope.state.get(name, {})
        rng = (jax.random.fold_in(scope.rng,
                                  _zlib.crc32(name.encode()))
               if scope.rng is not None else None)
        training = scope.training
        inner = self.inner

        def fn(p, xv):
            out, new_state = inner.apply({"params": p, "state": state_in},
                                         xv, training=training, rng=rng,
                                         **kwargs)
            return out, new_state

        policy = (jax.checkpoint_policies.save_only_these_names(
            *self.save_names) if self.save_names else None)
        out, new_state = jax.checkpoint(fn, policy=policy)(params, x)
        if new_state or state_in:
            scope.state[name] = new_state
        return out


class MaxoutDense(Module):
    """max over k linear pieces (reference: keras-1 MaxoutDense / BigDL
    Maxout)."""

    def __init__(self, units: int, nb_feature: int = 4,
                 use_bias: bool = True, name: Optional[str] = None):
        super().__init__(name)
        self.units = units
        self.nb_feature = nb_feature
        self.use_bias = use_bias

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        w = scope.param("kernel", initializers.get("glorot_uniform"),
                        (self.nb_feature, x.shape[-1], self.units))
        y = jnp.einsum("bd,kdu->bku", x, w.astype(x.dtype))
        if self.use_bias:
            b = scope.param("bias", initializers.get("zeros"),
                            (self.nb_feature, self.units))
            y = y + b.astype(y.dtype)
        return y.max(axis=1)
