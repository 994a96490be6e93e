"""ResNet v1.5 behind an on-device normalisation of uint8 NHWC images:
``bench.py``'s ``TrainNet`` and ``chip_smoke.py``'s ``ServeNet`` in one
family (the normalisation runs in the model's own dtype, as each original
has it), built from ``models.ResNet`` with the configuration's arguments.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.models import ResNet
from analytics_zoo_tpu.nn.module import Module

#: Largest |system - reference| over the reference's largest magnitude, at
#: the logits: 18 to 50 layers of bf16-rounded weights and activations
#: (2^-9 = 2e-3 a rounding); chip_smoke's SERVE_TOL, same measure.  A
#: float32 system lands near 1e-5 and an 8-bit one above 1e-1.
TOLERANCE = 5e-2

#: depth -> (blocks per stage, bottleneck?) (He et al. 2015, table 1)
SPECS = {18: ((2, 2, 2, 2), False), 34: ((3, 4, 6, 3), False),
         50: ((3, 4, 6, 3), True), 101: ((3, 4, 23, 3), True),
         152: ((3, 8, 36, 3), True)}
_NF_RELU_GAIN = 1.7139588594436646  # sqrt(2 / (1 - 1/pi)), Brock et al.
_NF_ALPHA = 0.2


class Uint8ResNet(Module):
    def __init__(self, model: dict):
        super().__init__()
        self.dtype = jnp.dtype(model["dtype"])
        self.net = ResNet(depth=model["depth"], class_num=model["class_num"],
                          width=model["width"], dtype=model["dtype"],
                          stem=model["stem"], norm=model["norm"])

    def forward(self, scope, x):
        x = (x.astype(self.dtype) - 127.0) * (1.0 / 64.0)
        return scope.child(self.net, x, name="resnet")


def build(config: dict) -> Module:
    return Uint8ResNet(config["model"])


def _pool(config: dict, traffic: dict, seed: int):
    size = traffic["image_size"]
    rng = np.random.default_rng([seed, 0])
    images = rng.integers(0, 256, (traffic["pool_size"], size, size, 3),
                          dtype=np.uint8)
    labels = rng.integers(0, config["model"]["class_num"],
                          (traffic["pool_size"],)).astype(np.int32)
    return images, labels


def loader(config: dict, traffic: dict, seed: int):
    """bench.py's DRAM-cached pool (the reference's FeatureSet kept the
    training set in DRAM): a worker copies a pool image and flips it at
    random, so a sample costs a memcpy and an augmentation, not an RNG."""
    images, labels = _pool(config, traffic, seed)

    def load_sample(i: int, rng=None) -> dict:
        r = np.random.default_rng([seed, 1, i])
        j = int(r.integers(0, len(images)))
        img = images[j]
        if r.integers(0, 2):
            img = img[:, ::-1]  # horizontal flip
        return {"x": np.ascontiguousarray(img), "y": labels[j]}

    return load_sample


def inputs(config: dict, traffic: dict, seed: int, n: int) -> np.ndarray:
    return _pool(config, traffic, seed)[0][:n]


def batch_spec(config: dict, traffic: dict):
    size = traffic["image_size"]
    return (traffic["global_batch"], size, size, 3), np.uint8


def forward_macs(depth: int, width: int, classes: int, image: int) -> float:
    """Multiply-accumulates of the convolutions and the classifier in one
    forward pass of one image (torchvision quotes 4.09e9 for ResNet-50 at
    224; tests hold this function to it)."""
    blocks, bottleneck = SPECS[depth]
    h = math.ceil(image / 2)
    macs = h * h * 7 * 7 * 3 * width
    h = math.ceil(h / 2)
    cin = width
    for stage, n in enumerate(blocks):
        f = width * 2 ** stage
        out_f = 4 * f if bottleneck else f
        for b in range(n):
            stride = 2 if (b == 0 and stage > 0) else 1
            ho = math.ceil(h / stride)
            if cin != out_f or stride != 1:
                macs += ho * ho * cin * out_f
            if bottleneck:
                macs += h * h * cin * f + ho * ho * 9 * f * f \
                    + ho * ho * f * out_f
            else:
                macs += ho * ho * 9 * cin * f + ho * ho * 9 * f * f
            cin, h = out_f, ho
    return float(macs + cin * classes)


def flops_per_sample(config: dict, traffic: dict) -> float:
    """Training FLOPs an image: 2 a multiply-accumulate, and the backward
    pass twice the forward (weight and input gradients)."""
    m = config["model"]
    return 3.0 * 2.0 * forward_macs(m["depth"], m["width"], m["class_num"],
                                    traffic["image_size"])


def reference(config: dict, variables: dict, images: np.ndarray
              ) -> np.ndarray:
    """Plain float32 inference forward on the system's parameter tree:
    batch norm with its running statistics (``norm="batch"``), or scaled
    weight standardisation with analytic variance tracking
    (``norm="nf"``).  The stem is the plain 7x7/2 convolution either way:
    the system's ``space_to_depth`` stem claims to equal it."""
    m = config["model"]
    blocks, bottleneck = SPECS[m["depth"]]
    nf = m["norm"] == "nf"

    def conv(x, p, stride=1, skip_scale=None):
        w = p["kernel"]
        if nf:
            fan_in = w.shape[0] * w.shape[1] * w.shape[2]
            mean = w.mean((0, 1, 2), keepdims=True)
            var = w.var((0, 1, 2), keepdims=True)
            gain = p["ws_gain"]
            if skip_scale is not None:
                gain = gain * p["skip_gain"] * skip_scale
            w = (w - mean) / jnp.sqrt(jnp.maximum(var * fan_in, 1e-4)) * gain
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def bn(x, p, s):
        return (x - s["mean"]) / jnp.sqrt(s["var"] + 1e-3) \
            * p["gamma"] + p["beta"]

    def forward(params, state, x):
        x = (x.astype(jnp.float32) - 127.0) / 64.0
        h = conv(x, params["stem"], 2)
        if not nf:
            h = bn(h, params["stem_bn"], state["stem_bn"])
        h = jax.nn.relu(h)
        h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                  (1, 2, 2, 1), "SAME")
        var = 1.0
        for stage, n in enumerate(blocks):
            f = m["width"] * 2 ** stage
            out_f = 4 * f if bottleneck else f
            for b in range(n):
                stride = 2 if (b == 0 and stage > 0) else 1
                project = h.shape[-1] != out_f or stride != 1
                name = f"stage{stage}_block{b}"
                p, st = params[name], state[name]
                strides = (1, stride, 1) if bottleneck else (stride, 1)
                last = len(strides) - 1
                if nf:
                    pre = jax.nn.relu(h) * (_NF_RELU_GAIN / math.sqrt(var))
                    shortcut = conv(pre, p["proj"], stride) if project else h
                    y = pre
                    for i, s in enumerate(strides):
                        y = conv(y, p[f"conv{i + 1}"], s,
                                 _NF_ALPHA if i == last else None)
                        if i < last:
                            y = jax.nn.relu(y) * _NF_RELU_GAIN
                    h = shortcut + y
                    var = (1.0 if project else var) + _NF_ALPHA ** 2
                else:
                    shortcut = h
                    if project:
                        shortcut = bn(conv(h, p["proj"], stride),
                                      p["proj_bn"], st["proj_bn"])
                    y = h
                    for i, s in enumerate(strides):
                        k = f"bn{i + 1}"
                        y = bn(conv(y, p[f"conv{i + 1}"], s), p[k], st[k])
                        if i < last:
                            y = jax.nn.relu(y)
                    h = jax.nn.relu(y + shortcut)
        if nf:
            h = jax.nn.relu(h)
        h = h.mean((1, 2))
        return h @ params["head"]["kernel"] + params["head"]["bias"]

    as_f32 = lambda tree: jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), tree)
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(forward)(
            as_f32(variables["params"]["resnet"]),
            as_f32(variables["state"]["resnet"]), jnp.asarray(images)))
