"""The chip's compiler, asked here: the main path's kernels and steps at
real widths, compiled for a described (not attached) TPU v5e.

No chip time and no results — a compile that passes is not a chip run — but
what the TPU compiler refuses (a slice off the tiling, too much fast memory,
a program over 16 GB) is refused here, for every later PR, in about two
seconds a case.  ``jax.default_backend()`` is ``cpu`` during these compiles,
so each case lowers the kernel or the jitted function itself, never a wrapper
that dispatches on the backend.
"""

import importlib
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import analytics_zoo_tpu.nn as nn
from analytics_zoo_tpu.ops.fused_bn import bn_train

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the module, not the function ops/__init__ re-exports under its name
fa = importlib.import_module("analytics_zoo_tpu.ops.flash_attention")

HBM_BYTES = 16 * 2 ** 30  # one v5e chip


@pytest.fixture(scope="module")
def chip():
    """One described v5e device.  The persistent compilation cache is off
    around these compiles: an entry written for a described device cannot
    be read back without the chip, and the next run would only warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu: nothing to ask
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(chip, tree):
    """Shapes of ``tree`` placed on the described device."""
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 2**30:.1f} GiB does not fit one v5e"
    return compiled


# BERT-base head shape: B*H = 4*12, D = 64.  2048 is where
# MultiHeadAttention(use_flash="auto") starts taking the kernel; 200 is the
# unaligned case (T padded to the block, D padded to the 128-lane tile).
# Block sizes are the ones flash_attention() passes by default.
@pytest.mark.parametrize("t,causal", [(2048, False), (4096, True),
                                      (200, False)])
def test_flash_forward_kernel_compiles(chip, t, causal):
    qkv = _on(chip, jax.ShapeDtypeStruct((48, t, 64), jnp.bfloat16))
    compiled = _compile(
        lambda q, k, v: fa._padded_pallas(q, k, v, 0.125, causal, 256, 256,
                                          interpret=False),
        qkv, qkv, qkv)
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_backward_compiles(chip):
    t = 4096
    x = _on(chip, jax.ShapeDtypeStruct((48, t, 64), jnp.bfloat16))
    lse = _on(chip, jax.ShapeDtypeStruct((48, t), jnp.float32))
    _compile(lambda q, k, v, o, l, g: fa._blocked_bwd_jax(
        q, k, v, o, l, g, 0.125, True, 256), x, x, x, x, lse, x)


def test_bert_base_layer_fwd_bwd_compiles(chip):
    layer = nn.TransformerLayer(12, remat_attention=True, pre_ln=True)
    x = jax.ShapeDtypeStruct((4, 512, 768), jnp.bfloat16)
    variables = jax.eval_shape(
        lambda a: layer.init(jax.random.PRNGKey(0), a, training=True), x)

    def loss(params, a):
        out, _ = layer.apply({"params": params,
                              "state": variables["state"]}, a,
                             training=True, rng=jax.random.PRNGKey(1))
        return out.astype(jnp.float32).mean()

    _compile(jax.value_and_grad(loss), _on(chip, variables["params"]),
             _on(chip, x))


def test_fused_bn_fwd_bwd_compiles(chip):
    # ResNet-50 stage-1 feature map at the bench batch
    x = _on(chip, jax.ShapeDtypeStruct((128, 56, 56, 256), jnp.bfloat16))
    c = _on(chip, jax.ShapeDtypeStruct((256,), jnp.float32))

    def loss(a, gamma, beta):
        y, mean, var = bn_train(a, gamma, beta, 1e-5)
        return y.astype(jnp.float32).mean() + mean.sum() + var.sum()

    _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), x, c, c)


def test_resnet18_serving_forward_compiles(chip):
    import chip_smoke
    model = chip_smoke.ServeNet()
    x = jax.ShapeDtypeStruct((16, 224, 224, 3), jnp.uint8)
    variables = jax.eval_shape(
        lambda a: model.init(jax.random.PRNGKey(0), a), x)
    bf16 = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, jnp.bfloat16 if jnp.issubdtype(s.dtype, jnp.floating)
            else s.dtype), variables)

    def fwd(v, a):
        return model.apply(v, a, training=False)[0]

    _compile(fwd, _on(chip, bf16), _on(chip, x))
