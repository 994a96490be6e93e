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
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

import analytics_zoo_tpu.nn as nn
from analytics_zoo_tpu.ops.fused_bn import bn_train

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the module, not the function ops/__init__ re-exports under its name
fa = importlib.import_module("analytics_zoo_tpu.ops.flash_attention")
gdr = importlib.import_module("analytics_zoo_tpu.ops.gated_delta_rule")
qkv = importlib.import_module("analytics_zoo_tpu.ops.gdn_qkv_conv")
gnorm = importlib.import_module("analytics_zoo_tpu.ops.gdn_gated_norm")
ssd = importlib.import_module("analytics_zoo_tpu.ops.mamba2_ssd")
rows = importlib.import_module("analytics_zoo_tpu.ops.moe_rows")

HBM_BYTES = 16 * 2 ** 30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2 (four chips).  The persistent compilation cache
    is off around these compiles: an entry written for a described device
    cannot be read back without the chip, and the next run would only
    warn."""
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """One described v5e device."""
    return SingleDeviceSharding(topo.devices[0])


def _on(chip, tree):
    """Shapes of ``tree`` placed on the described device."""
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        tree)


def _per_chip_bytes(compiled):
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    used = _per_chip_bytes(compiled)
    assert used < HBM_BYTES, f"{used / 2**30:.1f} GiB does not fit one v5e"
    return compiled


VMEM_BYTES = 128 * 2 ** 20  # one v5e TensorCore


def _kernels_vmem(text, name):
    """(requested, used) bytes of VMEM of each call of the Pallas kernel
    ``name`` in a compiled program's text: the limit its ``pallas_call``
    asked for and what Mosaic allocated under it."""
    return [tuple(int(re.search(
        key + r'":\[\{"memory_space":"1","offset":"\d+","size":"(\d+)"',
        line).group(1)) for key in ('"scoped_memory_configs',
                                    '"used_scoped_memory_configs'))
        for line in text.splitlines() if re.search(
            rf"%{name}[.\d]* = .*custom_call_target=\"tpu_custom_call\"",
            line)]


def _kernel_vmem(text, name):
    """The same for a kernel the program calls once."""
    (one,) = _kernels_vmem(text, name)
    return one


# The forward kernel, blocks from the shapes.  BERT-base's heads (B*H = 4*12,
# D = 64: padded to the 128 lanes) at 2,048, where MultiHeadAttention(
# use_flash="auto") starts taking the kernel, at 4,096 causal, and at 200
# (T padded to the block); the cells' shapes: a Trinity-Mini layer (one row
# of 16,384 tokens, 32 heads of 128) full and with a window of 2,048,
# Qwen3-Next's two rows x 16 heads of 256 at 8,192, Granite's 32 heads of 64
# at its scale of 1/64; 200 rows with a window of 50 (nothing aligned).
@pytest.mark.parametrize("bh,t,d,causal,window,scale", [
    (48, 2048, 64, False, None, None), (48, 4096, 64, True, None, None),
    (48, 200, 64, False, None, None), (32, 16384, 128, True, None, None),
    (32, 16384, 128, True, 2048, None), (32, 8192, 256, True, None, None),
    (32, 8192, 64, True, None, 1 / 64), (32, 200, 128, True, 50, None)])
def test_flash_forward_kernel_compiles(chip, bh, t, d, causal, window, scale):
    qkv = _on(chip, jax.ShapeDtypeStruct((bh, t, d), jnp.bfloat16))
    compiled = _compile(
        lambda q, k, v: fa._padded_pallas(
            q, k, v, fa._softmax_scale(scale, d), causal, None, None,
            interpret=False, window=window), qkv, qkv, qkv)
    name = ("flash_attention_fwd" if window is None
            else "flash_attention_window_fwd")
    asked, used = _kernel_vmem(compiled.as_text(), name)
    assert used <= asked < VMEM_BYTES, (asked, used)
    if d % 128 == 0 and t % 1024 == 0:
        # nothing is padded: no temporary of the program reaches HBM
        assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_flash_backward_compiles(chip):
    t = 4096
    x = _on(chip, jax.ShapeDtypeStruct((48, t, 64), jnp.bfloat16))
    lse = _on(chip, jax.ShapeDtypeStruct((48, t), jnp.float32))
    _compile(lambda q, k, v, o, l, g: fa._blocked_bwd_jax(
        q, k, v, o, l, g, 0.125, True, 256), x, x, x, x, lse, x)


def test_windowed_flash_backward_compiles_in_the_bands_memory(chip):
    """... and its temporaries are the band's (a k block against the 2303
    queries that see it), a third of what the causal walk of the same row
    takes."""
    t = 16384
    x = _on(chip, jax.ShapeDtypeStruct((32, t, 128), jnp.bfloat16))
    lse = _on(chip, jax.ShapeDtypeStruct((32, t), jnp.float32))
    band = _compile(lambda q, k, v, o, l, g: fa._band_bwd_jax(
        q, k, v, o, l, g, 128 ** -0.5, 2048, 256), x, x, x, x, lse, x)
    whole = _compile(lambda q, k, v, o, l, g: fa._blocked_bwd_jax(
        q, k, v, o, l, g, 128 ** -0.5, True, 256), x, x, x, x, lse, x)
    assert band.memory_analysis().temp_size_in_bytes \
        < 0.5 * whole.memory_analysis().temp_size_in_bytes


# the backward kernels at both cells' shapes (Qwen3-Next: 2 rows x 16 heads of
# 256 at 8,192; Trinity-Mini: 32 heads of 128 at 16,384, full and with a
# window of 2,048), the unaligned pair, and BERT's heads non-causal
@pytest.mark.parametrize("bh,t,d,causal,window", [
    (32, 8192, 256, True, None), (32, 16384, 128, True, None),
    (32, 16384, 128, True, 2048), (32, 200, 128, True, 50),
    (48, 2048, 64, False, None)])
def test_flash_backward_kernel_compiles(chip, bh, t, d, causal, window):
    x = _on(chip, jax.ShapeDtypeStruct((bh, t, d), jnp.bfloat16))
    lse = _on(chip, jax.ShapeDtypeStruct((bh, t), jnp.float32))
    compiled = _compile(
        lambda q, k, v, o, l, g: fa._padded_pallas_bwd(
            q, k, v, o, l, g, d ** -0.5, causal, interpret=False,
            window=window), x, x, x, x, lse, x)
    name = ("flash_attention_bwd" if window is None
            else "flash_attention_window_bwd")
    assert re.findall(rf"%({name}[.\d]*) = ", compiled.as_text())
    if d % 128 == 0 and t % 1024 == 0:
        # nothing is padded and nothing of a tile reaches HBM: the program's
        # temporaries are delta and lse as rows, where one [BH, T, 256]
        # float32 term of the blocked form is 268 MB
        assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


# one DeltaNet layer of the Qwen3-Next cell: two rows of 8192 tokens, 16 key
# and 32 value heads of 128, chunks of 64 in tiles of 128 rows
@pytest.mark.parametrize("which", ["forward", "forward_for_a_gradient",
                                   "backward"])
def test_delta_rule_kernels_compile(chip, which):
    b, t, hk, hv, d = 2, 8192, 16, 32, 128
    on = lambda shape, dtype: _on(chip, jax.ShapeDtypeStruct(shape, dtype))
    qk, v = on((b, t, hk, d), jnp.bfloat16), on((b, t, hv, d), jnp.bfloat16)
    gate, state = on((b, t, hv), jnp.float32), on((b, hv, d, d), jnp.float32)
    if which == "backward":
        states = on((b, hv, t // 64, d, d), jnp.bfloat16)
        inverse = on((b, hv, t // 128, 64, 128), jnp.float32)
        compiled = _compile(
            lambda *a: gdr._bwd_call(*a, 64, False), qk, qk, v, gate, gate,
            states, inverse, v, state)
    else:
        compiled = _compile(
            lambda *a: gdr._fwd_call(*a, 64, which != "forward", False),
            qk, qk, v, gate, gate, state)
    assert "tpu_custom_call" in compiled.as_text()


# ... and the way into them (PR 36): conv, SiLU, split and l2norm of the same
# layer as one kernel each way, over ``qkvz`` [2, 8192, 12288] in place
@pytest.mark.parametrize("which", ["forward", "backward"])
def test_gdn_qkv_conv_kernels_compile(chip, which):
    b, t, hk, hv, d, taps = 2, 8192, 16, 32, 128, 4
    on = lambda shape, dtype: _on(chip, jax.ShapeDtypeStruct(shape, dtype))
    qkvz, w = on((b, t, 12288), jnp.bfloat16), on((taps, 8192), jnp.float32)
    sizes = (b, t, hk, hv, d, d, taps, 1e-6, jnp.dtype(jnp.bfloat16), False)
    if which == "forward":
        compiled = _compile(lambda x, w: qkv._forward(*sizes)(x, w), qkvz, w)
        name = "gdn_qkv_conv_fwd"
    else:
        dqk, dvz = on((b, t, 2048), jnp.bfloat16), on((b, t, 4096),
                                                      jnp.bfloat16)
        compiled = _compile(lambda *a: qkv._backward(*sizes)(*a), dqk, dqk,
                            dvz, dvz, qkvz, qkvz, w)
        name = "gdn_qkv_conv_bwd"
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    asked, used = _kernel_vmem(text, name)
    assert used <= asked < VMEM_BYTES, (asked, used)
    # one pass: nothing of the program but the kernel's operands and results
    # reaches HBM (the slice that fed the conv was a 268 MB copy)
    assert compiled.memory_analysis().temp_size_in_bytes == 0


# ... and the way out of them: the per-head RMSNorm and the silu(z) gate of
# the same layer as one kernel each way, o as [2, 8192, 4096] and z read in
# place as the last 4,096 of qkvz's 12,288 columns
@pytest.mark.parametrize("which", ["forward", "backward"])
def test_gdn_gated_norm_kernels_compile(chip, which):
    b, t, h, d = 2, 8192, 32, 128
    on = lambda shape, dtype: _on(chip, jax.ShapeDtypeStruct(shape, dtype))
    o, qkvz = on((b, t, h * d), jnp.bfloat16), on((b, t, 12288), jnp.bfloat16)
    w = on((1, d), jnp.float32)
    sizes = (b, t, h * d, d, 8192, 1e-6, jnp.dtype(jnp.bfloat16), False)
    if which == "forward":
        compiled = _compile(lambda *a: gnorm._forward(*sizes)(*a), o, qkvz, w)
        name = "gdn_gated_norm_fwd"
    else:
        compiled = _compile(lambda *a: gnorm._backward(*sizes)(*a), o, qkvz,
                            w, o)
        name = "gdn_gated_norm_bwd"
    text = compiled.as_text()
    asked, used = _kernel_vmem(text, name)
    assert used <= asked < VMEM_BYTES, (asked, used)
    # one pass: nothing of the program but the kernel's operands and results
    # reaches HBM (z is no slice of qkvz)
    assert compiled.memory_analysis().temp_size_in_bytes == 0
    assert not re.search(r"\bslice\(", text)


# one expert layer, forward and backward, at each cell's shapes: tokens,
# top-k, experts held of all, expert width (Qwen3-Next: two rows of 8,192,
# top-10 of 512 holding 32 of width 512; Trinity-Mini: one row of 16,384,
# top-8 of 128 holding 16 of width 1,024, sigmoid router balanced by a
# bias; GLM-4.7-Flash: one row of 8,192, top-4 of 64 holding 8 of 1,536)
@pytest.mark.parametrize("rows_, experts, top_k, held, units, balance", [
    ((2, 8192), 512, 10, 32, 512, None), ((1, 16384), 128, 8, 16, 1024, 1e-3),
    ((1, 8192), 64, 4, 8, 1536, 1e-3)])
def test_expert_layer_moves_its_rows_in_its_kernels(
        chip, monkeypatch, rows_, experts, top_k, held, units, balance):
    """The rows reach the grouped matmuls and return to their tokens in
    ``moe_gather_rows`` and ``moe_combine_rows``, each inside the fast
    memory it asked for, and the compiled layer holds no scatter at all:
    no row scatter-add forward or backward, no top-k's transposed gather."""
    from analytics_zoo_tpu.parallel import DroplessMoE
    monkeypatch.setattr(rows, "dispatch", lambda *sizes: False)
    layer = DroplessMoE(experts, top_k, units, experts_held=held,
                        score_func="softmax" if balance is None
                        else "sigmoid", balance_coeff=balance)
    x = jax.ShapeDtypeStruct(rows_ + (2048,), jnp.bfloat16)
    variables = jax.eval_shape(
        lambda a: layer.init(jax.random.PRNGKey(0), a), x)

    def loss(params, state, a):
        out, _ = layer.apply({"params": params, "state": state}, a,
                             training=True)
        return jnp.sum(out.astype(jnp.float32))
    compiled = _compile(jax.grad(loss, argnums=(0, 2)), *_on(
        chip, (variables["params"], variables["state"], x)))
    text = compiled.as_text()
    for name in ("moe_gather_rows", "moe_combine_rows"):
        calls = _kernels_vmem(text, name)
        assert calls and all(used <= asked < VMEM_BYTES
                             for asked, used in calls), (name, calls)
    assert not re.search(r"\bscatter(-add)?[.\d]* = |\bscatter\(", text)


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


def _abstract_train_step(est, mesh, x, y):
    """``est``'s train step lowered for ``mesh`` from shapes alone, for a
    strategy that replicates the parameters (``sharding="dp"``): the train
    state as ``fit()`` would place it, the batch as the feed shards it.  A
    described device holds no array, so nothing is placed and
    ``_ensure_initialized`` is stood in for."""
    from analytics_zoo_tpu.data import batch_sharding
    replicated = NamedSharding(mesh, P())

    def on(tree):
        return jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                           sharding=replicated), tree)

    variables = jax.eval_shape(
        lambda a: est.model.init(jax.random.PRNGKey(0), a, training=True), x)
    est._ts = on({
        "params": variables["params"], "state": variables["state"],
        "opt_state": jax.eval_shape(est.tx.init, variables["params"]),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
        "rng": jax.eval_shape(lambda: jax.random.PRNGKey(0)),
        "bad_steps": jax.ShapeDtypeStruct((), jnp.int32)})
    est._build_steps(mesh)
    batch = {
        "x": jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=batch_sharding(
            mesh, x.ndim, seq_dim_size=x.shape[1], dim0_size=x.shape[0])),
        "y": jax.ShapeDtypeStruct(y.shape, y.dtype, sharding=batch_sharding(
            mesh, y.ndim, dim0_size=y.shape[0]))}
    return est._train_step.lower(est._ts, batch)


def _bert_base_step(mesh, global_batch, accum):
    import json
    from analytics_zoo_tpu.orca.learn import Estimator
    from benchmark.families import bert_mlm
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark/configs/bert_base_mlm.json")) as f:
        config = json.load(f)
    est = Estimator.from_keras(
        bert_mlm.build(config), loss=config["loss"],
        optimizer=config["optimizer"]["name"],
        learning_rate=config["optimizer"]["learning_rate"],
        grad_accum=accum)
    ids = jax.ShapeDtypeStruct((global_batch, 512), jnp.int32)
    return _abstract_train_step(est, mesh, ids, ids).compile()


@pytest.fixture(scope="module")
def bert_one_chip(topo):
    """``bert_base_fit_s512``'s train step (batch 32 = micro 16 x accum 2)
    compiled for one described chip, once for the tests that read it."""
    return _bert_base_step(Mesh(np.asarray(topo.devices[:1]), ("data",)),
                           32, 2)


def _megabytes(result_type):
    """Bytes of an HLO result type such as ``(f32[768,768]{...}, f32[])``,
    in MB."""
    width = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2}
    total = 0
    for dtype, dims in re.findall(r"\b(f32|s32|u32|bf16|f16)\[([\d,]*)\]",
                                  result_type):
        total += width[dtype] * int(np.prod(
            [int(d) for d in dims.split(",") if d] or [1]))
    return total / 1e6


def test_bert_base_dp4_accum_step_computes_a_chips_rows_once(topo,
                                                             bert_one_chip):
    """The ``bert_base_fit_dp4`` cell's train step (BERT-base, seq 512,
    global batch 128 = 4 chips x micro 16 x accum 2, mesh {data: 4}) for
    the described 2x2: it fits a chip in the one-chip cell's memory, the
    largest logits buffer is a chip's 16 rows of a micro-batch (the parent
    held 32: GSPMD had replicated half of every micro-batch), and a chip's
    FLOPs are the one-chip cell's (batch 32 on one device).

    The gradient reduce: the TPU compiler's while-loop code motion moves
    the all-reduce of each ``gsum + g`` out of the accumulation loop, so
    436 of the 530 MB of f32 gradients meet once a step.  The exception is
    the token table: XLA folds its gradient's scatter-add INTO the carry
    (``scatter(gsum, ids, rows)``), which leaves no ``carry + all-reduce``
    to move, so those 94 MB are reduced once a micro-batch (PERF.md,
    section 7)."""
    from hlo_loops import collectives
    four = _bert_base_step(Mesh(np.asarray(topo.devices), ("data",)), 128, 2)
    one = bert_one_chip
    assert _per_chip_bytes(four) < HBM_BYTES
    assert _per_chip_bytes(four) <= 1.05 * _per_chip_bytes(one)
    ratio = four.cost_analysis()["flops"] / one.cost_analysis()["flops"]
    assert 0.98 < ratio < 1.02, ratio
    text = four.as_text()
    logits_rows = {int(m) for m in re.findall(
        r"(?:f32|bf16)\[(\d+),512,30522\]", text)}
    assert max(logits_rows) == 16, logits_rows
    in_loop, outside = collectives(text)
    assert set(in_loop) <= {"all-reduce"}, in_loop
    looped = sum(map(_megabytes, in_loop.get("all-reduce", [])))
    sunk = sum(map(_megabytes, outside.get("all-reduce", [])))
    assert looped < 95 and sunk > 430, (looped, sunk)
    assert collectives(one.as_text()) == ({}, {})


def test_bert_base_one_chip_step_is_the_program_pr26_recorded(bert_one_chip):
    """``bert_base_fit_s512``'s train step, compiled for one described chip:
    the FLOPs and bytes PERF.md records for PR 26, to the byte.  A change to
    code the BERT cells share (``MultiHeadAttention``, ``Dense``, the loss,
    the train step) that means to leave them alone shows it here."""
    cost = bert_one_chip.cost_analysis()
    assert int(cost["flops"]) == 5922331557888
    assert int(cost["bytes accessed"]) == 61243039744
    assert bert_one_chip.memory_analysis().temp_size_in_bytes == 4455141888


def test_bert_base_step_names_its_matmuls_by_module(bert_one_chip):
    """The same executable's text as the program's table of its device ops
    (``core/trace.py scopes_of_hlo``, PR 35), on real TPU HLO: every
    ``convolution`` outside a fusion and every fusion that carries one has
    a scope under a BERT layer, the head, the loss or the optimizer; the
    optimizer's update and the loss are there under their names."""
    from analytics_zoo_tpu.core.trace import scopes_of_hlo
    text = bert_one_chip.as_text()
    table = scopes_of_hlo(text)
    carries, matmuls, computation = set(), [], None
    for line in text.splitlines():
        if not line.startswith(" "):
            computation = line.split(" ")[1 if line.startswith("ENTRY")
                                          else 0].lstrip("%")
            continue
        name = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = ", line)
        called = re.search(r"calls=%?([\w.\-]+)", line)
        if name and (" convolution(" in line
                     or (called and called.group(1) in carries)):
            carries.add(computation)
            matmuls.append(name.group(1))
    assert len(matmuls) > 300, len(matmuls)
    known = re.compile(r"(^|/)(bert/layer_\d+|mlm_head|loss|optimizer)(/|$)")
    unnamed = [m for m in matmuls
               if not known.search(table[m][0] or "")]
    assert not unnamed, [(m, table[m]) for m in unnamed[:5]]
    scopes = {s for s, _ in table.values() if s is not None}
    assert {"optimizer", "grad_accum/loss", "grad_accum/mlm_head"} <= scopes
    assert {f"grad_accum/bert/layer_{i}/mha" for i in range(12)} <= scopes


def _flash_takes_the_chips_branch(monkeypatch):
    """``default_backend()`` is "cpu" during these compiles: make flash
    attention's forward and backward take the branch they take on the
    chip, the compiled kernels."""
    monkeypatch.setattr(
        fa, "_flash_fwd_dispatch",
        lambda q, k, v, causal, bq, bk, window=None, scale=None:
        fa._padded_pallas(q, k, v, fa._softmax_scale(scale, q.shape[-1]),
                          causal, bq, bk, interpret=False, window=window))
    monkeypatch.setattr(
        fa, "_flash_bwd_dispatch",
        lambda q, k, v, o, lse, g, causal, bk, window=None, scale=None:
        fa._padded_pallas_bwd(q, k, v, o, lse, g,
                              fa._softmax_scale(scale, q.shape[-1]),
                              causal, interpret=False, window=window))


def test_qwen3next_cell_train_step_compiles_for_one_chip(topo, monkeypatch):
    """``qwen3next_ep16_fit_s8192``'s train step at its real sizes (625.7 M
    parameters with AdamW's moments, two rows of 8192 tokens): the chip's
    compiler takes it — a program over the chip's memory is refused here —
    with the flash kernels (forward and backward), the two delta-rule
    kernels and the two kernels of the way into them under their names,
    the two of the way out of them, the grouped matmuls of the expert layer
    as XLA's ragged-dot kernels, and one call of each kernel a layer and
    step (the blocks' recomputation keeps what the kernels' backward passes
    read: three DeltaNet layers, one attention layer), but for
    ``gdn_qkv_conv_fwd`` and ``gdn_gated_norm_fwd``, which the
    recomputation runs again."""
    import json
    from analytics_zoo_tpu.orca.learn import Estimator
    from benchmark.families import qwen3_next
    _flash_takes_the_chips_branch(monkeypatch)
    monkeypatch.setattr(gdr, "dispatch", lambda dk, dv, chunk: False)
    monkeypatch.setattr(qkv, "dispatch", lambda *sizes: False)
    monkeypatch.setattr(gnorm, "dispatch", lambda *sizes: False)
    monkeypatch.setattr(rows, "dispatch", lambda *sizes: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark/configs/qwen3_next_80b_a3b_ep16.json")) as f:
        config = json.load(f)
    est = Estimator.from_keras(
        qwen3_next.build(config), loss=config["loss"],
        optimizer=config["optimizer"]["name"],
        learning_rate=config["optimizer"]["learning_rate"])
    ids = jax.ShapeDtypeStruct((2, 8192), jnp.int32)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    compiled = _abstract_train_step(est, mesh, ids, ids).compile()
    assert _per_chip_bytes(compiled) < HBM_BYTES
    text = compiled.as_text()
    kernels = re.findall(r"%(flash_attention_fwd[.\d]*) = ", text)
    assert len(kernels) == 1, kernels
    backward = re.findall(r"%(flash_attention_bwd[.\d]*) = ", text)
    assert len(backward) == 1, backward
    for name in ("gated_delta_rule_fwd", "gated_delta_rule_bwd"):
        calls = re.findall(rf"%({name}[.\d]*) = ", text)
        assert len(calls) == 3, (name, calls)
    # the way into them (PR 36) engages once a layer and pass: forward and
    # recomputed forward, and one backward (its inputs are not kept); so
    # does the way out of them
    for name, count in (("gdn_qkv_conv_fwd", 6), ("gdn_qkv_conv_bwd", 3),
                        ("gdn_gated_norm_fwd", 6), ("gdn_gated_norm_bwd", 3)):
        calls = re.findall(rf"%({name}[.\d]*) = ", text)
        assert len(calls) == count, (name, calls)
    # ... and no float32 [2, 8192, 4096] array (silu(z), the norm's product
    # and their gradients, as rows or as heads) is left under a DeltaNet
    # mixer outside them, nor a slice that would make z, nor a copy of the
    # delta rule's output between rows and heads
    from analytics_zoo_tpu.core.trace import scopes_of_hlo
    table = scopes_of_hlo(text)
    wide = re.findall(r"%([\w.\-]+) = f32\[2,8192,(?:4096|32,128)\]", text)
    assert not [n for n in wide if re.search(r"(^|/)gdn(/|$)",
                                             (table.get(n) or ("",))[0] or "")]
    assert not re.findall(
        r"= bf16\[(?:2,8192,4096|2048,8,32,128)\]\S* (?:slice|reshape|copy)\(",
        text)
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) >= 6 * 4
    held = sum(int(np.prod(l.shape)) for l in
               jax.tree_util.tree_leaves(est._ts["params"]))
    assert held == 625_667_136
    # the expert layers' rows move through their two kernels (one gather
    # forward, two backward; one combine each way; the first window taken
    # before the windows' loop, whose copy the program holds too)
    for name, count in (("moe_gather_rows", 6 * 4), ("moe_combine_rows",
                                                      4 * 4)):
        calls = re.findall(rf"%({name}[.\d]*) = ", text)
        assert len(calls) == count, (name, calls)
    # the program as it stands, to the byte: a change to code this cell shares
    # (``MultiHeadAttention``, the flash kernels' wrapper, ``DroplessMoE``)
    # that means to leave it alone shows it here.  PR 32 - 35 left
    # 25,031,698,546,688 FLOPs, 340,933,083,136 bytes accessed and
    # 5,699,792,384 B of temporaries (PR 34: + 96,768 B for the forward
    # kernel's tile lists); PR 36's two kernels a DeltaNet layer took
    # 52.2 GB of passes out (288,747,782,144 B, 4,838,883,328 B of
    # temporaries); PR 40's row kernels took the expert layers' gathers,
    # masks and scatter-adds and the top-k's gather and its transpose out
    # (the FLOPs count the grouped matmuls of the peeled first window twice)
    # and left 27,054,875,607,040 FLOPs, 150,427,795,456 B and
    # 4,929,047,552 B of temporaries; the two kernels of the mixer's way
    # out took its norm, float32 gate and z slices out, and the delta
    # rule's output kept in the layout its kernel wrote took the relayout
    # copies of it out: 18.0 GB of passes (temporaries + 19.7 MB, 0.4%:
    # the compiler's schedule, which reading z as a slice moved as much)
    cost = compiled.cost_analysis()
    assert int(cost["flops"]) == 27_037_754_458_112
    assert int(cost["bytes accessed"]) == 132_446_543_872
    assert 150_427_795_456 - int(cost["bytes accessed"]) > 17.9e9
    assert compiled.memory_analysis().temp_size_in_bytes == 4_948_792_320


def test_trinity_mini_cell_train_step_compiles_for_one_chip(topo,
                                                            monkeypatch):
    """``trinity_mini_ep8_fit_s16384``'s train step at its real sizes
    (705.5 M parameters with AdamW's moments, one row of 16,384 tokens):
    the chip's compiler takes it inside the chip's memory, with the
    windowed kernels (forward, backward) once a sliding layer and the plain
    ones once for the full layer (the blocks' recomputation keeps what the
    backward passes read), and the expert layers' grouped matmuls as
    ragged-dot kernels."""
    import json
    from analytics_zoo_tpu.orca.learn import Estimator
    from benchmark.families import afmoe
    _flash_takes_the_chips_branch(monkeypatch)
    monkeypatch.setattr(rows, "dispatch", lambda *sizes: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark/configs/trinity_mini_ep8.json")) as f:
        config = json.load(f)
    est = Estimator.from_keras(
        afmoe.build(config), loss=config["loss"],
        optimizer=config["optimizer"]["name"],
        learning_rate=config["optimizer"]["learning_rate"])
    ids = jax.ShapeDtypeStruct((1, 16384), jnp.int32)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    compiled = _abstract_train_step(est, mesh, ids, ids).compile()
    assert _per_chip_bytes(compiled) < HBM_BYTES
    text = compiled.as_text()
    window = re.findall(r"%(flash_attention_window_fwd[.\d]*) = ", text)
    full = re.findall(r"%(flash_attention_fwd[.\d]*) = ", text)
    assert (len(window), len(full)) == (4, 1), (window, full)
    window = re.findall(r"%(flash_attention_window_bwd[.\d]*) = ", text)
    full = re.findall(r"%(flash_attention_bwd[.\d]*) = ", text)
    assert (len(window), len(full)) == (4, 1), (window, full)
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) >= 6 * 4
    assert len(re.findall(r"%moe_combine_rows[.\d]* = ", text)) >= 4 * 4
    held = sum(int(np.prod(l.shape)) for l in
               jax.tree_util.tree_leaves(est._ts["params"]))
    assert held == 705_473_792


@pytest.mark.slow   # two minutes of compiling: a builder's tool, outside tier-1
def test_granite_cell_train_step_compiles_for_one_chip(topo, monkeypatch):
    """``granite4h_micro_fit_s8192``'s train step at its real sizes (772.2 M
    parameters with AdamW's moments, one row of 8,192 tokens): the chip's
    compiler takes it inside the chip's memory, with the flash kernels
    (forward, backward) once for the one attention layer, at heads of 64
    padded to the 128 lanes, and the scan as its two kernels (PR 38): the
    forward twice a Mamba layer (the forward pass and the block's
    recomputation), the backward once, and no chunk's [.., 256, 256] term
    left in HBM."""
    import json
    from analytics_zoo_tpu.orca.learn import Estimator
    from benchmark.families import granite_hybrid
    _flash_takes_the_chips_branch(monkeypatch)
    monkeypatch.setattr(ssd, "dispatch", lambda *sizes: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark/configs/granite_4_0_h_micro_pp4.json")) as f:
        config = json.load(f)
    est = Estimator.from_keras(
        granite_hybrid.build(config), loss=config["loss"],
        optimizer=config["optimizer"]["name"],
        learning_rate=config["optimizer"]["learning_rate"])
    ids = jax.ShapeDtypeStruct((1, 8192), jnp.int32)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    compiled = _abstract_train_step(est, mesh, ids, ids).compile()
    assert _per_chip_bytes(compiled) < HBM_BYTES
    text = compiled.as_text()
    assert len(re.findall(r"%(flash_attention_fwd[.\d]*) = ", text)) == 1
    assert len(re.findall(r"%(flash_attention_bwd[.\d]*) = ", text)) == 1
    assert re.search(r"bf16\[32,8192,128\]", text)      # 64 padded to 128
    for name, count in (("mamba2_ssd_fwd", 18), ("mamba2_ssd_bwd", 9)):
        calls = re.findall(rf"%({name}[.\d]*) = ", text)
        assert len(calls) == count, (name, calls)
    assert not re.search(r"\[[\d,]*,256,256\]", text)  # the chunks' terms
    held = sum(int(np.prod(l.shape)) for l in
               jax.tree_util.tree_leaves(est._ts["params"]))
    assert held == 772_160_448
    # the jax.numpy scan's passes are gone: PR 37's step accessed
    # 226,534,309,888 bytes (1,161 mentions of a [.., 256, 256] array in
    # its text), PR 38's 146,739,462,144, with 2.36 GB of temporaries both
    cost = compiled.cost_analysis()
    assert 226_534_309_888 - int(cost["bytes accessed"]) > 60e9
    assert compiled.memory_analysis().temp_size_in_bytes < 2.6e9


@pytest.mark.slow   # a minute or two of compiling: a builder's tool
def test_glm_cell_train_step_compiles_for_one_chip(topo, monkeypatch):
    """``glm47_flash_ep8_fit_s8192``'s train step at its real sizes (706.5 M
    parameters with AdamW's moments, one row of 8,192 tokens, both
    prediction depths' logits): the chip's compiler takes it inside the
    chip's memory, with the flash kernels (forward, backward) once for each
    of the six latent-attention layers at ``[20, 8192, 256]`` (the blocks'
    recomputation keeps what the backward passes read), the five expert
    layers' grouped matmuls as ragged-dot kernels, and ONE head matmul
    over both depths' 16,384 rows."""
    import json
    from analytics_zoo_tpu.orca.learn import Estimator
    from benchmark.families import glm_moe_lite
    _flash_takes_the_chips_branch(monkeypatch)
    monkeypatch.setattr(rows, "dispatch", lambda *sizes: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmark/configs/glm_4_7_flash_ep8.json")) as f:
        config = json.load(f)
    est = Estimator.from_keras(
        glm_moe_lite.build(config), loss=config["loss"],
        optimizer=config["optimizer"]["name"],
        learning_rate=config["optimizer"]["learning_rate"])
    ids = jax.ShapeDtypeStruct((1, 8192), jnp.int32)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    compiled = _abstract_train_step(est, mesh, ids, ids).compile()
    print("per-chip bytes", _per_chip_bytes(compiled) / 1e9, "GB;",
          compiled.memory_analysis())
    assert _per_chip_bytes(compiled) < HBM_BYTES
    text = compiled.as_text()
    assert len(re.findall(r"%(flash_attention_fwd[.\d]*) = ", text)) == 6
    assert len(re.findall(r"%(flash_attention_bwd[.\d]*) = ", text)) == 6
    assert re.search(r"bf16\[20,8192,256\]", text)
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) >= 6 * 5
    assert re.search(r"bf16\[1,2,8192,19360\]", text)   # one head pass
    held = sum(int(np.prod(l.shape)) for l in
               jax.tree_util.tree_leaves(est._ts["params"]))
    assert held == 706_518_528


@pytest.mark.slow   # a few minutes of compiling: a builder's tool
def test_ouro_cell_train_step_compiles_for_one_chip(topo, monkeypatch):
    """``ouro26b_pp6_fit_s8192``'s train step at its real sizes (612.4 M
    parameters with AdamW's moments, one row of 8,192 tokens, 8 layers run
    4 times): the chip's compiler takes it inside the chip's memory, with
    the flash kernels (forward, backward) once for each of the 32 block
    applications at ``[16, 8192, 128]`` (the blocks' recomputation keeps
    what the backward passes read), and no ``[*, 49152]`` array wider than
    one head chunk of 1,024 tokens but the head's weight and its gradient:
    the four passes' logits are never held."""
    import json
    from analytics_zoo_tpu.orca.learn import Estimator
    from benchmark.families import ouro
    _flash_takes_the_chips_branch(monkeypatch)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark/configs/ouro_2_6b_pp6.json")) as f:
        config = json.load(f)
    est = Estimator.from_keras(
        ouro.build(config), loss=config["loss"],
        optimizer=config["optimizer"]["name"],
        learning_rate=config["optimizer"]["learning_rate"])
    ids = jax.ShapeDtypeStruct((1, 8192), jnp.int32)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    compiled = _abstract_train_step(est, mesh, ids, ids).compile()
    cost = compiled.cost_analysis()
    print("per-chip bytes", _per_chip_bytes(compiled) / 1e9, "GB;",
          compiled.memory_analysis(), "flops", cost["flops"],
          "bytes accessed", cost["bytes accessed"])
    assert _per_chip_bytes(compiled) < HBM_BYTES
    text = compiled.as_text()
    assert len(re.findall(r"%(flash_attention_fwd[.\d]*) = ", text)) == 32
    assert len(re.findall(r"%(flash_attention_bwd[.\d]*) = ", text)) == 32
    assert re.search(r"bf16\[16,8192,128\]", text)
    from analytics_zoo_tpu.models.ouro import HEAD_CHUNK as chunk
    for dims in re.findall(r"\b(?:f32|bf16)\[([\d,]*),49152\]", text):
        rows = int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
        assert rows <= chunk or dims == "2048", dims
    held = sum(int(np.prod(l.shape)) for l in
               jax.tree_util.tree_leaves(est._ts["params"]))
    assert held == 612_438_017
    # the compiler's own counts at PR 39 (the Pallas kernels' FLOPs are not
    # in them): 108.87 TFLOP, 419.7 GB accessed, 6.52 GB of temporaries
    assert abs(cost["flops"] - 108.87e12) < 0.5e12
    assert abs(cost["bytes accessed"] - 419.7e9) < 5e9
    assert compiled.memory_analysis().temp_size_in_bytes < 7.0e9
