"""The program's table of its own device ops (``core/trace.py``
``scopes_of_hlo`` / ``register_program`` / ``op_scopes``): the parser on a
literal HLO text, and a small ``Estimator.fit`` that registers its train
step without lowering, compiling or parsing anything itself."""

import ast

import numpy as np
import pytest

from analytics_zoo_tpu.core import trace as trace_lib

# Shaped like the TPU compiler's text (``compiled.as_text()``): computations
# first, their callers after, metadata last on the line.
HLO = '''HloModule jit_train_step, is_scheduled=true, entry_computation_layout={(f32[8,8]{1,0})->f32[8,8]{1,0}}

%region_0.1 (reduce_sum.1: f32[], reduce_sum.2: f32[]) -> f32[] {
  %reduce_sum.1 = f32[]{:T(128)} parameter(0), metadata={op_name="reduce_sum"}
  %reduce_sum.2 = f32[]{:T(128)} parameter(1), metadata={op_name="reduce_sum"}
  ROOT %reduce_sum.3 = f32[]{:T(128)} add(%reduce_sum.1, %reduce_sum.2), metadata={op_name="jit(train_step)/reduce_sum" stack_frame_id=9}
}

%fused_computation.1 (param_0.1: f32[8,8], param_1.1: f32[8,8]) -> f32[8,8] {
  %param_0.1 = f32[8,8]{1,0:T(8,128)} parameter(0)
  %param_1.1 = f32[8,8]{1,0:T(8,128)} parameter(1)
  %convert.1 = bf16[8,8]{1,0:T(8,128)(2,1)} convert(%param_0.1), metadata={op_name="jit(train_step)/jvp(bert)/layer_0/ffn1/convert_element_type" stack_frame_id=3}
  %convert.2 = bf16[8,8]{1,0:T(8,128)(2,1)} convert(%param_1.1), metadata={op_name="jit(train_step)/jvp(bert)/layer_0/ffn1/convert_element_type" stack_frame_id=3}
  ROOT %tanh.1 = bf16[8,8]{1,0:T(8,128)(2,1)} tanh(%convert.1), metadata={op_name="jit(train_step)/jvp(bert)/layer_0/ffn2/tanh"}
}

%fused_computation.2 (param_0.2: f32[8,8], param_1.2: f32[8,8]) -> f32[8,8] {
  %param_0.2 = f32[8,8]{1,0:T(8,128)} parameter(0)
  %param_1.2 = f32[8,8]{1,0:T(8,128)} parameter(1)
  %convolution.3 = f32[8,8]{1,0:T(8,128)} convolution(%param_0.2, %param_1.2), window={size=1}, dim_labels=bf0_oi0->bf0, metadata={op_name="jit(train_step)/transpose(jvp(bert))/layer_0/ffn1/dot_general" stack_frame_id=4}
  %mul.1 = f32[8,8]{1,0:T(8,128)} multiply(%convolution.3, %param_1.2), metadata={op_name="jit(train_step)/optimizer/mul" stack_frame_id=20}
  ROOT %add.1 = f32[8,8]{1,0:T(8,128)} add(%mul.1, %param_0.2), metadata={op_name="jit(train_step)/optimizer/add" stack_frame_id=21}
}

%fused_computation.3 (param_0.3: f32[8,8]) -> bf16[8,8] {
  %param_0.3 = f32[8,8]{1,0:T(8,128)} parameter(0)
  ROOT %convert.9 = bf16[8,8]{1,0:T(8,128)(2,1)} convert(%param_0.3)
}

ENTRY %main.7 (Arg_0.1: f32[8,8]) -> f32[8,8] {
  %Arg_0.1 = f32[8,8]{1,0:T(8,128)} parameter(0), metadata={op_name="ts[\\'params\\'][\\'w\\']"}
  %fusion.1 = bf16[8,8]{1,0:T(8,128)(2,1)} fusion(%Arg_0.1, %Arg_0.1), kind=kLoop, calls=%fused_computation.1
  %fusion.3 = bf16[8,8]{1,0:T(8,128)(2,1)} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.3
  %copy.4 = f32[8,8]{0,1:T(8,128)} copy(%Arg_0.1)
  %multiply_add_fusion = f32[8,8]{1,0:T(8,128)} fusion(%Arg_0.1, %copy.4), kind=kOutput, calls=%fused_computation.2, metadata={op_name="jit(train_step)/optimizer/add" stack_frame_id=21}
  %ragged-dot-none.8 = (bf16[8,8]{1,0:T(8,128)(2,1)}, s32[1]{0:T(128)}) custom-call(%Arg_0.1, /*index=1*/%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}, backend_config={"custom_call_config":{"body":"bW9kdWxl"}}
  %get-tuple-element.9 = bf16[8,8]{1,0:T(8,128)(2,1)} get-tuple-element(%ragged-dot-none.8), index=0
  %reduce.5 = f32[]{:T(128)} reduce(%multiply_add_fusion, %Arg_0.1), dimensions={0,1}, to_apply=%region_0.1, metadata={op_name="jit(train_step)/jvp(loss)/reduce_sum;jit(train_step)/jvp(head)/dot_general" stack_frame_id=9}
  ROOT %tuple.6 = (f32[8,8]{1,0:T(8,128)}) tuple(%multiply_add_fusion)
}
'''


@pytest.mark.parametrize("op_name,scope", [
    ("jit(train_step)/jvp(bert)/layer_3/mha/dot_general", "bert/layer_3/mha"),
    ("jit(train_step)/grad_accum/while/body/closed_call/transpose(jvp(bert))"
     "/layer_3/mha/dot_general", "grad_accum/bert/layer_3/mha"),
    ("jit(train_step)/optimizer/add", "optimizer"),
    # the step's own arithmetic: the empty path, not None
    ("jit(train_step)/div", ""),
    # no path of JAX's: an argument's name, one the compiler gave
    ("reduce_sum", None),
    ("ragged-dot-none", None),
    ("ts['params']['w']", None),
    # the primitive goes before the wrappers: what is left is the loop's own
    ("jit(train_step)/grad_accum/while/body/closed_call", "grad_accum"),
    # under jax.checkpoint the backward's path repeats the forward's
    ("jit(step)/transpose(jvp(layer_0))/moe/jvp(layer_0)/moe/checkpoint/"
     "rematted_computation/tanh", "layer_0/moe"),
    ("jit(train_step)/transpose(jvp(remat_0))/jvp(remat_0)/checkpoint/"
     "rematted_computation/gdn/conv/jit(silu)/mul", "remat_0/gdn/conv"),
    ("jit(train_step)/transpose(jvp(remat_0))/jvp(remat_0)/checkpoint/moe/"
     "while/body/transpose(jvp())/mul", "remat_0/moe"),
    # names XLA joined: the first
    ("jit(train_step)/jvp(remat_0)/gdn/transpose;jit(train_step)/jvp("
     "remat_1)/moe/router/dot_general", "remat_0/gdn"),
    # nested jits, a custom_vjp's call and an einsum's subscripts are JAX's
    ("jit(train_step)/jvp(embed)/jit(_take)/jit(_where)/select_n", "embed"),
    ("jit(train_step)/transpose(jvp(jit(take_along_axis)))/scatter-add", ""),
    ("jit(train_step)/jvp(remat_0)/gdn/custom_vjp_call/...ck,...kd->...cd/"
     "dot_general", "remat_0/gdn"),
    ("jit(train_step)/jvp(layer_2)/mamba/ssd/cond/branch_1_fun/exp",
     "layer_2/mamba/ssd"),
])
def test_a_scope_is_the_module_path_of_an_op_name(op_name, scope):
    assert trace_lib.scope_of_op_name(op_name) == scope


def test_every_instruction_of_every_computation_is_a_key():
    table = trace_lib.scopes_of_hlo(HLO)
    assert set(table) == {
        "reduce_sum.1", "reduce_sum.2", "reduce_sum.3", "param_0.1",
        "param_1.1", "convert.1", "convert.2", "tanh.1", "param_0.2",
        "param_1.2", "convolution.3", "mul.1", "add.1", "param_0.3",
        "convert.9", "Arg_0.1", "fusion.1", "fusion.3", "copy.4",
        "multiply_add_fusion", "ragged-dot-none.8", "get-tuple-element.9",
        "reduce.5", "tuple.6"}
    assert table["convolution.3"] == ("bert/layer_0/ffn1", frozenset())
    assert table["reduce.5"] == ("loss", frozenset())   # of two joined names
    assert table["reduce_sum.3"] == ("", frozenset())


def test_a_fusion_without_metadata_takes_its_computations_commonest_scope():
    table = trace_lib.scopes_of_hlo(HLO)
    assert table["fusion.1"] == ("bert/layer_0/ffn1",
                                 frozenset({"bert/layer_0/ffn2"}))
    # a compiler-made convert or copy carries nothing and calls nothing
    # that does
    assert table["fusion.3"] == (None, frozenset())
    assert table["copy.4"] == (None, frozenset())
    assert table["param_0.1"] == table["Arg_0.1"] == (None, frozenset())
    assert table["reduce_sum.1"] == (None, frozenset())


def test_an_op_the_compiler_named_takes_the_scope_of_what_feeds_it():
    """XLA:TPU replaces a ragged-dot's ``op_name`` with its own: the kernel
    belongs to the module whose rows it is fed, and so does what is made
    from its result without a name."""
    table = trace_lib.scopes_of_hlo(HLO)
    assert table["ragged-dot-none.8"] == ("bert/layer_0/ffn1", frozenset())
    assert table["get-tuple-element.9"] == ("bert/layer_0/ffn1", frozenset())


def test_also_tells_a_fusion_built_across_the_optimizers_boundary():
    scope, also = trace_lib.scopes_of_hlo(HLO)["multiply_add_fusion"]
    assert scope == "optimizer" and also == {"bert/layer_0/ffn1"}


def test_trace_imports_no_jax_at_module_level():
    with open(trace_lib.__file__) as f:
        tree = ast.parse(f.read())
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    names = [a.name for n in top if isinstance(n, ast.Import)
             for a in n.names] + [n.module or "" for n in top
                                  if isinstance(n, ast.ImportFrom)]
    assert names and not [n for n in names if n.split(".")[0] == "jax"]


def test_a_registered_program_is_read_once_and_only_when_asked():
    calls = []

    def hlo_text():
        calls.append(1)
        return HLO

    assert trace_lib.op_scopes("no_such_program") is None
    trace_lib.register_program("test_program", hlo_text)
    assert not calls                        # registering costs the closure
    table = trace_lib.op_scopes("test_program")
    assert table["multiply_add_fusion"][0] == "optimizer"
    assert trace_lib.op_scopes("test_program") is table and len(calls) == 1
    trace_lib.register_program("test_program", hlo_text)  # a new step
    assert trace_lib.op_scopes("test_program") is not table
    assert len(calls) == 2


def _arrays_in(fn):
    import jax
    seen = []
    for cell in fn.__closure__ or ():
        seen += [l for l in jax.tree_util.tree_leaves(cell.cell_contents)
                 if isinstance(l, (jax.Array, np.ndarray))]
    return seen


@pytest.mark.parametrize("accum", [1, 2])
def test_fit_registers_its_train_step_and_computes_nothing(monkeypatch,
                                                           accum):
    import analytics_zoo_tpu.nn as nn
    from analytics_zoo_tpu.core import init_orca_context, stop_orca_context
    from analytics_zoo_tpu.core.context import OrcaContext
    from analytics_zoo_tpu.orca.learn import Estimator

    mesh = init_orca_context("local")
    parsed, meshes = [], []
    real = trace_lib.scopes_of_hlo
    monkeypatch.setattr(trace_lib, "scopes_of_hlo",
                        lambda text: parsed.append(1) or real(text))
    class AsksTheMesh(nn.Module):      # as ring_self_attention does
        def forward(self, scope, x):
            from analytics_zoo_tpu.core import get_mesh
            meshes.append(get_mesh())
            return x

    model = nn.Sequential([nn.Dense(16, activation="relu", name="hidden"),
                           AsksTheMesh(name="asks"),
                           nn.Dense(4, name="head")])
    est = Estimator.from_keras(
        model, loss="sparse_categorical_crossentropy", optimizer="adamw",
        learning_rate=1e-3, grad_accum=accum, profile=True)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((64, 8)).astype("float32")
    y = rng.integers(0, 4, (64,))
    est.fit((x, y), epochs=2, batch_size=16, verbose=False)
    # fit() lowered, compiled and parsed nothing for the table
    assert est.compile_count == 1 and est._train_step._cache_size() == 1
    assert not parsed
    hlo_text = trace_lib._programs["train_step"]
    assert not _arrays_in(hlo_text)       # shapes, never the train state
    # ... and whoever asks later needs no context: the mesh rides along
    stop_orca_context()
    table = trace_lib.op_scopes("train_step")
    assert len(parsed) == 1 and not OrcaContext.initialized
    assert meshes.pop() is mesh           # traced again for the fit's mesh
    assert est._train_step._cache_size() == 1
    scopes = {s for s, _ in table.values() if s is not None}
    under = "grad_accum/" if accum > 1 else ""
    assert {"optimizer", under + "loss", under + "hidden",
            under + "head"} <= scopes, scopes
    assert trace_lib.op_scopes("train_step") is table and len(parsed) == 1
