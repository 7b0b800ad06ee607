"""observability/opindex.py: the scopes the nets write, the op index the
compiled step program yields, and where each instruction is placed."""

import re

import numpy as np
import pytest

from deeplearning4j_tpu import zoo
from deeplearning4j_tpu.datasets import DataSet
from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import Dense, Output
from deeplearning4j_tpu.nn.conf.layers_conv import (
    BatchNorm, Convolution2D, GlobalPooling)
from deeplearning4j_tpu.nn.conf.vertices import ElementWiseVertex
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.updater import Sgd
from deeplearning4j_tpu.observability import opindex
from deeplearning4j_tpu.parallel import make_mesh

# Recorded from a v5e compile (AOT, jax 0.9) of a scanned two-layer step
# with one scope per layer and one round the update; shapes and
# backend_config trimmed. %fusion.51 is named by XLA after nothing and
# would be `tanh...` by its root; its computation holds the matmul (a
# `convolution` on the TPU) of layer L0_dense, behind a nested fusion.
HLO = '''HloModule jit_multi, is_scheduled=true

%fused_computation.9 (param_0.68: bf16[4,64,128], param_1.83: s32[]) -> bf16[64,128] {
  %param_0.68 = bf16[4,64,128]{2,1,0:T(8,128)(2,1)S(1)} parameter(0)
  %param_1.83 = s32[]{:T(128)} parameter(1)
  %constant.92 = s32[]{:T(128)} constant(0)
  %dynamic_slice.24 = bf16[1,64,128]{2,1,0} dynamic-slice(%param_0.68, %param_1.83, %constant.92, %constant.92), dynamic_slice_sizes={1,64,128}, metadata={op_name="jit(multi)/while/body/dynamic_slice" stack_frame_id=2}
  ROOT %bitcast.37 = bf16[64,128]{1,0} bitcast(%dynamic_slice.24), metadata={op_name="jit(multi)/while/body/squeeze" stack_frame_id=2}
}

%fused_computation.11 (param_0.69: f32[128,128], param_1.84: bf16[4,64,128], param_2.72: s32[], param_3.41: f32[128]) -> f32[64,128] {
  %param_1.84 = bf16[4,64,128]{2,1,0:T(8,128)(2,1)S(1)} parameter(1)
  %param_2.72 = s32[]{:T(128)} parameter(2)
  %fusion.41 = bf16[64,128]{1,0:T(8,128)(2,1)} fusion(%param_1.84, %param_2.72), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(multi)/while/body/squeeze" stack_frame_id=2}
  %param_0.69 = f32[128,128]{1,0:T(8,128)S(1)} parameter(0)
  %convolution.25 = f32[64,128]{1,0:T(8,128)} convolution(%fusion.41, %param_0.69), dim_labels=bf_io->bf, metadata={op_name="jit(multi)/while/body/closed_call/jvp(L0_dense)/dot_general" stack_frame_id=7}
  %param_3.41 = f32[128]{0:T(128)S(1)} parameter(3)
  %add.44 = f32[64,128]{1,0:T(8,128)} broadcast(%param_3.41), dimensions={1}, metadata={op_name="jit(multi)/while/body/closed_call/jvp(L0_dense)/add" stack_frame_id=7}
  %add.43 = f32[64,128]{1,0:T(8,128)} add(%convolution.25, %add.44), metadata={op_name="jit(multi)/while/body/closed_call/jvp(L0_dense)/add" stack_frame_id=7}
  ROOT %tanh.13 = f32[64,128]{1,0:T(8,128)S(1)} tanh(%add.43), metadata={op_name="jit(multi)/while/body/closed_call/jvp(L0_dense)/tanh" stack_frame_id=8}
}

%region_1.1 (reduce_sum.3: f32[], reduce_sum.4: f32[]) -> f32[] {
  %reduce_sum.3 = f32[]{:T(128)} parameter(0)
  %reduce_sum.4 = f32[]{:T(128)} parameter(1)
  ROOT %reduce_sum.5 = f32[]{:T(128)} add(%reduce_sum.3, %reduce_sum.4), metadata={op_name="reduce_sum"}
}

%body.7 (arg_tuple.0: (s32[], f32[128,128], f32[128], bf16[4,64,128])) -> (s32[], f32[128,128], f32[128], bf16[4,64,128]) {
  %arg_tuple.0 = (s32[]{:T(128)}, f32[128,128]{1,0:T(8,128)S(1)}, f32[128]{0:T(128)S(1)}, /*index=3*/bf16[4,64,128]{2,1,0:T(8,128)(2,1)S(1)}) parameter(0)
  %get-tuple-element.170 = s32[]{:T(128)} get-tuple-element(%arg_tuple.0), index=0
  %get-tuple-element.171 = f32[128,128]{1,0:T(8,128)S(1)} get-tuple-element(%arg_tuple.0), index=1
  %get-tuple-element.172 = f32[128]{0:T(128)S(1)} get-tuple-element(%arg_tuple.0), index=2
  %get-tuple-element.186 = bf16[4,64,128]{2,1,0:T(8,128)(2,1)S(1)} get-tuple-element(%arg_tuple.0), index=3
  %fusion.51 = f32[64,128]{1,0:T(8,128)S(1)} fusion(%get-tuple-element.171, %get-tuple-element.186, %get-tuple-element.170, %get-tuple-element.172), kind=kOutput, calls=%fused_computation.11, metadata={op_name="jit(multi)/while/body/closed_call/jvp(L0_dense)/tanh" stack_frame_id=7}, backend_config={"flag_configs":[],"window_config":{"kernel_window_bounds":["16","1"]}}
  %reduce.9 = f32[128]{0:T(128)S(1)} reduce(%fusion.51, %get-tuple-element.172), dimensions={0}, to_apply=%region_1.1, metadata={op_name="jit(multi)/while/body/closed_call/transpose(jvp(L0_dense))/reduce_sum" stack_frame_id=7}
  %copy-start.5 = (f32[128]{0:T(128)}, f32[128]{0:T(128)S(1)}, u32[]{:S(2)}) copy-start(%reduce.9)
  %copy-done.5 = f32[128]{0:T(128)} copy-done(%copy-start.5)
  %multiply_subtract_fusion.4 = f32[128]{0:T(128)S(1)} subtract(%get-tuple-element.172, %copy-done.5), metadata={op_name="jit(multi)/while/body/closed_call/update/sub" stack_frame_id=12}
  %add.45 = s32[]{:T(128)} add(%get-tuple-element.170, %get-tuple-element.170), metadata={op_name="jit(multi)/while/body/add" stack_frame_id=2}
  ROOT %tuple.31 = (s32[]{:T(128)}, f32[128,128]{1,0:T(8,128)S(1)}, f32[128]{0:T(128)S(1)}, /*index=3*/bf16[4,64,128]{2,1,0:T(8,128)(2,1)S(1)}) tuple(%add.45, %get-tuple-element.171, %multiply_subtract_fusion.4, %get-tuple-element.186)
}

ENTRY %main.9 (params.1: f32[128,128], xs.1: f32[4,64,128]) -> f32[128,128] {
  %params.1 = f32[128,128]{1,0:T(8,128)} parameter(0), metadata={op_name="params"}
  %xs.1 = f32[4,64,128]{2,1,0:T(8,128)} parameter(1), metadata={op_name="xs"}
  %convert.3 = bf16[4,64,128]{2,1,0:T(8,128)(2,1)} convert(%xs.1), metadata={op_name="jit(multi)/convert_element_type" stack_frame_id=1}
  %copy.29 = f32[128,128]{1,0:T(8,128)S(1)} copy(%params.1)
  %constant.22 = s32[]{:T(128)} constant(0)
  %copy.30 = s32[]{:T(128)} copy(%constant.22)
  ROOT %tuple.33 = (f32[128,128]{1,0:T(8,128)S(1)}, s32[]{:T(128)}) tuple(%copy.29, %copy.30)
}
'''
SCOPES = {"L0_dense", "bn1", "update", "loss"}
STEP = "jit(multi)/while/body/closed_call/"


def test_parse_recorded_module():
    index = opindex.parse(HLO)
    # instructions of a fusion's computation and of a reduction's region
    # run as no op of their own: reached through ``inner`` only
    assert "convolution.25" not in index and "reduce_sum.5" not in index
    fusion = index["fusion.51"]
    assert fusion["opcode"] == "fusion"
    assert fusion["op_name"].endswith("jvp(L0_dense)/tanh")
    assert ("convolution", STEP + "jvp(L0_dense)/dot_general") in fusion["inner"]
    # the nested fusion is flattened into it
    assert ("dynamic-slice", "jit(multi)/while/body/dynamic_slice") in fusion["inner"]
    assert index["reduce.9"]["opcode"] == "reduce"
    assert index["arg_tuple.0"]["opcode"] == "parameter"
    assert set(fusion) == {"opcode", "op_name", "inner"}
    # copies XLA put in carry no metadata: named through their operand,
    # or, failing that, their user
    assert index["copy-done.5"]["via"] == "reduce.9"
    assert index["copy-done.5"]["op_name"] == index["reduce.9"]["op_name"]
    assert index["copy.30"]["op_name"] == "" and "via" not in index["copy.30"]


@pytest.mark.parametrize("name, expected", [
    ("fusion.51", ("forward", "L0_dense", "dot_general")),
    ("reduce.9", ("backward", "L0_dense", "reduce_sum")),
    ("copy-done.5", ("backward", "L0_dense", "reduce_sum")),
    ("multiply_subtract_fusion.4", ("update", "update", "sub")),
    ("add.45", ("input", "", "add")),
    ("convert.3", ("input", "", "convert_element_type")),
    ("copy.29", ("input", "", "params")),
    ("copy.30", ("unplaced", "", "copy")),
])
def test_place_recorded_module(name, expected):
    assert opindex.place(opindex.parse(HLO)[name], SCOPES) == expected


def test_place_missing_instruction_is_unplaced():
    assert opindex.place(None) == ("unplaced", "", "")


def test_place_fusion_by_heaviest_inner_not_by_root():
    """XLA calls it convert_reduce_fusion after its epilogue; it is the
    convolution of a layer's backward."""
    entry = {"opcode": "fusion",
             "op_name": STEP + "transpose(jvp(bn1))/convert_element_type",
             "inner": [
                 ("convert", STEP + "transpose(jvp(bn1))/convert_element_type"),
                 ("reduce", STEP + "transpose(jvp(bn1))/reduce_sum"),
                 ("multiply", STEP + "jvp(bn1)/mul"),
                 ("convolution",
                  STEP + "transpose(jvp(L0_dense))/conv_general_dilated")]}
    assert opindex.place(entry, SCOPES) == (
        "backward", "L0_dense", "conv_general_dilated")
    assert opindex.contains(entry, "convolution")
    assert not opindex.contains(entry, "dot")
    # without the convolution the reduction decides, not the two
    # elementwise ops
    entry["inner"].pop()
    assert opindex.place(entry, SCOPES) == ("backward", "bn1", "reduce_sum")


@pytest.mark.parametrize("op_name, expected", [
    ("jit(step_fn)/transpose(jvp(L0_dense))/dot_general",
     ("backward", "L0_dense", "dot_general")),          # a gradient
    ("jit(step_fn)/jvp(bn1)/reduce_sum",
     ("forward", "bn1", "reduce_sum")),                 # a statistic
    ("jit(step_fn)/update/reduce_sum", ("update", "update", "reduce_sum")),
    ("jit(step_fn)/jvp(loss)/add", ("loss", "loss", "add")),
    ("jit(step_fn)/transpose(jvp(loss))/mul", ("backward", "loss", "mul")),
    # a forward recomputed under remat belongs to the backward
    ("jit(step_fn)/jvp(checkpoint)/rematted_computation/jvp(bn1)/mul",
     ("backward", "bn1", "mul")),
    # a jitted helper of the same name as a layer is no scope
    ("jit(step_fn)/jvp(L0_dense)/jit(bn1)/max", ("forward", "L0_dense", "max")),
])
def test_place_all_reduce_by_purpose(op_name, expected):
    entry = {"opcode": "all-reduce", "op_name": op_name, "inner": []}
    assert opindex.place(entry, SCOPES) == expected


@pytest.mark.parametrize("name", ["res-2a/conv 1", "blk.0:att", "plain_9"])
def test_scope_name_is_word_characters(name):
    clean = opindex.scope_name(name)
    assert re.fullmatch(r"[A-Za-z0-9_]+", clean) and len(clean) == len(name)
    with opindex.scope(name):
        pass
    assert clean in opindex._scopes


# ------------------------------------------------ the nets' step programs
_SKIP = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")


def _check_coverage(index, layers):
    """Every instruction that does something has a phase, and every layer
    with parameters shows under both jvp( and transpose(jvp(."""
    seen = set()
    work = unplaced = 0
    for name, entry in index.items():
        if entry["opcode"] in _SKIP:
            continue
        phase, layer, _ = opindex.place(entry)
        assert phase in opindex.PHASES
        work += 1
        # XLA:CPU adds loop-carried copies and counters between unnamed
        # tuples, with no named neighbour to take a place from
        unplaced += phase == "unplaced"
        seen.add((phase, layer))
    assert unplaced <= 0.05 * work, (unplaced, work)
    for layer in layers:
        assert ("forward", layer) in seen and ("backward", layer) in seen, (
            layer, sorted(seen))
    assert ("update", "update") in seen
    return seen


def _chars(rng, n=4, t=6, vocab=12):
    onehot = np.eye(vocab, dtype=np.float32)[rng.integers(0, vocab, (n, t + 1))]
    return DataSet(onehot[:, :-1], onehot[:, 1:])


def test_multilayer_chunked_step_is_covered():
    rng = np.random.default_rng(0)
    net = zoo.char_rnn(12, 16, 2, seed=1)
    net.fit(ListDataSetIterator([_chars(rng) for _ in range(4)]),
            multi_step=4, device_prefetch=True)
    index = opindex.lookup("jit_multi")
    assert index is not None
    _check_coverage(index, ["layer_0", "layer_1", "layer_2"])
    assert opindex.lookup("jit_multi") is index      # memoised


def _small_graph(seed=3):
    g = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(0.1))
         .graph_builder().add_inputs("img"))
    g.add_layer("stem-conv", Convolution2D(
        n_out=4, kernel=(3, 3), stride=(1, 1), mode="same", has_bias=False,
        activation="identity"), "img")
    g.add_layer("stem/bn", BatchNorm(activation="relu"), "stem-conv")
    g.add_vertex("res add", ElementWiseVertex(op="add"), "stem/bn",
                 "stem-conv")
    g.add_layer("pool", GlobalPooling(pooling="avg"), "res add")
    g.add_layer("fc", Output(n_out=3, loss="mcxent", activation="softmax"),
                "pool")
    conf = (g.set_outputs("fc")
            .set_input_types(InputType.convolutional(8, 8, 2)).build())
    return ComputationGraph(conf).init()


def _images(rng, n=4):
    return DataSet(rng.normal(0, 1, (n, 8, 8, 2)).astype(np.float32),
                   np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)])


def test_graph_step_is_covered():
    rng = np.random.default_rng(1)
    net = _small_graph()
    net.fit_batch(_images(rng))
    index = opindex.lookup("jit_step_fn")
    seen = _check_coverage(index, ["stem_conv", "stem_bn", "fc"])
    # vertices without parameters are scopes too (fused into a
    # neighbour's op they may win no place of their own)
    assert ("forward", "pool") in seen
    assert any("jvp(res_add)/" in name for entry in index.values()
               for _, name in entry["inner"])


def test_one_device_mesh_registers_the_inner_step():
    rng = np.random.default_rng(2)
    net = _small_graph(seed=4).use_mesh(make_mesh({"data": 1}))
    net.fit_batch(_images(rng))
    _check_coverage(opindex.lookup("jit_step_fn"),
                    ["stem_conv", "stem_bn", "fc"])


def test_register_runs_once_per_key_never_per_dispatch(monkeypatch):
    made, lowered = [], []
    program = opindex._Program
    monkeypatch.setattr(opindex, "_Program",
                        lambda *a: made.append(a) or program(*a))
    monkeypatch.setattr(opindex, "parse",
                        lambda text: lowered.append(text) or {})
    conf = (NeuralNetConfiguration.builder().seed(5).updater(Sgd(0.1)).list()
            .layer(Dense(n_in=6, n_out=5, activation="relu"))
            .layer(Output(n_out=3, loss="mcxent", activation="softmax"))
            .build())
    from deeplearning4j_tpu import MultiLayerNetwork
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(3)

    def batch(n):
        return DataSet(rng.normal(0, 1, (n, 6)).astype(np.float32),
                       np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)])

    for _ in range(20):
        net.fit_batch(batch(8))
    assert len(made) == 1
    net.fit_batch(batch(4))             # new shapes: a new key
    net.fit_batch(batch(4))
    assert len(made) == 2
    assert not lowered                  # nothing before lookup is called
    assert opindex.lookup("jit_step_fn") == {} and len(lowered) == 1
    opindex.lookup("jit_step_fn")
    assert len(lowered) == 1


def test_lookup_of_unknown_or_collected_program_is_none():
    import gc

    import jax
    import jax.numpy as jnp
    assert opindex.lookup("jit_no_such_program") is None

    def short_lived(x):
        return x + 1

    jitted = jax.jit(short_lived)
    x = jnp.ones((3,))
    opindex.register(jitted, (x,), (x,))
    assert opindex.lookup("jit_short_lived") is not None
    key = id(jitted)
    del jitted
    gc.collect()
    assert opindex.lookup("jit_short_lived") is None
    assert key not in opindex._seen
