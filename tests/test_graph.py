"""ComputationGraph tests: DAG building/validation, the full vertex algebra,
gradient checks through branches and merges
(GradientCheckTestsComputationGraph analogue), multi-input/multi-output
training, ResNet-style residual blocks, JSON + checkpoint round-trip."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu import zoo

from deeplearning4j_tpu.datasets import DataSet
from deeplearning4j_tpu.datasets.dataset import MultiDataSet
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.core import DtypePolicy
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import Dense, Output
from deeplearning4j_tpu.nn.conf.layers_conv import (
    BatchNorm, Convolution2D, GlobalPooling, Subsampling)
from deeplearning4j_tpu.nn.conf.layers_recurrent import GravesLSTM, RnnOutput
from deeplearning4j_tpu.nn.conf.graph_conf import ComputationGraphConfiguration
from deeplearning4j_tpu.nn.conf.vertices import (
    DuplicateToTimeSeriesVertex,
    ElementWiseVertex,
    L2NormalizeVertex,
    L2Vertex,
    LastTimeStepVertex,
    MergeVertex,
    ScaleVertex,
    StackVertex,
    SubsetVertex,
    UnstackVertex,
)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.updater import Adam, Sgd
from deeplearning4j_tpu.utils.gradient_check import gradient_check_fn
from deeplearning4j_tpu.zoo.models import _bottleneck

F64 = DtypePolicy(param_dtype="float64", compute_dtype="float64")


def graph_grad_check(net, mds, sample_per_leaf=30):
    inputs, fmasks = net._prepare_inputs(mds.features, mds.features_masks)
    labels = [jnp.asarray(l) for l in mds.labels]
    lmasks = [None if m is None else jnp.asarray(m) for m in mds.labels_masks]
    if all(m is None for m in lmasks):
        lmasks = None

    def loss_fn(params):
        loss, _ = net._loss(params, net.state, inputs, labels, fmasks,
                            lmasks, rng=None, train=True)
        return loss

    return gradient_check_fn(loss_fn, net.params, min_abs_error=1e-9,
                             sample_per_leaf=sample_per_leaf)


def ff_ds(n=8, dim=5, classes=3, seed=0):
    rng = np.random.default_rng(seed)
    return DataSet(rng.normal(size=(n, dim)),
                   np.eye(classes)[rng.integers(0, classes, n)])


def builder():
    return (NeuralNetConfiguration.builder()
            .seed(42).updater(Sgd(0.1)).dtype(F64).graph_builder())


# ------------------------------------------------------------- construction
def test_cycle_detection():
    with pytest.raises(ValueError, match="cycle"):
        (builder()
         .add_inputs("in")
         .add_layer("a", Dense(n_in=4, n_out=4), "b")
         .add_layer("b", Dense(n_in=4, n_out=4), "a")
         .set_outputs("b")
         .build())


def test_unknown_input_rejected():
    with pytest.raises(ValueError, match="unknown input"):
        (builder()
         .add_inputs("in")
         .add_layer("a", Dense(n_in=4, n_out=4), "nope")
         .set_outputs("a")
         .build())


def test_simple_chain_equals_multilayer_semantics():
    conf = (builder()
            .add_inputs("in")
            .add_layer("d1", Dense(n_out=6, activation="tanh"), "in")
            .add_layer("out", Output(n_out=3, activation="softmax",
                                     loss="mcxent"), "d1")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(5))
            .build())
    net = ComputationGraph(conf).init()
    ds = ff_ds()
    out = np.asarray(net.output(ds.features))
    assert out.shape == (8, 3)
    np.testing.assert_allclose(out.sum(axis=1), 1.0, rtol=1e-6)
    s0 = net.score(ds)
    for _ in range(20):
        net.fit_batch(ds)
    assert net.score(ds) < s0


# ------------------------------------------------------------ vertex algebra
def test_vertex_forward_semantics():
    rng = np.random.default_rng(0)
    a = rng.normal(size=(4, 3))
    b = rng.normal(size=(4, 3))
    assert np.allclose(MergeVertex().forward(a, b),
                       np.concatenate([a, b], axis=1))
    assert np.allclose(ElementWiseVertex(op="add").forward(a, b), a + b)
    assert np.allclose(ElementWiseVertex(op="subtract").forward(a, b), a - b)
    assert np.allclose(ElementWiseVertex(op="product").forward(a, b), a * b)
    assert np.allclose(ElementWiseVertex(op="average").forward(a, b),
                       (a + b) / 2)
    assert np.allclose(ElementWiseVertex(op="max").forward(a, b),
                       np.maximum(a, b))
    assert np.allclose(ScaleVertex(factor=2.5).forward(a), 2.5 * a)
    n = np.asarray(L2NormalizeVertex().forward(a))
    np.testing.assert_allclose(np.linalg.norm(n, axis=1), 1.0, rtol=1e-6)
    d = np.asarray(L2Vertex().forward(a, b))
    assert d.shape == (4, 1)
    np.testing.assert_allclose(d[:, 0], np.linalg.norm(a - b, axis=1),
                               rtol=1e-4)
    s = np.asarray(StackVertex().forward(a, b))
    assert s.shape == (8, 3)
    u = np.asarray(UnstackVertex(index=1, stack_size=2).forward(s))
    np.testing.assert_allclose(u, b)
    sub = np.asarray(SubsetVertex(from_index=1, to_index=2).forward(a))
    np.testing.assert_allclose(sub, a[:, 1:3])


def test_last_time_step_vertex_masked():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(3, 5, 2))
    mask = np.array([[1, 1, 1, 0, 0], [1, 1, 1, 1, 1], [1, 0, 0, 0, 0]],
                    dtype=float)
    out = np.asarray(LastTimeStepVertex().forward(
        jnp.asarray(x), masks=[jnp.asarray(mask)]))
    np.testing.assert_allclose(out[0], x[0, 2])
    np.testing.assert_allclose(out[1], x[1, 4])
    np.testing.assert_allclose(out[2], x[2, 0])


def test_duplicate_to_time_series_vertex():
    v = np.ones((2, 3))
    seq = np.zeros((2, 7, 5))
    out = np.asarray(DuplicateToTimeSeriesVertex().forward(
        jnp.asarray(v), jnp.asarray(seq)))
    assert out.shape == (2, 7, 3)


# ------------------------------------------------------------- grad checks
def test_branch_merge_gradients():
    conf = (builder()
            .add_inputs("in")
            .add_layer("a", Dense(n_out=4, activation="tanh"), "in")
            .add_layer("b", Dense(n_out=3, activation="sigmoid"), "in")
            .add_vertex("m", MergeVertex(), "a", "b")
            .add_layer("out", Output(n_out=3, activation="softmax",
                                     loss="mcxent"), "m")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(5))
            .build())
    net = ComputationGraph(conf).init()
    res = graph_grad_check(net, MultiDataSet.from_dataset(ff_ds()))
    assert res.passed, res.failures[:5]


def test_residual_elementwise_gradients():
    conf = (builder()
            .add_inputs("in")
            .add_layer("a", Dense(n_out=5, activation="tanh"), "in")
            .add_vertex("res", ElementWiseVertex(op="add"), "a", "in")
            .add_layer("out", Output(n_out=3, activation="softmax",
                                     loss="mcxent"), "res")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(5))
            .build())
    net = ComputationGraph(conf).init()
    res = graph_grad_check(net, MultiDataSet.from_dataset(ff_ds()))
    assert res.passed, res.failures[:5]


def test_multi_input_multi_output_gradients():
    conf = (builder()
            .add_inputs("in1", "in2")
            .add_layer("d1", Dense(n_out=4, activation="tanh"), "in1")
            .add_layer("d2", Dense(n_out=4, activation="tanh"), "in2")
            .add_vertex("m", MergeVertex(), "d1", "d2")
            .add_layer("shared", Dense(n_out=6, activation="tanh"), "m")
            .add_layer("out1", Output(n_out=3, activation="softmax",
                                      loss="mcxent"), "shared")
            .add_layer("out2", Output(n_out=2, activation="identity",
                                      loss="mse"), "shared")
            .set_outputs("out1", "out2")
            .set_input_types(InputType.feed_forward(5),
                             InputType.feed_forward(4))
            .build())
    net = ComputationGraph(conf).init()
    rng = np.random.default_rng(0)
    mds = MultiDataSet(
        [rng.normal(size=(8, 5)), rng.normal(size=(8, 4))],
        [np.eye(3)[rng.integers(0, 3, 8)], rng.normal(size=(8, 2))])
    res = graph_grad_check(net, mds)
    assert res.passed, res.failures[:5]
    # training runs + learns
    s0 = net.score(mds)
    for _ in range(30):
        net.fit_batch(mds)
    assert net.score(mds) < s0


def test_seq2vec_attention_free_encoder_decoder_gradients():
    """LastTimeStepVertex + DuplicateToTimeSeriesVertex round-trip
    (the reference's rnn vertex pair)."""
    conf = (builder()
            .add_inputs("seq")
            .add_layer("enc", GravesLSTM(n_out=4, activation="tanh"), "seq")
            .add_vertex("last", LastTimeStepVertex(mask_input="seq"), "enc")
            .add_vertex("dup", DuplicateToTimeSeriesVertex(seq_input="seq"),
                        "last", "seq")
            .add_layer("dec", GravesLSTM(n_out=4, activation="tanh"), "dup")
            .add_layer("out", RnnOutput(n_out=3, activation="softmax",
                                        loss="mcxent"), "dec")
            .set_outputs("out")
            .set_input_types(InputType.recurrent(2, 5))
            .build())
    net = ComputationGraph(conf).init()
    rng = np.random.default_rng(0)
    mds = MultiDataSet([rng.normal(size=(4, 5, 2))],
                       [np.eye(3)[rng.integers(0, 3, (4, 5))]])
    res = graph_grad_check(net, mds, sample_per_leaf=20)
    assert res.passed, res.failures[:5]


def test_resnet_block_cnn():
    """Conv -> BN -> residual add -> pool -> dense: the ResNet building
    block (baseline #2 capability path), gradient-checked."""
    conf = (builder()
            .add_inputs("img")
            .add_layer("c1", Convolution2D(n_out=4, kernel=(3, 3),
                                           mode="same", activation="relu"),
                       "img")
            .add_layer("c2", Convolution2D(n_out=4, kernel=(3, 3),
                                           mode="same", activation="identity"),
                       "c1")
            .add_layer("bn", BatchNorm(), "c2")
            .add_vertex("res", ElementWiseVertex(op="add"), "bn", "c1")
            .add_layer("gp", GlobalPooling(pooling="avg"), "res")
            .add_layer("out", Output(n_out=3, activation="softmax",
                                     loss="mcxent"), "gp")
            .set_outputs("out")
            .set_input_types(InputType.convolutional(8, 8, 2))
            .build())
    net = ComputationGraph(conf).init()
    rng = np.random.default_rng(0)
    mds = MultiDataSet([rng.normal(size=(4, 8, 8, 2))],
                       [np.eye(3)[rng.integers(0, 3, 4)]])
    res = graph_grad_check(net, mds, sample_per_leaf=20)
    assert res.passed, res.failures[:5]


# ------------------------------------------------------------ serialization
def test_graph_json_round_trip():
    conf = (builder()
            .add_inputs("in")
            .add_layer("a", Dense(n_in=5, n_out=4, activation="tanh"), "in")
            .add_vertex("s", ScaleVertex(factor=0.5), "a")
            .add_layer("out", Output(n_in=4, n_out=3, activation="softmax",
                                     loss="mcxent"), "s")
            .set_outputs("out")
            .build())
    restored = ComputationGraphConfiguration.from_json(conf.to_json())
    assert restored.topological_order() == conf.topological_order()
    assert restored.vertices["s"].factor == 0.5
    assert restored.vertices["a"].n_out == 4
    assert restored.network_outputs == ("out",)


def test_graph_checkpoint_round_trip(tmp_path):
    from deeplearning4j_tpu.utils.serialization import (
        restore_computation_graph, write_computation_graph)

    conf = (builder()
            .add_inputs("in")
            .add_layer("a", Dense(n_out=4, activation="tanh"), "in")
            .add_layer("out", Output(n_out=3, activation="softmax",
                                     loss="mcxent"), "a")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(5))
            .build())
    net = ComputationGraph(conf).init()
    ds = ff_ds()
    for _ in range(3):
        net.fit_batch(ds)
    path = str(tmp_path / "graph.zip")
    write_computation_graph(net, path)
    restored = restore_computation_graph(path)
    np.testing.assert_allclose(np.asarray(net.output(ds.features)),
                               np.asarray(restored.output(ds.features)),
                               rtol=1e-6)
    assert restored.iteration == net.iteration


def test_graph_mesh_training():
    """Data-parallel graph training over an 8-device CPU mesh."""
    from deeplearning4j_tpu.parallel.mesh import make_mesh

    conf = (builder()
            .add_inputs("in")
            .add_layer("a", Dense(n_out=8, activation="relu"), "in")
            .add_layer("out", Output(n_out=3, activation="softmax",
                                     loss="mcxent"), "a")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(5))
            .build())
    net = ComputationGraph(conf).init()
    net.use_mesh(make_mesh({"data": 8}))
    ds = ff_ds(n=20)  # not divisible by 8 -> exercises pad+mask path
    s0 = net.score(ds)
    for _ in range(30):
        net.fit_batch(ds)
    assert net.score(ds) < s0


# ---------------------------------------------------------------- CG parity
def _rnn_graph(tbptt=None, f=4, classes=2, hidden=8, seed=42):
    b = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(5e-3))
         .dtype(F64).graph_builder().add_inputs("seq"))
    if tbptt:
        b = b.backprop_type("tbptt", tbptt, tbptt)
    conf = (b.add_layer("lstm", GravesLSTM(n_out=hidden, activation="tanh"),
                        "seq")
            .add_layer("out", RnnOutput(n_out=classes, activation="softmax",
                                        loss="mcxent"), "lstm")
            .set_outputs("out")
            .set_input_types(InputType.recurrent(f))
            .build())
    return ComputationGraph(conf).init()


def test_graph_tbptt_training_runs_and_learns():
    """CG tBPTT chunks the time axis and carries LSTM state across chunks
    (ComputationGraphConfiguration tBPTT parity — round-2 gap at
    graph.py:341)."""
    rng = np.random.default_rng(0)
    n, t, f, classes = 32, 12, 4, 2
    # the label depends on the FIRST chunk: state must carry across chunks
    x = rng.normal(size=(n, t, f))
    y_idx = (x[:, :4, :].mean(axis=(1, 2)) > 0).astype(int)
    y = np.eye(classes)[np.repeat(y_idx[:, None], t, axis=1)]
    net = _rnn_graph(tbptt=4, f=f, classes=classes)
    ds = MultiDataSet([x], [y])
    for _ in range(60):
        net.fit_batch(ds)
    for sub in net.state.values():
        assert "h" not in sub and "c" not in sub
    assert float(net.score(ds)) < 0.55


def test_graph_tbptt_matches_standard_when_single_chunk():
    """With t <= tbptt_fwd_length the chunked path must be identical to a
    standard full-sequence step (same params after one batch)."""
    rng = np.random.default_rng(3)
    x = rng.normal(size=(8, 4, 4))
    y = np.eye(2)[rng.integers(0, 2, (8, 4))]
    ds = MultiDataSet([x], [y])
    a = _rnn_graph(tbptt=8, seed=9)
    b = _rnn_graph(tbptt=None, seed=9)
    a.fit_batch(ds)
    b.fit_batch(ds)
    for name in a.params:
        for k in a.params[name]:
            np.testing.assert_allclose(a.params[name][k], b.params[name][k],
                                       rtol=1e-12, atol=1e-12)


def test_graph_rnn_time_step_streaming_matches_full():
    """CG streaming decode: chunked rnn_time_step == full-sequence output
    (the ComputationGraph.rnnTimeStep parity gap from round 2)."""
    rng = np.random.default_rng(1)
    x = rng.normal(size=(3, 6, 4))
    net = _rnn_graph()
    full = np.asarray(net.output(x))
    net.rnn_clear_previous_state()
    a = np.asarray(net.rnn_time_step(x[:, :2, :]))
    b = np.asarray(net.rnn_time_step(x[:, 2:, :]))
    np.testing.assert_allclose(full, np.concatenate([a, b], axis=1),
                               rtol=1e-8, atol=1e-10)
    # single-step [b, f] form returns [b, out]
    net.rnn_clear_previous_state()
    s = np.asarray(net.rnn_time_step(x[:, 0, :]))
    np.testing.assert_allclose(s, full[:, 0, :], rtol=1e-8, atol=1e-10)


def test_graph_pretrain_autoencoder_vertex():
    """CG layer-wise pretraining (pretrainLayer(String, iter) parity):
    the AE vertex trains on its featurized input and reconstruction
    improves."""
    from deeplearning4j_tpu.nn.conf.layers_pretrain import AutoEncoder
    rng = np.random.default_rng(5)
    x = rng.normal(size=(64, 6)).astype(np.float64)
    conf = (NeuralNetConfiguration.builder().seed(2).updater(Adam(1e-2))
            .dtype(F64).graph_builder().add_inputs("in")
            .add_layer("ae", AutoEncoder(n_out=4, activation="tanh"), "in")
            .add_layer("out", Output(n_out=2, activation="softmax",
                                     loss="mcxent"), "ae")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(6))
            .build())
    net = ComputationGraph(conf).init()
    y = np.eye(2)[rng.integers(0, 2, 64)]
    mds = MultiDataSet([x], [y])
    net.pretrain(mds, epochs=1)
    first = float(net.score_value)
    net.pretrain(mds, epochs=30)
    assert float(net.score_value) < first


def test_graph_evaluate_regression():
    rng = np.random.default_rng(8)
    x = rng.normal(size=(40, 3))
    W = rng.normal(size=(3, 2))
    y = x @ W + 0.01 * rng.normal(size=(40, 2))
    conf = (NeuralNetConfiguration.builder().seed(4).updater(Adam(5e-2))
            .dtype(F64).graph_builder().add_inputs("in")
            .add_layer("out", Output(n_out=2, activation="identity",
                                     loss="mse"), "in")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(3))
            .build())
    net = ComputationGraph(conf).init()
    mds = MultiDataSet([x], [y])
    for _ in range(200):
        net.fit_batch(mds)
    ev = net.evaluate_regression(mds)
    assert ev.average_mean_squared_error() < 0.01


def test_graph_rnn_time_step_multi_input_static_plus_sequence():
    """Single-step streaming with a STATIC 2d first input (review finding:
    single-step mode must be decided per input, not from features[0])."""
    rng = np.random.default_rng(2)
    conf = (NeuralNetConfiguration.builder().seed(6).updater(Adam(1e-2))
            .dtype(F64).graph_builder().add_inputs("static", "seq")
            .add_vertex("dup", DuplicateToTimeSeriesVertex(seq_input="seq"),
                        "static")
            .add_layer("lstm", GravesLSTM(n_out=5, activation="tanh"), "seq")
            .add_vertex("cat", MergeVertex(), "lstm", "dup")
            .add_layer("out", RnnOutput(n_out=2, activation="softmax",
                                        loss="mcxent"), "cat")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(3), InputType.recurrent(4))
            .build())
    net = ComputationGraph(conf).init()
    static = rng.normal(size=(2, 3))
    seq = rng.normal(size=(2, 6, 4))
    full = np.asarray(net.output(static, seq))
    net.rnn_clear_previous_state()
    steps = [np.asarray(net.rnn_time_step(static, seq[:, i, :]))
             for i in range(6)]
    np.testing.assert_allclose(full, np.stack(steps, axis=1),
                               rtol=1e-8, atol=1e-10)


def test_selective_remat_exact_in_f32(monkeypatch):
    """DL4J_TPU_REMAT drops tagged stage activations from the residual set
    (jax.checkpoint save_anything_except_these_names); the recompute must
    be mathematically invisible — identical score and post-step params in
    f32 (PERF.md round 5: the large-batch memory lever)."""
    def build():
        conf = (NeuralNetConfiguration.builder()
                .seed(11).updater(Sgd(0.05))
                .dtype(DtypePolicy(param_dtype="float32",
                                   compute_dtype="float32"))
                .graph_builder()
                .add_inputs("img")
                .add_layer("s0b0_conv", Convolution2D(
                    n_out=4, kernel=(3, 3), mode="same",
                    activation="identity"), "img")
                .add_layer("s0b0_bn", BatchNorm(activation="identity"),
                           "s0b0_conv")
                .add_vertex("s0b0_add", ElementWiseVertex(op="add"),
                            "s0b0_bn", "img")
                .add_layer("gp", GlobalPooling(pooling="avg"), "s0b0_add")
                .add_layer("out", Output(n_out=3, activation="softmax",
                                         loss="mcxent"), "gp")
                .set_outputs("out")
                .set_input_types(InputType.convolutional(8, 8, 4))
                .build())
        return ComputationGraph(conf).init()

    rng = np.random.default_rng(5)
    mds = MultiDataSet(
        [rng.normal(size=(4, 8, 8, 4)).astype(np.float32)],
        [np.eye(3, dtype=np.float32)[rng.integers(0, 3, 4)]])

    monkeypatch.delenv("DL4J_TPU_REMAT", raising=False)
    base = build()
    s0 = float(base.fit_batch(mds))

    monkeypatch.setenv("DL4J_TPU_REMAT", "s0b")
    rem = build()
    s1 = float(rem.fit_batch(mds))

    assert s0 == s1
    for ln in base.params:
        for pn in base.params[ln]:
            np.testing.assert_array_equal(
                np.asarray(base.params[ln][pn]),
                np.asarray(rem.params[ln][pn]), err_msg=f"{ln}.{pn}")


# ------------------------------------ one ResNet-50 unit against plain jnp
def _bottleneck_reference(params, state, x, *, stride, project, train):
    """``zoo`` bottleneck unit (1x1 -> 3x3 -> 1x1 x4, batch norm after each
    convolution, identity or projected shortcut) in float32 jnp; batch
    statistics in training (biased variance), running ones otherwise.
    Returns the unit's output and the running statistics a step leaves."""
    new_state = {}

    def conv_bn(name, h, s):
        z = lax.conv_general_dilated(
            h, params[f"{name}_conv"]["W"], (s, s), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        p, run = params[f"{name}_bn"], state[f"{name}_bn"]
        if train:
            mean = z.mean((0, 1, 2))
            var = ((z - mean) ** 2).mean((0, 1, 2))
            new_state[f"{name}_bn"] = {
                "mean": 0.9 * run["mean"] + 0.1 * mean,
                "var": 0.9 * run["var"] + 0.1 * var}
        else:
            mean, var = run["mean"], run["var"]
        return (z - mean) / jnp.sqrt(var + 1e-5) * p["gamma"] + p["beta"]

    a = jax.nn.relu(conv_bn("u_a", x, stride))
    b = jax.nn.relu(conv_bn("u_b", a, 1))
    c = conv_bn("u_c", b, 1)
    shortcut = conv_bn("u_proj", x, stride) if project else x
    return jax.nn.relu(c + shortcut), new_state


# (values, gradients). Values: largest error over largest reference entry;
# bf16 rounds activations to 8 significant bits through three convolutions
# and batch norms (at most 1.5% here). Gradients: norm of the error over
# the norm of the reference gradient, every leaf of the unit and dx in one
# vector; bf16's backward rounds each cotangent through three batch norms
# (1 to 16% over seeds and sizes on the CPU)
UNIT_TOL = {"float32": (1e-4, 1e-4), "bfloat16": (4e-2, 0.25)}


@pytest.mark.parametrize("compute", sorted(UNIT_TOL))
@pytest.mark.parametrize("project", [False, True],
                         ids=["identity", "projection_s2"])
def test_bottleneck_unit_matches_composed_reference(project, compute):
    """One ``zoo`` bottleneck unit on the graph's walk: the train-mode
    output and gradients, batch norm's running statistics after one
    ``fit`` step, and the eval-mode output, against plain jnp."""
    stride, c_in, h = (2, 8, 8) if project else (1, 16, 6)
    policy = {"float32": zoo.F32, "bfloat16": zoo.BF16}[compute]
    g = (NeuralNetConfiguration.builder().seed(7).updater(Sgd(0.1))
         .dtype(policy).graph_builder().add_inputs("img"))
    out = _bottleneck(g, "u", "img", 4, stride, project)
    g.add_layer("pool", GlobalPooling(pooling="avg"), out)
    g.add_layer("fc", Output(n_out=3, activation="softmax", loss="mcxent"),
                "pool")
    net = ComputationGraph(
        g.set_outputs("fc")
        .set_input_types(InputType.convolutional(h, h, c_in)).build()).init()

    rng = np.random.default_rng(11 + project)

    def draw(shape, lo=None):
        v = rng.normal(size=shape) if lo is None else rng.uniform(
            lo, 2.0, shape)
        return jnp.asarray(v, jnp.float32)

    # batch norm away from its identity start: gamma, beta and the running
    # statistics (the centre of the variance's single pass) all drawn
    for name in net.state:
        f = net.state[name]["mean"].shape
        net.params[name] = {"gamma": draw(f, 0.5), "beta": draw(f)}
        net.state[name] = {"mean": draw(f), "var": draw(f, 0.5)}
    x = draw((4, h, h, c_in))
    tol_value, tol_grad = UNIT_TOL[compute]

    def close(a, b, what):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        err = np.abs(a - b).max()
        assert err <= tol_value * np.abs(b).max(), (what, err,
                                                    np.abs(b).max())

    def system(params, x):
        acts, _, _, new_state = net._walk(params, net.state, {"img": x},
                                          train=True, rng=None)
        return acts[out], new_state

    def reference(params, x):
        return _bottleneck_reference(params, net.state, x, stride=stride,
                                     project=project, train=True)

    y, _ = system(net.params, x)
    want, want_state = reference(net.params, x)
    close(y, want, "train output")
    cot = draw(want.shape)

    def grads(fn):
        gp, gx = jax.grad(lambda p, x: jnp.sum(
            fn(p, x)[0].astype(jnp.float32) * cot), argnums=(0, 1))(
                net.params, x)
        unit = {n: gp[n] for n in net.params if n.startswith("u_")}
        return np.concatenate([np.ravel(np.asarray(v, np.float64)) for v in
                               jax.tree_util.tree_leaves((unit, gx))])

    got_g, want_g = grads(system), grads(reference)
    err = np.linalg.norm(got_g - want_g) / np.linalg.norm(want_g)
    assert err <= tol_grad, err

    net.fit_batch(DataSet(np.asarray(x), np.eye(3, dtype=np.float32)[
        rng.integers(0, 3, 4)]))
    for name in want_state:
        for k in ("mean", "var"):
            close(net.state[name][k], want_state[name][k], f"{name}.{k}")

    got = net.feed_forward(np.asarray(x))[out]
    want, _ = _bottleneck_reference(net.params, net.state, x, stride=stride,
                                    project=project, train=False)
    close(got, want, "eval output")
