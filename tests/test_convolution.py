"""CNN stack tests: ConvolutionMode shape semantics, gradient checks per
layer type (CNNGradientCheckTest.java / BNGradientCheckTest.java /
LRNGradientCheckTests.java / GlobalPoolingGradientCheckTests.java analogue),
and a LeNet end-to-end smoke run (MultiLayerTest-style convergence)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from deeplearning4j_tpu.datasets import ArrayDataSetIterator, DataSet
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.core import DtypePolicy
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import Dense, Output
from deeplearning4j_tpu.nn.conf.layers_conv import (
    BatchNorm,
    Convolution1D,
    Convolution2D,
    GlobalPooling,
    LocalResponseNormalization,
    Subsampling,
    Subsampling1D,
    ZeroPadding,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updater import Adam, Sgd
from deeplearning4j_tpu.ops.convolution import out_size
from deeplearning4j_tpu.utils.gradient_check import check_network_gradients

F64 = DtypePolicy(param_dtype="float64", compute_dtype="float64")


def cnn_ds(n=4, h=8, w=8, c=2, classes=3, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, h, w, c))
    y = np.eye(classes)[rng.integers(0, classes, n)]
    return DataSet(x, y)


def cnn_net(*mid_layers, h=8, w=8, c=2, classes=3, seed=42):
    b = (NeuralNetConfiguration.builder()
         .seed(seed).updater(Sgd(0.1)).dtype(F64).list())
    for l in mid_layers:
        b.layer(l)
    b.layer(Output(n_out=classes, activation="softmax", loss="mcxent"))
    b.set_input_type(InputType.convolutional(h, w, c))
    return MultiLayerNetwork(b.build()).init()


# ---------------------------------------------------------------- shape math
def test_out_size_modes():
    # truncate floors partial windows
    assert out_size(10, 3, 2, 0, "truncate") == 4
    # same: ceil(in/stride)
    assert out_size(10, 3, 2, 0, "same") == 5
    assert out_size(28, 5, 1, 0, "same") == 28
    # strict raises on non-exact fit
    with pytest.raises(ValueError):
        out_size(10, 3, 2, 0, "strict")
    assert out_size(9, 3, 2, 0, "strict") == 4
    # dilation enlarges the effective kernel
    assert out_size(10, 3, 1, 0, "truncate", dilation=2) == 6


def test_conv_output_shapes():
    net = cnn_net(
        Convolution2D(n_out=4, kernel=(3, 3), stride=(1, 1), activation="relu"),
        Subsampling(kernel=(2, 2), stride=(2, 2)),
    )
    ds = cnn_ds()
    acts = net.feed_forward(ds.features)
    assert acts[0].shape == (4, 6, 6, 4)   # 8-3+1 = 6
    assert acts[1].shape == (4, 3, 3, 4)   # pooled /2
    assert acts[-1].shape == (4, 3)


def test_same_mode_preserves_hw():
    net = cnn_net(Convolution2D(n_out=4, kernel=(3, 3), mode="same",
                                activation="relu"))
    acts = net.feed_forward(cnn_ds().features)
    assert acts[0].shape == (4, 8, 8, 4)


def test_zero_padding_shape():
    net = cnn_net(ZeroPadding(pad=(1, 2, 3, 4)),
                  Convolution2D(n_out=2, kernel=(3, 3), activation="relu"))
    acts = net.feed_forward(cnn_ds().features)
    assert acts[0].shape == (4, 8 + 3, 8 + 7, 2)


# ------------------------------------------------------------ gradient checks
def test_conv2d_gradients():
    net = cnn_net(Convolution2D(n_out=3, kernel=(3, 3), activation="tanh"))
    res = check_network_gradients(net, cnn_ds(), sample_per_leaf=40)
    assert res.passed, res.failures[:5]


def test_conv2d_same_strided_gradients():
    net = cnn_net(Convolution2D(n_out=3, kernel=(3, 3), stride=(2, 2),
                                mode="same", activation="tanh"))
    res = check_network_gradients(net, cnn_ds(), sample_per_leaf=40)
    assert res.passed, res.failures[:5]


@pytest.mark.parametrize("pooling", ["max", "avg", "pnorm"])
def test_subsampling_gradients(pooling):
    net = cnn_net(
        Convolution2D(n_out=3, kernel=(3, 3), activation="tanh"),
        Subsampling(kernel=(2, 2), stride=(2, 2), pooling=pooling),
    )
    res = check_network_gradients(net, cnn_ds(), sample_per_leaf=40)
    assert res.passed, res.failures[:5]


def test_batchnorm_dense_gradients():
    conf = (NeuralNetConfiguration.builder()
            .seed(42).updater(Sgd(0.1)).dtype(F64).list()
            .layer(Dense(n_in=5, n_out=6, activation="tanh"))
            .layer(BatchNorm())
            .layer(Output(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(5))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    ds = DataSet(rng.normal(size=(8, 5)), np.eye(3)[rng.integers(0, 3, 8)])
    res = check_network_gradients(net, ds, sample_per_leaf=40)
    assert res.passed, res.failures[:5]


def test_batchnorm_cnn_gradients():
    net = cnn_net(
        Convolution2D(n_out=3, kernel=(3, 3), activation="identity"),
        BatchNorm(activation="relu"),
    )
    res = check_network_gradients(net, cnn_ds(), sample_per_leaf=40)
    assert res.passed, res.failures[:5]


def test_lrn_gradients():
    net = cnn_net(
        Convolution2D(n_out=4, kernel=(3, 3), activation="tanh"),
        LocalResponseNormalization(),
    )
    res = check_network_gradients(net, cnn_ds(), sample_per_leaf=40)
    assert res.passed, res.failures[:5]


@pytest.mark.parametrize("pooling", ["max", "avg", "sum", "pnorm"])
def test_global_pooling_cnn_gradients(pooling):
    net = cnn_net(
        Convolution2D(n_out=3, kernel=(3, 3), activation="tanh"),
        GlobalPooling(pooling=pooling),
    )
    res = check_network_gradients(net, cnn_ds(), sample_per_leaf=40)
    assert res.passed, res.failures[:5]


def test_conv1d_gradients():
    conf = (NeuralNetConfiguration.builder()
            .seed(42).updater(Sgd(0.1)).dtype(F64).list()
            .layer(Convolution1D(n_out=4, kernel=3, activation="tanh"))
            .layer(Subsampling1D(kernel=2, stride=2, pooling="max"))
            .layer(GlobalPooling(pooling="avg"))
            .layer(Output(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.recurrent(5, 10))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    ds = DataSet(rng.normal(size=(4, 10, 5)), np.eye(3)[rng.integers(0, 3, 4)])
    res = check_network_gradients(net, ds, sample_per_leaf=40)
    assert res.passed, res.failures[:5]


# ---------------------------------------------------------------- state + e2e
def test_batchnorm_running_stats_update():
    net = cnn_net(Convolution2D(n_out=3, kernel=(3, 3), activation="identity"),
                  BatchNorm(decay=0.5))
    bn_name = net.layers[1].name
    before = np.asarray(net.state[bn_name]["mean"]).copy()
    ds = cnn_ds()
    net.fit_batch(ds)
    after = np.asarray(net.state[bn_name]["mean"])
    assert not np.allclose(before, after)
    # inference uses running stats: two eval calls agree (no batch dependence)
    o1 = np.asarray(net.output(ds.features[:2]))
    o2 = np.asarray(net.output(ds.features[:2]))
    np.testing.assert_allclose(o1, o2)


def test_lenet_learns_synthetic_mnist():
    """LeNet-style net reaches high train accuracy on a separable synthetic
    image problem (the MultiLayerTest MNIST smoke-test analogue)."""
    rng = np.random.default_rng(0)
    n, classes = 256, 4
    templates = rng.normal(0, 1.5, size=(classes, 12, 12, 1))
    idx = rng.integers(0, classes, n)
    x = templates[idx] + rng.normal(0, 0.4, size=(n, 12, 12, 1))
    y = np.eye(classes)[idx]

    conf = (NeuralNetConfiguration.builder()
            .seed(7).updater(Adam(1e-2)).list()
            .layer(Convolution2D(n_out=8, kernel=(3, 3), activation="relu"))
            .layer(Subsampling(kernel=(2, 2), stride=(2, 2)))
            .layer(Convolution2D(n_out=16, kernel=(3, 3), activation="relu"))
            .layer(Subsampling(kernel=(2, 2), stride=(2, 2)))
            .layer(Dense(n_out=32, activation="relu"))
            .layer(Output(n_out=classes, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.convolutional(12, 12, 1))
            .build())
    net = MultiLayerNetwork(conf).init()
    it = ArrayDataSetIterator(x, y, batch_size=64)
    net.fit(it, epochs=6, async_prefetch=False)
    acc = net.evaluate(DataSet(x, y)).accuracy()
    assert acc > 0.9, f"LeNet failed to learn: acc={acc}"


# ------------------------------------------- the layer against lax directly
# (kernel, stride, mode, dilation, h, c_in, c_out): the ResNet-50 stem on 3
# channels, its unpadded 1x1/s2 projection, and the block convolutions
CONV_GEOMETRIES = {
    "stem7x7s2_h28_same": (7, 2, "same", 1, 28, 3, 8),
    "stem7x7s2_h29_same": (7, 2, "same", 1, 29, 3, 8),
    "stem7x7s2_h28_truncate": (7, 2, "truncate", 1, 28, 3, 8),
    "proj1x1s2_h56": (1, 2, "same", 1, 56, 16, 8),
    "proj1x1s2_h57": (1, 2, "same", 1, 57, 16, 8),
    "3x3s1_same": (3, 1, "same", 1, 12, 8, 8),
    "3x3s2_same": (3, 2, "same", 1, 13, 8, 8),
    "1x1s1": (1, 1, "same", 1, 12, 16, 32),
    "3x3_dilation2": (3, 1, "same", 2, 12, 8, 8),
}
# bf16 compute rounds x, W, the output and the cotangents to 8 significant
# bits (at most 0.6% of the largest reference entry here): 2%. The bias's
# gradient is a bf16 sum of thousands of cotangents and is left out
CONV_TOL = {"float32": 1e-5, "bfloat16": 2e-2}


@pytest.mark.parametrize("compute", sorted(CONV_TOL))
@pytest.mark.parametrize("geometry", sorted(CONV_GEOMETRIES))
def test_conv_layer_matches_lax_reference(geometry, compute):
    """``ConvolutionLayer`` forward, dW and dx against a direct float32
    ``lax.conv_general_dilated`` whose padding lax derives itself."""
    k, s, mode, d, h, c_in, c_out = CONV_GEOMETRIES[geometry]
    policy = DtypePolicy(param_dtype="float32", compute_dtype=compute)
    conf = (NeuralNetConfiguration.builder().seed(3).updater(Sgd(0.1))
            .dtype(policy).list()
            .layer(Convolution2D(n_out=c_out, kernel=(k, k), stride=(s, s),
                                 dilation=(d, d), mode=mode,
                                 activation="identity"))
            .layer(Output(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.convolutional(h, h, c_in))
            .build())
    net = MultiLayerNetwork(conf).init()
    layer = net.layers[0]
    rng = np.random.default_rng(k * 100 + h)
    params = {"W": jnp.asarray(rng.normal(size=(k, k, c_in, c_out)),
                               jnp.float32),
              "b": jnp.asarray(rng.normal(size=(c_out,)), jnp.float32)}
    x = jnp.asarray(rng.normal(size=(2, h, h, c_in)), jnp.float32)

    def ref(params, x):
        return lax.conv_general_dilated(
            x, params["W"], (s, s), "SAME" if mode == "same" else "VALID",
            rhs_dilation=(d, d),
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + params["b"]

    def got(params, x):
        y, _ = layer.apply(params, {}, x, train=True)
        return y

    want = ref(params, x)
    y = got(params, x)
    assert y.dtype == jnp.dtype(compute) and y.shape == want.shape
    cot = jnp.asarray(rng.normal(size=want.shape), jnp.float32)
    tol = CONV_TOL[compute]

    def close(a, b, what):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        err = np.abs(a - b).max()
        assert err <= tol * np.abs(b).max(), (what, err, np.abs(b).max())

    close(y, want, "y")
    g_ref = jax.grad(lambda p, x: jnp.sum(ref(p, x) * cot),
                     argnums=(0, 1))(params, x)
    g_got = jax.grad(lambda p, x: jnp.sum(got(p, x).astype(jnp.float32)
                                          * cot), argnums=(0, 1))(params, x)
    close(g_got[0]["W"], g_ref[0]["W"], "dW")
    close(g_got[1], g_ref[1], "dx")
