"""Distributed-training semantics tests on the virtual 8-device CPU mesh.

Mirrors the reference's pinned distributed semantics (SURVEY.md §4):
TestCompareParameterAveragingSparkVsSingleMachine — with fixed seeds and
averaging_frequency=1, distributed training must match single-machine
training; plus sharded-step equivalence (the performance path)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.datasets import ArrayDataSetIterator, DataSet
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import Dense, Output
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updater import Sgd
from deeplearning4j_tpu.parallel import ParallelWrapper, make_mesh
from tests.test_multilayer import build_mlp, make_blobs


def test_mesh_has_8_devices():
    assert len(jax.devices()) == 8


def test_sharded_step_matches_single_device():
    """The data-parallel sharded train step must produce the same params as
    the single-device step on identical batches (modulo float reduction
    order)."""
    x, y = make_blobs(n=256, seed=3)
    net_single = MultiLayerNetwork(build_mlp(updater=Sgd(0.1))).init()
    net_sharded = MultiLayerNetwork(build_mlp(updater=Sgd(0.1))).init()
    mesh = make_mesh({"data": 8})
    net_sharded.use_mesh(mesh)

    it1 = ArrayDataSetIterator(x, y, batch_size=64)
    it2 = ArrayDataSetIterator(x, y, batch_size=64)
    net_single.fit(it1, epochs=3, async_prefetch=False)
    net_sharded.fit(it2, epochs=3, async_prefetch=False)

    w1 = np.asarray(net_single.params["layer_0"]["W"])
    w2 = np.asarray(net_sharded.params["layer_0"]["W"])
    np.testing.assert_allclose(w1, w2, rtol=2e-4, atol=1e-5)


def test_parameter_averaging_freq1_equals_larger_batch():
    """averagingFrequency=1 with N workers on batch b == single training on
    batch N*b (the reference's pinned Spark-vs-single-machine semantics),
    exactly, given SGD and identical data order."""
    x, y = make_blobs(n=128, seed=5)
    workers = 4
    small_b, big_b = 16, 64

    net_pw = MultiLayerNetwork(build_mlp(updater=Sgd(0.1))).init()
    wrapper = ParallelWrapper(net_pw, workers=workers, averaging_frequency=1)
    wrapper.fit(ArrayDataSetIterator(x, y, batch_size=small_b), epochs=2)

    net_big = MultiLayerNetwork(build_mlp(updater=Sgd(0.1))).init()
    net_big.fit(ArrayDataSetIterator(x, y, batch_size=big_b), epochs=2,
                async_prefetch=False)

    w1 = np.asarray(net_pw.params["layer_0"]["W"])
    w2 = np.asarray(net_big.params["layer_0"]["W"])
    np.testing.assert_allclose(w1, w2, rtol=1e-5, atol=1e-6)


def test_parameter_averaging_converges():
    x, y = make_blobs(n=256, seed=6)
    net = MultiLayerNetwork(build_mlp()).init()
    wrapper = ParallelWrapper(net, workers=2, averaging_frequency=4)
    wrapper.fit(ArrayDataSetIterator(x, y, batch_size=32), epochs=20)
    assert net.evaluate(DataSet(x, y)).accuracy() > 0.9


def test_sharded_inference_matches():
    x, _ = make_blobs(n=64, seed=7)
    net = MultiLayerNetwork(build_mlp()).init()
    out_single = np.asarray(net.output(x))
    mesh = make_mesh({"data": 8})
    net.use_mesh(mesh)
    out_sharded = np.asarray(net.output(x))
    np.testing.assert_allclose(out_single, out_sharded, rtol=1e-5, atol=1e-6)


def build_mlp_graph(updater):
    """``build_mlp`` as a ComputationGraph, layer names and all."""
    conf = (NeuralNetConfiguration.builder().seed(123).updater(updater)
            .weight_init("xavier").graph_builder().add_inputs("in")
            .add_layer("layer_0", Dense(n_out=64, activation="relu"), "in")
            .add_layer("layer_1", Dense(n_out=64, activation="relu"),
                       "layer_0")
            .add_layer("layer_2", Output(n_out=4, activation="softmax",
                                         loss="mcxent"), "layer_1")
            .set_outputs("layer_2")
            .set_input_types(InputType.feed_forward(20)).build())
    return ComputationGraph(conf)


@pytest.mark.parametrize("make_net", [
    lambda: MultiLayerNetwork(build_mlp(updater=Sgd(0.1))),
    lambda: build_mlp_graph(Sgd(0.1)),
], ids=["mln", "graph"])
def test_sharded_step_partial_batch(make_net):
    """Partial final batches (not divisible by mesh size) must train without
    error and match the unsharded result (pad+mask path), whichever net
    arranges the batch."""
    x, y = make_blobs(n=250, seed=11)  # 250 % 64 = 58, 58 % 8 != 0
    net_single = make_net().init()
    net_sharded = make_net().init()
    net_sharded.use_mesh(make_mesh({"data": 8}))
    net_single.fit(ArrayDataSetIterator(x, y, batch_size=64), epochs=2,
                   async_prefetch=False)
    net_sharded.fit(ArrayDataSetIterator(x, y, batch_size=64), epochs=2,
                    async_prefetch=False)
    np.testing.assert_allclose(
        np.asarray(net_single.params["layer_0"]["W"]),
        np.asarray(net_sharded.params["layer_0"]["W"]), rtol=2e-4, atol=1e-5)


def test_parameter_averaging_short_data_not_diluted():
    """A worker that never received a batch must not participate in the
    average (1-batch iterator with 2 workers == plain single-worker step)."""
    x, y = make_blobs(n=16, seed=12)
    net_pw = MultiLayerNetwork(build_mlp(updater=Sgd(0.1))).init()
    ParallelWrapper(net_pw, workers=2, averaging_frequency=1).fit(
        ArrayDataSetIterator(x, y, batch_size=16), epochs=1)
    net_ref = MultiLayerNetwork(build_mlp(updater=Sgd(0.1))).init()
    net_ref.fit(ArrayDataSetIterator(x, y, batch_size=16), epochs=1,
                async_prefetch=False)
    np.testing.assert_allclose(
        np.asarray(net_pw.params["layer_0"]["W"]),
        np.asarray(net_ref.params["layer_0"]["W"]), rtol=1e-6, atol=1e-7)


class TestTensorParallel:
    """dp x tp over a 2-D mesh via GSPMD sharding annotations
    (parallel/tensor.py — model parallelism the reference never had)."""

    def _mesh2d(self):
        import jax
        from jax.sharding import Mesh
        devs = np.array(jax.devices()[:8]).reshape(2, 4)
        return Mesh(devs, ("data", "model"))

    def _mlp(self, seed=5):
        from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf.core import DtypePolicy
        from deeplearning4j_tpu.nn.conf.layers import Dense, Output
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        from deeplearning4j_tpu.nn.updater import Sgd
        conf = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(0.1))
                .dtype(DtypePolicy(param_dtype="float32",
                                   compute_dtype="float32"))
                .list()
                .layer(Dense(n_in=12, n_out=32, activation="tanh"))
                .layer(Dense(n_out=16, activation="tanh"))
                .layer(Output(n_out=3, activation="softmax", loss="mcxent"))
                .build())
        return MultiLayerNetwork(conf).init()

    def test_weights_sharded_on_model_axis(self):
        from jax.sharding import PartitionSpec as P
        mesh = self._mesh2d()
        net = self._mlp().use_mesh(mesh, model_axis="model")
        spec = net.params["layer_0"]["W"].sharding.spec
        assert tuple(spec) == (None, "model")
        # indivisible (out=3) and 1-D leaves replicate
        assert tuple(net.params["layer_2"]["b"].sharding.spec) == ()

    def test_tp_step_matches_single_device(self):
        import jax
        mesh = self._mesh2d()
        rng = np.random.default_rng(3)
        x = rng.normal(size=(16, 12)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 16)]
        ds = DataSet(x, y)

        tp = self._mlp().use_mesh(mesh, model_axis="model")
        s_tp = float(tp.fit_batch(ds))
        single = self._mlp()
        s_single = float(single.fit_batch(ds))
        assert abs(s_tp - s_single) < 1e-5
        for ln in single.params:
            for pn in single.params[ln]:
                np.testing.assert_allclose(
                    np.asarray(jax.device_get(tp.params[ln][pn])),
                    np.asarray(single.params[ln][pn]),
                    rtol=1e-5, atol=1e-6, err_msg=f"{ln}.{pn}")

    def test_tp_rules_override(self):
        from jax.sharding import PartitionSpec as P
        mesh = self._mesh2d()
        net = self._mlp().use_mesh(
            mesh, model_axis="model",
            tp_rules={"['layer_0']['W']": P()})
        assert tuple(net.params["layer_0"]["W"].sharding.spec) == ()
        assert tuple(net.params["layer_1"]["W"].sharding.spec) == (
            None, "model")

    def test_tp_checkpoint_restore_keeps_placement(self, tmp_path):
        import jax
        from deeplearning4j_tpu.utils.checkpoint import (
            restore_multi_layer_network, save_checkpoint)
        mesh = self._mesh2d()
        rng = np.random.default_rng(4)
        x = rng.normal(size=(16, 12)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 16)]
        net = self._mlp().use_mesh(mesh, model_axis="model")
        net.fit_batch(DataSet(x, y))
        save_checkpoint(net, str(tmp_path / "tp_ck"))
        back = restore_multi_layer_network(str(tmp_path / "tp_ck"),
                                           mesh=mesh, model_axis="model")
        spec = tuple(back.params["layer_0"]["W"].sharding.spec)
        assert spec == (None, "model"), spec
        # resumed net trains and matches the original's next step
        s1 = float(net.fit_batch(DataSet(x, y)))
        s2 = float(back.fit_batch(DataSet(x, y)))
        assert abs(s1 - s2) < 1e-5

    def test_tp_rules_override_places_opt_state_consistently(self):
        from jax.sharding import PartitionSpec as P

        from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf.layers import Dense, Output
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        from deeplearning4j_tpu.nn.updater import Nesterovs
        mesh = self._mesh2d()
        conf = (NeuralNetConfiguration.builder().seed(5)
                .updater(Nesterovs(0.1, 0.9)).list()
                .layer(Dense(n_in=12, n_out=32, activation="tanh"))
                .layer(Dense(n_out=16, activation="tanh"))
                .layer(Output(n_out=3, activation="softmax", loss="mcxent"))
                .build())
        net = MultiLayerNetwork(conf).init().use_mesh(
            mesh, model_axis="model",
            tp_rules={"['layer_0']['W']": P()})
        # momentum for the overridden param must also replicate
        m = net.opt_state["layer_0"]["v"]["W"]
        assert tuple(m.sharding.spec) == ()
        m1 = net.opt_state["layer_1"]["v"]["W"]
        assert tuple(m1.sharding.spec) == (None, "model")

    def test_tp_computation_graph_conv_matches_single_device(self):
        """dp x tp on the DAG path: conv channel dims sharded over
        'model', BN batch stats partitioned by GSPMD — one f32 ResNet-18
        step must match the single-device step."""
        import jax
        from deeplearning4j_tpu.datasets.dataset import MultiDataSet
        from deeplearning4j_tpu.zoo import resnet18
        from deeplearning4j_tpu.zoo.models import F32
        mesh = self._mesh2d()
        rng = np.random.default_rng(6)
        x = rng.normal(size=(8, 32, 32, 3)).astype(np.float32)
        y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 8)]
        mds = MultiDataSet([x], [y])
        tp = resnet18(seed=11, dtype=F32).use_mesh(mesh,
                                                   model_axis="model")
        # a conv with 64 output channels shards over the 4-way model axis
        spec = tuple(tp.params["stem_conv"]["W"].sharding.spec)
        assert spec[-1] == "model", spec
        s_tp = float(tp.fit_batch(mds))
        single = resnet18(seed=11, dtype=F32)
        s_one = float(single.fit_batch(mds))
        assert abs(s_tp - s_one) < 1e-4, (s_tp, s_one)
        for ln in single.params:
            for pn in single.params[ln]:
                np.testing.assert_allclose(
                    np.asarray(jax.device_get(tp.params[ln][pn])),
                    np.asarray(single.params[ln][pn]),
                    rtol=1e-4, atol=1e-4, err_msg=f"{ln}.{pn}")
