"""Async training-loop runtime tests: the pipelined fit path (device
prefetch + lazy score sync + chunked scan dispatch) must be BIT-IDENTICAL
to the sequential per-batch loop — same parameters, same optimizer state,
same rng chain — listeners must observe the identical (iteration, score)
stream under chunked replay, and prefetch threads must never outlive
their consumer."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.datasets import ArrayDataSetIterator, DataSet
from deeplearning4j_tpu.datasets.dataset import MultiDataSet
from deeplearning4j_tpu.datasets.iterator import (
    AsyncDataSetIterator,
    DevicePrefetchIterator,
    ListDataSetIterator,
    default_prefetch_depth,
)
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import Dense, Output
from deeplearning4j_tpu.nn.conf.layers_recurrent import GravesLSTM, RnnOutput
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.trainer import Trainer
from deeplearning4j_tpu.nn.updater import Adam
from deeplearning4j_tpu.optimize.listeners import (
    CollectScoresIterationListener,
    PerformanceListener,
    ScoreIterationListener,
    TrainingListener,
)


def make_blobs(n=176, dim=12, classes=3, seed=7):
    rng = np.random.default_rng(seed)
    centers = rng.normal(0, 3.0, (classes, dim))
    idx = rng.integers(0, classes, n)
    x = centers[idx] + rng.normal(0, 1.0, (n, dim))
    return x.astype(np.float32), np.eye(classes)[idx].astype(np.float32)


def build_mlp(dim=12, classes=3, seed=123):
    # dropout makes every step consume the rng chain, so a single split
    # out of order anywhere in the chunked path would show up as a diff
    return (NeuralNetConfiguration.builder()
            .seed(seed).updater(Adam(1e-2)).weight_init("xavier")
            .list()
            .layer(Dense(n_in=dim, n_out=32, activation="relu", dropout=0.5))
            .layer(Output(n_out=classes, activation="softmax", loss="mcxent"))
            .build())


def build_graph(dim=10, classes=3, seed=321):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(Adam(1e-2)).graph_builder()
            .add_inputs("in")
            .add_layer("d1", Dense(n_out=16, activation="tanh", dropout=0.3),
                       "in")
            .add_layer("out", Output(n_out=classes, activation="softmax",
                                     loss="mcxent"), "d1")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(dim))
            .build())
    return ComputationGraph(conf).init()


def assert_trees_bit_identical(a, b, what=""):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb), what
    for x, y in zip(la, lb):
        xa, ya = np.asarray(x), np.asarray(y)
        assert xa.dtype == ya.dtype and xa.shape == ya.shape, what
        assert xa.tobytes() == ya.tobytes(), (
            f"{what}: leaves differ (max abs diff "
            f"{np.max(np.abs(xa.astype(np.float64) - ya.astype(np.float64)))})")


# ------------------------------------------------------------ bit identity
def test_mln_pipelined_fit_bit_identical_to_per_batch_loop():
    """Prefetch + lazy sync + chunked scan vs the plain fit_batch loop:
    params, optimizer state, rng key and score must match bit for bit.
    168 examples / batch 16 = 10 full batches + one short one, so the
    run exercises full chunks, a partial tail chunk AND the shape-change
    regroup between the 16-row and 8-row batches."""
    x, y = make_blobs(n=168)
    seq = MultiLayerNetwork(build_mlp()).init()
    pipe = MultiLayerNetwork(build_mlp()).init()

    seq.fit(ArrayDataSetIterator(x, y, batch_size=16), epochs=2,
            async_prefetch=False, device_prefetch=False, multi_step=1)
    pipe.fit(ArrayDataSetIterator(x, y, batch_size=16), epochs=2,
             async_prefetch=True, device_prefetch=True, multi_step=8)

    assert pipe.iteration == seq.iteration == 22
    assert_trees_bit_identical(seq.params, pipe.params, "params")
    assert_trees_bit_identical(seq.opt_state, pipe.opt_state, "opt_state")
    assert_trees_bit_identical(seq._rng_key, pipe._rng_key, "rng key")
    assert float(seq.score_value) == float(pipe.score_value)


def test_graph_pipelined_fit_bit_identical_to_per_batch_loop():
    x, y = make_blobs(n=112, dim=10)
    batches = [MultiDataSet([x[i:i + 16]], [y[i:i + 16]])
               for i in range(0, 112, 16)]  # 7 batches -> chunks of 4+3
    seq = build_graph()
    pipe = build_graph()

    seq.fit(ListDataSetIterator(batches), epochs=2, async_prefetch=False,
            device_prefetch=False, multi_step=1)
    pipe.fit(ListDataSetIterator(batches), epochs=2, async_prefetch=True,
             device_prefetch=True, multi_step=4)

    assert pipe.iteration == seq.iteration == 14
    assert_trees_bit_identical(seq.params, pipe.params, "params")
    assert_trees_bit_identical(seq.opt_state, pipe.opt_state, "opt_state")
    assert_trees_bit_identical(seq._rng_key, pipe._rng_key, "rng key")
    assert float(seq.score_value) == float(pipe.score_value)


# ------------------------------------------------------- listener contract
def test_chunked_replay_gives_listeners_identical_score_stream():
    """CollectScoresIterationListener under chunked dispatch must record
    exactly the (iteration, score) pairs the per-batch loop produces."""
    x, y = make_blobs(n=160)
    seq = MultiLayerNetwork(build_mlp()).init()
    pipe = MultiLayerNetwork(build_mlp()).init()
    seq_scores = CollectScoresIterationListener()
    pipe_scores = CollectScoresIterationListener()
    seq.set_listeners(seq_scores)
    pipe.set_listeners(pipe_scores)

    seq.fit(ArrayDataSetIterator(x, y, batch_size=16), epochs=1,
            async_prefetch=False, device_prefetch=False, multi_step=1)
    pipe.fit(ArrayDataSetIterator(x, y, batch_size=16), epochs=1,
             multi_step=5)

    assert len(pipe_scores.scores) == 10
    assert pipe_scores.scores == seq_scores.scores


class CallRecorder(TrainingListener):
    """Every listener call ``fit`` makes, in order."""

    needs_per_iteration = False     # fit may keep its chunked path

    def __init__(self):
        self.calls = []

    def on_epoch_start(self, net):
        self.calls.append(("epoch_start", net.epoch))

    def iteration_done(self, net, iteration, epoch):
        self.calls.append(("iteration", iteration, epoch,
                           net.last_batch_examples))

    def on_epoch_end(self, net):
        self.calls.append(("epoch_end", net.epoch))


def build_net(kind):
    return (MultiLayerNetwork(build_mlp(dim=10)).init() if kind == "mln"
            else build_graph())


@pytest.mark.parametrize("mode", ["per_batch", "chunked", "one_dataset"])
@pytest.mark.parametrize("kind", ["mln", "graph"])
def test_fit_gives_both_nets_the_same_listener_call_stream(kind, mode):
    """One fit loop: epoch start, every iteration with its batch's size,
    epoch end, on either net, per batch or chunked, from an iterator or
    from one DataSet (which runs the same loop: no hook is skipped)."""
    x, y = make_blobs(n=56, dim=10)             # 3 batches of 16 + one of 8
    data = (DataSet(x[:16], y[:16]) if mode == "one_dataset"
            else ArrayDataSetIterator(x, y, batch_size=16))
    sizes = [16] if mode == "one_dataset" else [16, 16, 16, 8]
    net = build_net(kind)
    rec = CallRecorder()
    net.set_listeners(rec)
    net.fit(data, epochs=2, device_prefetch=False,
            multi_step=4 if mode == "chunked" else 1)
    expected, it = [], 0
    for epoch in range(2):
        expected.append(("epoch_start", epoch))
        for n in sizes:
            it += 1
            expected.append(("iteration", it, epoch, n))
        expected.append(("epoch_end", epoch))
    assert rec.calls == expected
    assert net.epoch == 2 and net.iteration == it


@pytest.mark.parametrize("kind", ["mln", "graph"])
def test_fit_batch_repeated_counts_its_batch_and_add_listener(kind):
    """``last_batch_examples`` (what PerformanceListener counts) is set
    by the scanned path too, and both nets take ``add_listener``."""
    x, y = make_blobs(n=24, dim=10)
    net = build_net(kind)
    rec = CallRecorder()
    assert net.add_listener(rec) is net and net.listeners == [rec]
    net.fit_batch(DataSet(x[:8], y[:8]))
    net.fit_batch_repeated(DataSet(x, y), 3)
    assert net.last_batch_examples == 24
    assert net.iteration == 4
    assert rec.calls == [("iteration", 1, 0, 8)]    # a scan replays nothing


@pytest.mark.parametrize("cls, own", [
    (MultiLayerNetwork, set()),
    # fit_batch_repeated's tBPTT rule is the graph's own (its docstring)
    (ComputationGraph, {"_repeat_per_batch"}),
])
def test_nets_define_nothing_the_trainer_owns(cls, own):
    """The fit loop, the step build and the mesh wrap live in Trainer
    alone: a net that defines one of its names has forked the loop."""
    owned = {n for n in vars(Trainer) if not n.startswith("__")}
    assert cls.__mro__[1] is Trainer
    assert (owned & set(vars(cls))) == own
    for adapter in ("_batch_args", "_needs_tbptt", "_tbptt_length", "_loss"):
        assert adapter in vars(cls) and adapter not in vars(Trainer)


def test_per_iteration_listener_disables_chunking():
    """A listener that needs real step boundaries (PerformanceListener
    measures wall-clock per step) must force per-batch dispatch even when
    multi_step asks for chunks; cadence-only listeners must not."""
    net = MultiLayerNetwork(build_mlp()).init()
    assert net._resolve_multi_step(8) == 8
    net.set_listeners(ScoreIterationListener(5))
    assert net._resolve_multi_step(8) == 8
    net.set_listeners(ScoreIterationListener(5), PerformanceListener())
    assert net._resolve_multi_step(8) == 1


def test_auto_knobs_resolve_off_on_cpu_backend():
    """On the CPU backend "auto" disables chunking and device prefetch
    (no dispatch overhead worth a scan, no transfer to hide); explicit
    values are always honored."""
    net = MultiLayerNetwork(build_mlp()).init()
    on_cpu = jax.default_backend() == "cpu"
    assert net._resolve_multi_step("auto") == (1 if on_cpu else 8)
    assert net._resolve_device_prefetch("auto") == (not on_cpu)
    assert net._resolve_multi_step(6) == 6
    assert net._resolve_device_prefetch(True) is True


def test_tbptt_disables_chunking_and_keeps_score_lazy():
    """tBPTT routes through its chunked-backprop path (never the scan)
    and its accumulated score stays a lazy device array — the per-chunk
    float() sync is gone."""
    rng = np.random.default_rng(0)
    n, t, f, classes = 8, 12, 4, 2
    x = rng.normal(size=(n, t, f)).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[
        rng.integers(0, classes, (n, t))]
    b = (NeuralNetConfiguration.builder()
         .seed(42).updater(Adam(1e-2)).list())
    b.layer(GravesLSTM(n_out=8, activation="tanh"))
    b.layer(RnnOutput(n_out=classes, activation="softmax", loss="mcxent"))
    b.set_input_type(InputType.recurrent(f, t))
    b.backprop_type("tbptt", 4, 4)
    net = MultiLayerNetwork(b.build()).init()

    assert net._resolve_multi_step(8) == 1
    net.fit_batch(DataSet(x, y))
    assert isinstance(net.score_value, jax.Array)
    assert np.isfinite(float(net.score_value))


# ----------------------------------------------------------- the iterators
def _alive_prefetch_threads():
    return [t for t in threading.enumerate()
            if t.name == AsyncDataSetIterator.THREAD_NAME and t.is_alive()]


def test_device_prefetch_iterator_preserves_values_and_order():
    rng = np.random.default_rng(3)
    batches = [DataSet(rng.normal(size=(4, 6)).astype(np.float32),
                       rng.normal(size=(4, 2)).astype(np.float32),
                       (np.arange(4) < 3).astype(np.float32).reshape(4, 1),
                       None)
               for _ in range(5)]
    out = list(DevicePrefetchIterator(ListDataSetIterator(batches)))
    assert len(out) == 5
    for src, got in zip(batches, out):
        assert isinstance(got.features, jax.Array)
        np.testing.assert_array_equal(np.asarray(got.features), src.features)
        np.testing.assert_array_equal(np.asarray(got.labels), src.labels)
        np.testing.assert_array_equal(np.asarray(got.features_mask),
                                      src.features_mask)
        assert got.labels_mask is None


def test_device_prefetch_iterator_multidataset_and_empty():
    rng = np.random.default_rng(4)
    mds = MultiDataSet([rng.normal(size=(4, 3)), rng.normal(size=(4, 2))],
                       [rng.normal(size=(4, 1))])
    (got,) = list(DevicePrefetchIterator(ListDataSetIterator([mds])))
    assert isinstance(got, MultiDataSet)
    for a, b in zip(got.features, mds.features):
        assert isinstance(a, jax.Array)
        np.testing.assert_array_equal(np.asarray(a), b)
    assert list(DevicePrefetchIterator(ListDataSetIterator([]))) == []


def test_async_iterator_queue_depth_configurable(monkeypatch):
    base = ListDataSetIterator([])
    assert AsyncDataSetIterator(base).queue_size == 2
    assert AsyncDataSetIterator(base, queue_size=5).queue_size == 5
    monkeypatch.setenv("DL4J_TPU_PREFETCH_DEPTH", "7")
    assert default_prefetch_depth() == 7
    assert AsyncDataSetIterator(base).queue_size == 7


def test_async_iterator_joins_thread_on_early_exit():
    """Abandoning the generator (break / close) must drain and JOIN the
    prefetch thread — a producer blocked on a full queue must not leak."""
    rng = np.random.default_rng(5)
    batches = [DataSet(rng.normal(size=(2, 3)), rng.normal(size=(2, 2)))
               for _ in range(64)]
    assert not _alive_prefetch_threads()

    it = iter(AsyncDataSetIterator(ListDataSetIterator(batches),
                                   queue_size=2))
    next(it)
    next(it)
    assert _alive_prefetch_threads()  # producer waiting on the full queue
    it.close()
    assert not _alive_prefetch_threads()

    # normal exhaustion cleans up too
    n = 0
    for _ in AsyncDataSetIterator(ListDataSetIterator(batches)):
        n += 1
    assert n == 64
    deadline = time.monotonic() + 5.0
    while _alive_prefetch_threads() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not _alive_prefetch_threads()


def test_pipelined_fit_leaks_no_threads():
    x, y = make_blobs(n=96)
    net = MultiLayerNetwork(build_mlp()).init()
    net.fit(ArrayDataSetIterator(x, y, batch_size=16), epochs=2,
            multi_step=4)
    deadline = time.monotonic() + 5.0
    while _alive_prefetch_threads() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not _alive_prefetch_threads()
    assert not [t for t in threading.enumerate()
                if t.name == "dl4j-ckpt-writer" and t.is_alive()]
