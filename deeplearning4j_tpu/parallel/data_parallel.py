"""Data-parallel training over a device mesh.

Replaces all three of the reference's data-parallel strategies (SURVEY.md
§2.8): ParallelWrapper (intra-node, Nd4j.averageAndPropagate at
ParallelWrapper.java:218), Spark ParameterAveragingTrainingMaster
(driver-centric broadcast/aggregate, ParameterAveragingTrainingMaster.java:358)
and the Aeron parameter server — with sharded computation: the batch is
sharded over the 'data' mesh axis, params are replicated, and XLA inserts the
gradient all-reduce over ICI as part of the single compiled train step.

``ParallelWrapper`` reproduces the reference's *semantics* (k local steps
between parameter averages) for the fixed-seed equivalence tests
(TestCompareParameterAveragingSparkVsSingleMachine analogue); with
``averaging_frequency=1`` it is mathematically the same as the sharded step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.observability import opindex


def replicate(mesh: Mesh, x):
    """Replicate a host value across the (possibly multi-process) mesh.
    In a multi-process runtime plain device_put cannot address remote
    devices; every process holds the identical full value, so the
    process-local-data assembly path produces the replicated global
    Array."""
    repl = NamedSharding(mesh, P())
    if jax.process_count() > 1:
        return jax.make_array_from_process_local_data(repl, np.asarray(x))
    return jax.device_put(x, repl)


def apply_mesh(net, mesh: Mesh, data_axis: str = "data"):
    """Replicate the net's params/state/opt state across the mesh. Batches
    get sharded in fit_batch; computation follows sharding, so the jitted
    step becomes data-parallel with an ICI (and, across hosts, DCN)
    all-reduce on gradients."""
    put = lambda tree: jax.tree_util.tree_map(
        lambda leaf: replicate(mesh, leaf), tree)
    if net.params is not None:
        net.params = put(net.params)
    if net.state:
        net.state = put(net.state)
    if net.opt_state is not None:
        net.opt_state = put(net.opt_state)
    return net


def shard_batch(mesh: Mesh, data_axis: str, x):
    """Place a host batch sharded over the data axis (leading dim). In a
    multi-process runtime each process passes its LOCAL slice of the
    global batch (the Spark-partition analogue — SURVEY.md §3.4); the
    slices are assembled into one global sharded Array."""
    spec = P(data_axis) if np.ndim(x) >= 1 else P()
    sh = NamedSharding(mesh, spec)
    if jax.process_count() > 1:
        return jax.make_array_from_process_local_data(sh, np.asarray(x))
    return jax.device_put(jnp.asarray(x), sh)


def _pad_batch(batch, multiple: int):
    """Pad a partial batch ``(inputs, labels, fmasks, lmasks)`` up to a
    multiple of the data-axis size. Padded examples are masked out via the
    label masks (a ones mask for every label that came without one), so
    the loss mean (and thus gradients) are identical to the unpadded
    batch."""
    inputs, labels, fmasks, lmasks = batch
    pad = -jax.tree_util.tree_leaves(inputs)[0].shape[0] % multiple
    if not pad:
        return batch
    label_leaves, treedef = jax.tree_util.tree_flatten(labels)
    mask_leaves = ([None] * len(label_leaves) if lmasks is None
                   else treedef.flatten_up_to(lmasks))
    # per-example mask shaped like the label-mask convention: [b] for
    # [b, c] labels, [b, t] for [b, t, c] sequence labels
    lmasks = treedef.unflatten([
        jnp.ones(l.shape[:-1] if l.ndim > 1 else l.shape, jnp.float32)
        if m is None else m for l, m in zip(label_leaves, mask_leaves)])
    return jax.tree_util.tree_map(
        lambda a: jnp.pad(jnp.asarray(a), [(0, pad)] + [(0, 0)] * (a.ndim - 1)),
        (inputs, labels, fmasks, lmasks))


def shard_step(step_fn, mesh: Mesh, data_axis: str = "data"):
    """Jit the train step for mesh execution. Params arrive replicated
    (set by apply_mesh) and every leaf of the batch ``(inputs, labels,
    fmasks, lmasks)`` is sharded over the data axis here; partial batches
    are zero-padded + mask-excluded so any batch size divides the mesh."""
    n_shards = mesh.shape[data_axis]
    # each process pads its LOCAL slice to its local share of the data axis
    pad_multiple = max(n_shards // jax.process_count(), 1)

    jitted = jax.jit(step_fn, donate_argnums=(0, 1, 2))

    def wrapped(params, state, opt_state, it, inputs, labels, fmasks, lmasks,
                rng):
        batch = jax.tree_util.tree_map(
            lambda a: shard_batch(mesh, data_axis, a),
            _pad_batch((inputs, labels, fmasks, lmasks), pad_multiple))
        args = (params, state, opt_state, it, *batch, replicate(mesh, rng))
        opindex.register(jitted, args, args[4:8])
        return jitted(*args)

    return wrapped


class ParallelWrapper:
    """Reference-semantics data-parallel trainer: each of N logical workers
    runs ``averaging_frequency`` local steps, then parameters and (optionally)
    updater state are averaged (ParallelWrapper.java:181-218,:239-252).

    Implemented as a vmapped worker dimension + ``pmean``-equivalent
    tree-average; runs on any mesh or a single device. This exists for
    capability/equivalence parity — the sharded step above is the
    performance path.
    """

    def __init__(self, net, workers: int = 2, averaging_frequency: int = 1,
                 average_updaters: bool = True):
        self.net = net
        self.workers = workers
        self.averaging_frequency = averaging_frequency
        self.average_updaters = average_updaters

    def fit(self, iterator, epochs: int = 1):
        net = self.net
        if net._train_step is None:
            net._train_step = net._build_train_step()
        step = net._train_step
        for _ in range(epochs):
            batch_iter = iter(iterator)
            done = False
            while not done:
                # Collect workers x averaging_frequency batches, round-robin
                # like the reference's per-worker queues.
                replicas = [
                    (jax.tree_util.tree_map(jnp.copy, net.params),
                     jax.tree_util.tree_map(jnp.copy, net.state),
                     jax.tree_util.tree_map(jnp.copy, net.opt_state))
                    for _ in range(self.workers)
                ]
                scores = []
                stepped = [False] * self.workers
                for _ in range(self.averaging_frequency):
                    for w in range(self.workers):
                        try:
                            ds = next(batch_iter)
                        except StopIteration:
                            done = True
                            break
                        stepped[w] = True
                        p, s, o = replicas[w]
                        net._rng_key, rng = jax.random.split(net._rng_key)
                        it_c = jnp.asarray(net.iteration, jnp.int32)
                        p, s, o, score = step(
                            p, s, o, it_c, *net._batch_args(ds), rng)
                        replicas[w] = (p, s, o)
                        scores.append(score)
                    if done:
                        break
                if not any(stepped):
                    break
                # Average params (and updater state) across the workers that
                # actually stepped — the Nd4j.averageAndPropagate equivalent,
                # here a tree-mean (idle tail workers are excluded so the
                # last partial round isn't diluted toward stale params).
                active = [replicas[w] for w in range(self.workers) if stepped[w]]
                def mean_leaf(*xs):
                    # Integer leaves (e.g. Adam's step counter 't') must stay
                    # integral: true-division would silently float them and
                    # retrace the donated jitted step. Max = the furthest
                    # worker's count, exact when workers step evenly.
                    if jnp.issubdtype(xs[0].dtype, jnp.integer):
                        return jnp.max(jnp.stack(xs), axis=0)
                    return sum(xs) / len(xs)
                def tree_mean(trees):
                    return jax.tree_util.tree_map(mean_leaf, *trees)
                net.params = tree_mean([r[0] for r in active])
                net.state = active[0][1]
                if self.average_updaters:
                    net.opt_state = tree_mean([r[2] for r in active])
                else:
                    net.opt_state = active[0][2]
                net.iteration += 1
                if scores:
                    net.score_value = scores[-1]
                for l in net.listeners:
                    l.iteration_done(net, net.iteration, net.epoch)
            iterator.reset()
            net.epoch += 1
        return net
