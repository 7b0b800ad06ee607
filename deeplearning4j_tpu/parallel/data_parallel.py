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
    if x is None:
        return None
    spec = P(data_axis) if np.ndim(x) >= 1 else P()
    sh = NamedSharding(mesh, spec)
    if jax.process_count() > 1:
        return jax.make_array_from_process_local_data(sh, np.asarray(x))
    return jax.device_put(jnp.asarray(x), sh)


def _pad_batch(x, labels, fmask, lmask, multiple: int):
    """Pad a partial batch up to a multiple of the data-axis size. Padded
    examples are masked out via the label mask, so the loss mean (and thus
    gradients) are identical to the unpadded batch."""
    n = x.shape[0]
    target = -(-n // multiple) * multiple
    if target == n:
        return x, labels, fmask, lmask
    pad = target - n

    def pad0(a):
        if a is None:
            return None
        widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
        return jnp.pad(jnp.asarray(a), widths)

    if lmask is None:
        # per-example mask shaped like the label-mask convention
        lead = labels.shape[:-1] if labels.ndim > 1 else labels.shape
        lmask = jnp.ones(lead, jnp.float32)
    return pad0(x), pad0(labels), pad0(fmask), pad0(lmask)


def shard_step(net, step_fn, mesh: Mesh, data_axis: str = "data"):
    """Jit the train step for mesh execution. Params arrive replicated and
    batches sharded (set by apply_mesh/shard_batch); partial batches are
    zero-padded + mask-excluded so any batch size divides the mesh."""
    n_shards = mesh.shape[data_axis]
    # each process pads its LOCAL slice to its local share of the data axis
    pad_multiple = max(n_shards // jax.process_count(), 1)

    jitted = jax.jit(step_fn, donate_argnums=(0, 1, 2))

    def wrapped(params, state, opt_state, it, x, labels, fmask, lmask, rng):
        x, labels, fmask, lmask = _pad_batch(x, labels, fmask, lmask,
                                             pad_multiple)
        x = shard_batch(mesh, data_axis, x)
        labels = shard_batch(mesh, data_axis, labels)
        fmask = shard_batch(mesh, data_axis, fmask)
        lmask = shard_batch(mesh, data_axis, lmask)
        rng = replicate(mesh, rng)
        args = (params, state, opt_state, it, x, labels, fmask, lmask, rng)
        opindex.register(jitted, args, args[4:8])
        return jitted(*args)

    return wrapped


def _mask_lead_shape(label):
    """Label-mask leading shape: [b] for [b, c] labels, [b, t] for
    [b, t, c] sequence labels."""
    return label.shape[:-1] if label.ndim > 1 else label.shape


def shard_step_multi(net, step_fn, mesh: Mesh, data_axis: str = "data"):
    """ComputationGraph variant of shard_step: inputs are a dict and labels/
    masks are lists; every batch-leading tensor is sharded over the data
    axis; partial batches are zero-padded with padded rows excluded via the
    per-output label masks."""
    n_shards = mesh.shape[data_axis]
    # each process pads its LOCAL slice to its local share of the data axis
    pad_multiple = max(n_shards // jax.process_count(), 1)

    jitted = jax.jit(step_fn, donate_argnums=(0, 1, 2))

    def wrapped(params, state, opt_state, it, inputs, labels, fmasks, lmasks,
                rng):
        n = next(iter(inputs.values())).shape[0]
        target = -(-n // pad_multiple) * pad_multiple
        if target != n:
            pad = target - n

            def pad0(a):
                if a is None:
                    return None
                widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
                return jnp.pad(jnp.asarray(a), widths)

            inputs = {k: pad0(v) for k, v in inputs.items()}
            if lmasks is None:
                lmasks = [jnp.ones(_mask_lead_shape(l), jnp.float32)
                          for l in labels]
            else:
                lmasks = [jnp.ones(_mask_lead_shape(l), jnp.float32)
                          if m is None else m
                          for l, m in zip(labels, lmasks)]
            labels = [pad0(l) for l in labels]
            lmasks = [pad0(m) for m in lmasks]
            fmasks = {k: pad0(v) for k, v in fmasks.items()}
        inputs = {k: shard_batch(mesh, data_axis, v) for k, v in inputs.items()}
        labels = [shard_batch(mesh, data_axis, l) for l in labels]
        fmasks = {k: shard_batch(mesh, data_axis, v) for k, v in fmasks.items()}
        if lmasks is not None:
            lmasks = [shard_batch(mesh, data_axis, m) for m in lmasks]
        rng = replicate(mesh, rng)
        args = (params, state, opt_state, it, inputs, labels, fmasks, lmasks,
                rng)
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
                            p, s, o, it_c,
                            jnp.asarray(ds.features), jnp.asarray(ds.labels),
                            None if ds.features_mask is None
                            else jnp.asarray(ds.features_mask),
                            None if ds.labels_mask is None
                            else jnp.asarray(ds.labels_mask),
                            rng)
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
