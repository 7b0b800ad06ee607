"""Trainer — how a net is trained, written once for both nets.

``MultiLayerNetwork`` and ``ComputationGraph`` inherit this class. It owns
the fused train step's build (jit, or the mesh wrapper of
parallel/data_parallel.py), ``fit`` and its async runtime (background
prefetch, device prefetch, chunks of k steps in one jitted scan),
``fit_batch``, tBPTT's dispatch loop, the optimizer-state trees and the
mesh placement.

Everything here is written over the *batch pytree* ``(inputs, labels,
fmasks, lmasks)``: arguments 4-7 of the step function and what the chunk
program scans over. Absent masks are ``None`` (or an empty dict), which
is an empty subtree, so no path treats them specially. What a net must
supply:

- ``_batch_args(ds, leaf=jnp.asarray)``: one minibatch arranged as that
  tree, ``leaf`` applied to every array in it (``np.shape`` gives the
  chunk signature without copying anything to the device);
- ``_needs_tbptt(ds)``: whether this batch is longer than the
  configuration's tBPTT length, and ``_tbptt_length(inputs, labels)``,
  which validates such a batch and returns its time length;
- ``_loss(params, state, inputs, labels, fmasks, lmasks, rng)``, the
  scalar the step autodiffs, and ``self.layers`` / ``self.conf``.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.datasets.iterator import (
    ArrayDataSetIterator,
    AsyncDataSetIterator,
    DevicePrefetchIterator,
    ListDataSetIterator,
)
from deeplearning4j_tpu.nn import precision
from deeplearning4j_tpu.observability import goodput as _goodput
from deeplearning4j_tpu.observability import metrics as _obs_metrics
from deeplearning4j_tpu.observability import moe as _obs_moe
from deeplearning4j_tpu.observability import opindex as _opindex
from deeplearning4j_tpu.observability.trace import get_tracer as _get_tracer


def _remat_match(name: str, prefixes) -> bool:
    """Prefix match; a trailing ``$`` anchors an EXACT name (needed for
    numeric layer names where 'layer_1' would also match 'layer_1x')."""
    for p in prefixes:
        if p.endswith("$"):
            if name == p[:-1]:
                return True
        elif name.startswith(p):
            return True
    return False


def _remat_prefixes() -> tuple:
    """Selective rematerialization scope: comma-separated vertex-name
    prefixes (e.g. ``DL4J_TPU_REMAT=s0b`` recomputes every stage-1 block
    interior in the backward instead of saving it; a trailing ``$``
    anchors an exact vertex/layer name — ``layer_1$`` does not match
    ``layer_10``). The TPU answer to
    activation-memory pressure at large batch: trade cheap stage FLOPs
    for HBM residency. Granularity is BLOCK-level: each maximal
    contiguous topo run of matching vertices executes under one
    jax.checkpoint, so only the span's INPUTS are saved and XLA keeps
    full scheduling freedom elsewhere. (The alternative — wrapping the
    whole loss in a jax.checkpoint name-policy — was measured NEGATIVE:
    forcing every untagged intermediate into the explicit residual set
    cost +18 GB/step and +3.8 GB peak on ResNet-50, PERF.md round 5.)
    Default off."""
    import os
    v = os.environ.get("DL4J_TPU_REMAT", "").strip()
    return tuple(p for p in (s.strip() for s in v.split(",")) if p)


class Trainer:
    _FIT_CHUNK_DEFAULT = 8

    def __init__(self, conf):
        self.conf = conf
        self.layers = None          # runtime Layer objects
        self.params = None          # pytree {layer_name: {param: array}}
        self.state = None           # pytree {layer_name: {...}} (e.g. BN stats)
        self.opt_state = None
        self.iteration = 0
        self.epoch = 0
        self.listeners: list = []
        self.score_value = None
        self._train_step = None
        self._tbptt_step = None
        self._multi_steps = {}
        self._apply_fns = {}
        self._forward_seen = set()  # (id(fn), input shapes) traced already
        self._mesh = None
        self._rng_key = None
        self._rnn_state = None
        # DL4J_TPU_REMAT resolved at train-step build time (None until
        # then); later env-var changes are no-ops for this model
        self.remat_prefixes = None
        self._remat_warned = False
        # runtime learning-rate multiplier (resilience NaN backoff); a
        # compile-time constant of the fused step — set via set_lr_scale
        self._lr_scale = 1.0

    # ----------------------------------------------------- trees and caches
    def _init_trees(self, seed: int, structure_only: bool):
        """``self._rng_key`` from ``seed``, and from it the parameter,
        state and optimizer pytrees of ``self.layers`` —
        ShapeDtypeStructs (via jax.eval_shape) with ``structure_only``,
        which clone()/restore use because they overwrite every leaf
        anyway."""
        def init_trees(key):
            params, state = {}, {}
            for layer in self.layers:
                key, sub = jax.random.split(key)
                p = layer.init_params(sub)
                if p:
                    params[layer.name] = p
                s = layer.init_state()
                if s:
                    state[layer.name] = s
            return params, state, self._fresh_opt_state(params)

        # eager: a sub-second program for the key and for every distinct
        # initializer call
        with _get_tracer().program_span("net_init"):
            self._rng_key = jax.random.PRNGKey(seed)
            if structure_only:
                self.params, self.state, self.opt_state = jax.eval_shape(
                    init_trees, self._rng_key)
            else:
                self.params, self.state, self.opt_state = init_trees(
                    self._rng_key)
        self.iteration = 0
        self._drop_compiled(forward_too=True)

    def _fresh_opt_state(self, params) -> dict:
        opt_state = {}
        for layer in self.layers:
            if layer.name in params:
                upd = layer.resolve("updater")
                opt_state[layer.name] = upd.init_state(params[layer.name])
        ls = precision.init_loss_scale_state(self.conf.global_conf.dtype)
        if ls is not None:
            opt_state[precision.LOSS_SCALE_KEY] = ls
        return opt_state

    def _drop_compiled(self, forward_too: bool = False):
        """Forget every cached train-step variant (and, with
        ``forward_too``, the inference programs and streaming state)."""
        self._train_step = None
        self._tbptt_step = None
        self._multi_steps = {}
        if forward_too:
            self._apply_fns = {}
            self._forward_seen = set()
            self._rnn_state = None

    def materialize_state(self):
        """Concrete layer state (e.g. BN running stats) — used after a
        structure-only init when a checkpoint lacks the state tree."""
        state = {}
        for layer in self.layers:
            s = layer.init_state()
            if s:
                state[layer.name] = s
        self.state = state

    def materialize_opt_state(self):
        """Fresh optimizer state from (concrete) params — used after a
        structure-only init when the updater state isn't being restored."""
        self.opt_state = self._fresh_opt_state(self.params)

    def _first_forward(self, key, shapes, build, *args):
        """``self._apply_fns[key](*args)``; ``build()`` makes and returns
        that entry on first use. The first call with inputs of these
        ``shapes`` traces, lowers and compiles the forward, so it runs
        under the span ``forward``; a later one pays a set lookup."""
        fn = self._apply_fns.get(key)
        if fn is not None and (id(fn), shapes) in self._forward_seen:
            return fn(*args)
        with _get_tracer().program_span("forward"):
            if fn is None:
                fn = build()
            self._forward_seen.add((id(fn), shapes))
            return fn(*args)

    def _require_init(self):
        if self.params is None:
            raise RuntimeError(
                "Network not initialized — call net.init() before "
                "fit()/output()/evaluate()")

    def num_params(self) -> int:
        return sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(self.params))

    def clone(self):
        """Deep copy (Model.clone parity) — used by transfer learning.
        Leaves are materially copied (jnp.copy): the jitted train step
        donates its input buffers, so an aliasing clone would be invalidated
        by the next fit_batch on either net."""
        net = type(self)(self.conf)
        net.init(structure_only=True)
        net.params = jax.tree_util.tree_map(jnp.copy, self.params)
        net.state = jax.tree_util.tree_map(jnp.copy, self.state)
        net.opt_state = jax.tree_util.tree_map(jnp.copy, self.opt_state)
        net.iteration = self.iteration
        net.epoch = self.epoch
        return net

    def set_lr_scale(self, scale: float):
        """Scale every layer's scheduled learning rate by ``scale`` from
        the next step on (resilience/supervisor.py backs off the rate
        after a NaN rollback). The scale is baked into the compiled step,
        so every cached step variant is invalidated — expect one
        recompile per change, which is why this is a recovery lever and
        not a schedule."""
        scale = float(scale)
        if scale <= 0.0:
            raise ValueError(f"lr scale must be > 0, got {scale}")
        if scale != self._lr_scale:
            self._lr_scale = scale
            self._drop_compiled()
        return self

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def add_listener(self, listener):
        self.listeners.append(listener)
        return self

    # ------------------------------------------------------------------ mesh
    def use_mesh(self, mesh, data_axis: str = "data",
                 model_axis: str | None = None, tp_rules=None):
        """Shard training over a jax Mesh: batches split on ``data_axis``;
        params replicated (pure dp) or, with ``model_axis`` set, sharded
        column-parallel over that axis (dp x tp — parallel/tensor.py).
        XLA inserts every collective (gradient all-reduce over data,
        activation all-gathers/reduce-scatters over model) in the one
        compiled step. (Replaces ParallelWrapper/Spark parameter
        averaging — SURVEY.md §2.8 — and adds the model-parallel axis the
        reference never had.)"""
        self._mark_meshed(mesh, data_axis, model_axis, tp_rules)
        if model_axis is not None:
            from deeplearning4j_tpu.parallel.tensor import (
                apply_tensor_parallel)
            apply_tensor_parallel(self, mesh, data_axis, model_axis,
                                  tp_rules)
        else:
            from deeplearning4j_tpu.parallel.data_parallel import apply_mesh
            apply_mesh(self, mesh, data_axis)
        return self

    def _mark_meshed(self, mesh, data_axis: str = "data",
                     model_axis=None, tp_rules=None):
        """Record mesh placement + drop compiled-step caches WITHOUT
        moving a single leaf. The elastic restore path
        (utils/checkpoint.py) places params/opt_state directly into
        their target NamedShardings and then calls this, instead of the
        replicate-then-``use_mesh`` double materialization."""
        self._mesh = (mesh, data_axis)
        self._mesh_detail = {"model_axis": model_axis, "tp_rules": tp_rules}
        self._drop_compiled(forward_too=True)
        return self

    def _prefetch_sharding(self):
        """Target sharding for prefetched batches (None = default device).
        Multi-process meshes assemble global arrays from host shards in
        shard_step, so they keep host-side batches."""
        if self._mesh is None:
            return None
        if jax.process_count() > 1:
            return None
        from jax.sharding import NamedSharding, PartitionSpec
        mesh, axis = self._mesh
        return NamedSharding(mesh, PartitionSpec(axis))

    # ------------------------------------------------ recurrent state helpers
    def _set_streaming(self, flag: bool):
        from deeplearning4j_tpu.nn.layers.recurrent import set_streaming
        set_streaming(self.layers, flag)

    def rnn_clear_previous_state(self):
        """Reset streaming decode state (rnnClearPreviousState parity)."""
        self._rnn_state = None

    # ---------------------------------------------------------- train step
    def _resolve_remat(self) -> tuple:
        """Read DL4J_TPU_REMAT exactly ONCE — when the first train step
        is built — and record the resolved prefixes on the model
        (``self.remat_prefixes``). The jitted step is cached, so a later
        env-var change can never take effect; resolving eagerly (and
        warning on a detected change) keeps remat experiments from
        silently measuring a stale configuration."""
        current = _remat_prefixes()
        if self.remat_prefixes is None:
            self.remat_prefixes = current
        elif current != self.remat_prefixes and not self._remat_warned:
            import warnings
            warnings.warn(
                f"DL4J_TPU_REMAT changed to {current!r} after the train "
                f"step was built with {self.remat_prefixes!r}; the cached "
                "step ignores the change (set the variable before the "
                "first training step, or rebuild the model)",
                RuntimeWarning, stacklevel=3)
            self._remat_warned = True
        return self.remat_prefixes

    def _step_fn(self):
        """The raw (un-jitted) fused train step: fwd+bwd+normalize+update,
        with loss scaling when the dtype policy calls for it (f16) —
        see nn/precision.py."""
        self._resolve_remat()

        def loss_fn(params, state, inputs, labels, fmasks, lmasks, rng):
            return self._loss(params, state, inputs, labels, fmasks, lmasks,
                              rng)

        return precision.build_step_fn(loss_fn, self.layers,
                                       self.conf.global_conf, self._lr_scale)

    def _build_train_step(self):
        step_fn = self._step_fn()
        if self._mesh is not None:
            from deeplearning4j_tpu.parallel.data_parallel import shard_step
            return shard_step(step_fn, *self._mesh)
        return jax.jit(step_fn, donate_argnums=(0, 1, 2))

    def _step_args(self, batch, rng):
        """The step's nine arguments for ``batch`` at this iteration."""
        return (self.params, self.state, self.opt_state,
                jnp.asarray(self.iteration, jnp.int32), *batch, rng)

    def step_cost_analysis(self, ds) -> dict:
        """XLA cost-model numbers for ONE compiled train step on this
        batch shape: {"flops", "bytes_accessed"} (SURVEY.md §5.1 — feeds
        PerformanceListener(flops_per_step=...) for live MFU)."""
        self._require_init()
        if self._train_step is None:
            self._train_step = self._build_train_step()
        from deeplearning4j_tpu.utils.perf import xla_step_cost
        return xla_step_cost(self._train_step, *self._step_args(
            self._batch_args(ds), jax.random.PRNGKey(0)))

    def _maybe_derive_flops(self, program, args, batch):
        """Per-step FLOPs from XLA's cost model of ``program``, the jitted
        step its caller has just dispatched with ``args``, the first time
        each (program, per-step batch shapes) pair is seen. The donated
        trees of ``args`` are swapped for the dispatch's outputs, of the
        same avals and shardings, so jax's caches hand back the lowering
        and the executable the dispatch made: nothing is traced anew,
        lowered or compiled. The chunked path's scan counts as one step:
        XLA costs a ``while`` body once, and ``build_multi_batch_step``
        pins ``unroll=1``. Feeds live dl4j_mfu / dl4j_flops_per_second
        with zero user wiring; DL4J_TPU_AUTO_FLOPS=0 opts out."""
        if not _goodput.auto_flops_enabled():
            return
        key = (id(program), jax.tree_util.tree_map(np.shape, batch))
        if getattr(self, "_flops_key", None) == key:
            return
        self._flops_key = key
        with _get_tracer().program_span("flops_derive"):
            try:
                from deeplearning4j_tpu.utils.perf import xla_step_cost
                cost = xla_step_cost(program, self.params, self.state,
                                     self.opt_state, *args[3:])
                self.flops_per_step = cost["flops"] or None
            except NotImplementedError:
                # meshed/wrapped steps have no .lower
                self.flops_per_step = None
        _goodput.observe_flops(self.flops_per_step)

    # ------------------------------------------------------------- one batch
    def fit_batch(self, ds):
        """One optimization step on one minibatch (Model.fit parity)."""
        self._require_init()
        if self._needs_tbptt(ds):
            return self._fit_tbptt(ds)
        if self._train_step is None:
            self._train_step = self._build_train_step()
        else:
            self._resolve_remat()  # warn if DL4J_TPU_REMAT changed since
        tracer = _get_tracer()
        with tracer.span("host_dispatch"):
            self._rng_key, rng = jax.random.split(self._rng_key)
            batch = self._batch_args(ds)
            args = self._step_args(batch, rng)
        with tracer.span("device_step"):
            if self._mesh is None:  # a meshed step registers its inner jit
                _opindex.register(self._train_step, args, args[4:8])
            self.params, self.state, self.opt_state, score = self._train_step(
                *args)
        self.iteration += 1
        self.score_value = score
        self.last_batch_examples = ds.num_examples
        _goodput.observe_steps(1)
        self._maybe_derive_flops(self._train_step, args, batch)
        if self.listeners:
            t0 = time.perf_counter()
            for l in self.listeners:
                l.iteration_done(self, self.iteration, self.epoch)
            t1 = time.perf_counter()
            tracer.record("score_sync", t0, t1)
            _obs_metrics.observe_dispatch_lag(t1 - t0)
        return score

    def _repeat_per_batch(self, ds) -> bool:
        """Whether ``fit_batch_repeated`` must go through ``fit_batch``:
        meshed execution needs shard_step's batch sharding/padding and
        tBPTT needs chunked backprop."""
        return self._mesh is not None or self._needs_tbptt(ds)

    def fit_batch_repeated(self, ds, n_steps: int):
        """Run ``n_steps`` optimization steps on one minibatch inside a
        SINGLE XLA execution (``lax.scan`` over the fused train step).

        TPU-native tight loop: one dispatch instead of n — removes
        host-dispatch latency from the hot path (the reference pays a
        JNI crossing per op; a jitted-scan epoch pays one per n steps).
        Used by bench.py for device-true step timing and usable for
        training on a small device-resident dataset."""
        self._require_init()
        if n_steps < 1:
            raise ValueError(f"n_steps must be >= 1, got {n_steps}")
        if self._repeat_per_batch(ds):
            # n dispatches, to keep semantics identical
            for _ in range(n_steps):
                score = self.fit_batch(ds)
            return score
        from deeplearning4j_tpu.nn.multistep import get_multi_step
        jitted = get_multi_step(self, n_steps)
        self._rng_key, rng = jax.random.split(self._rng_key)
        self.params, self.state, self.opt_state, score = jitted(
            *self._step_args(self._batch_args(ds), rng))
        self.iteration += n_steps
        self.score_value = score
        self.last_batch_examples = ds.num_examples
        _goodput.observe_steps(n_steps)
        return score

    def _fit_tbptt(self, ds):
        """Truncated BPTT (doTruncatedBPTT :1119): split the time axis of
        every time-series input, label and mask into tbptt_fwd_length
        chunks; recurrent state carries across chunks inside the compiled
        step (via the state pytree) and resets per batch. Static (2d)
        inputs are fed whole to every chunk."""
        from deeplearning4j_tpu.nn.layers.recurrent import strip_carries
        L = self.conf.tbptt_fwd_length
        inputs, labels, fmasks, lmasks = self._batch_args(ds)
        t_total = self._tbptt_length(inputs, labels)

        def cut(tree, sl, time_ndim):
            return jax.tree_util.tree_map(
                lambda a: a[:, sl] if a.ndim == time_ndim else a, tree)

        self._set_streaming(True)
        try:
            if self._tbptt_step is None:
                self._tbptt_step = self._build_train_step()
            score_sum, weight = 0.0, 0
            with _get_tracer().span("device_step", tbptt=True):
                for start in range(0, t_total, L):
                    sl = slice(start, min(start + L, t_total))
                    self._rng_key, rng = jax.random.split(self._rng_key)
                    chunk = (cut(inputs, sl, 3), cut(labels, sl, 3),
                             cut(fmasks, sl, 2), cut(lmasks, sl, 2))
                    (self.params, self.state, self.opt_state,
                     chunk_score) = self._tbptt_step(
                        *self._step_args(chunk, rng))
                    w = sl.stop - sl.start
                    # accumulate ON DEVICE: a float() here would sync the
                    # pipeline once per chunk; consumers pull the final mean
                    score_sum = score_sum + chunk_score * w
                    weight += w
            self.state = strip_carries(self.state)
            score = score_sum / max(weight, 1)
        finally:
            self._set_streaming(False)
        self.iteration += 1
        self.score_value = score
        self.last_batch_examples = ds.num_examples
        _goodput.observe_steps(1)
        with _get_tracer().span("score_sync"):
            for l in self.listeners:
                l.iteration_done(self, self.iteration, self.epoch)
        return score

    # ------------------------------------------------------------------- fit
    def fit(self, data, labels=None, *, epochs: int = 1, batch_size: int = 32,
            async_prefetch: bool = True, device_prefetch="auto",
            multi_step="auto"):
        """Train. Accepts a DataSetIterator (or any iterable of
        minibatches), one DataSet / MultiDataSet, or (features, labels)
        arrays (MultiLayerNetwork.fit overloads parity; an iterator is
        wrapped in an async prefetcher like MultiLayerNetwork.java:951 /
        ComputationGraph.java:701).

        Async runtime (all bit-identity-preserving vs the per-batch loop):
        ``async_prefetch`` overlaps host batch prep (background thread),
        ``device_prefetch`` overlaps the host→device copy of batch N+1 with
        step N (DevicePrefetchIterator; "auto" = on for accelerator
        backends, off on CPU where there is no transfer to hide), and
        ``multi_step`` collapses k Python dispatches into one jitted scan
        chunk ("auto" = 8 on accelerators when no attached listener needs
        per-iteration values; an int pins k; 1 disables). Chunking is
        skipped under a device mesh and for tBPTT, where per-batch
        semantics differ."""
        if isinstance(data, (DataSet, MultiDataSet)):
            it = ListDataSetIterator([data])
        elif labels is not None:
            it = ArrayDataSetIterator(data, labels, batch_size=batch_size)
        else:
            it = data
        # a plain list or generator of minibatches has no reset()
        resettable = hasattr(it, "reset")
        chunk = self._resolve_multi_step(multi_step)
        device_prefetch = self._resolve_device_prefetch(device_prefetch)
        _obs_metrics.install_runtime_metrics()
        _obs_moe.install(self)
        from deeplearning4j_tpu.compilecache import ensure_configured
        ensure_configured()  # JAX_COMPILATION_CACHE_DIR, if set
        tracer = _get_tracer()
        ledger = _goodput.start_run("fit", net=self)
        from deeplearning4j_tpu.observability import distributed as _obs_dist
        _obs_dist.stamp_run_marker("fit")
        status = "completed"
        try:
            for _ in range(epochs):
                source = (AsyncDataSetIterator(it)
                          if async_prefetch and resettable else it)
                if device_prefetch:
                    source = DevicePrefetchIterator(
                        source, sharding=self._prefetch_sharding())
                for l in self.listeners:
                    l.on_epoch_start(self)
                it0, t0 = self.iteration, time.perf_counter()
                if chunk > 1:
                    self._fit_epoch_chunked(source, chunk)
                else:
                    stream = iter(source)
                    while True:
                        with tracer.span("data_wait"):
                            ds = next(stream, None)
                        if ds is None:
                            break
                        self.fit_batch(ds)
                _obs_metrics.observe_rate(self.iteration - it0,
                                          time.perf_counter() - t0)
                for l in self.listeners:
                    l.on_epoch_end(self)
                self.epoch += 1
                if resettable and not getattr(it, "auto_epochs", False):
                    # datapipe Pipelines advance their own epoch state
                    # (seed + epoch shuffle orders); reset() would rewind
                    # them to epoch 0 every pass
                    it.reset()
        except BaseException:
            status = "failed"
            raise
        finally:
            self.last_run_report = _goodput.end_run(ledger, status=status)
        return self

    def resilient_fit(self, data, labels=None, *, checkpoint_dir: str,
                      epochs: int = 1, batch_size: int = 32, **supervisor_kw):
        """Supervised ``fit``: periodic checkpoints to fresh step
        directories, auto-resume from the newest valid one, transient-step
        retry, NaN rollback + LR backoff, SIGTERM preemption handling
        (resilience/supervisor.py). Returns the SupervisorResult."""
        from deeplearning4j_tpu.resilience import resilient_fit
        return resilient_fit(self, data, labels,
                             checkpoint_dir=checkpoint_dir, epochs=epochs,
                             batch_size=batch_size, **supervisor_kw)

    def _resolve_multi_step(self, multi_step) -> int:
        """How many fit steps one jitted dispatch may cover. 1 = per-batch
        (mesh / tbptt / a listener that needs real per-step boundaries).
        "auto" also resolves to 1 on the CPU backend: collapsing dispatch
        pays when per-step dispatch overhead rivals device compute
        (accelerators); XLA:CPU instead pays scan-carry copies + chunk
        slicing that dwarf the dispatch saved (measured in bench
        host_loop). An explicit int is always honored."""
        if multi_step in (None, False, 0, 1):
            return 1
        if self._mesh is not None or self.conf.backprop_type == "tbptt":
            return 1
        for l in self.listeners:
            if getattr(l, "needs_per_iteration", True):
                return 1
        if multi_step == "auto":
            if jax.default_backend() == "cpu":
                return 1
            return self._FIT_CHUNK_DEFAULT
        return max(1, int(multi_step))

    @staticmethod
    def _resolve_device_prefetch(device_prefetch) -> bool:
        """"auto" = on for accelerator backends (overlaps the host→device
        copy of batch N+1 with step N); off on CPU, where device_put is
        just an extra eager copy with no transfer to hide (measured in
        bench host_loop). Explicit booleans are always honored."""
        if device_prefetch == "auto":
            return jax.default_backend() != "cpu"
        return bool(device_prefetch)

    def _fit_epoch_chunked(self, source, chunk: int):
        """Group consecutive same-shape batches and dispatch each group as
        ONE jitted scan over distinct batches (bit-identical to the
        per-batch loop, including the rng chain — see multistep.py)."""
        self._require_init()
        tracer = _get_tracer()
        buf, sig = [], None
        stream = iter(source)
        while True:
            with tracer.span("data_wait"):
                ds = next(stream, None)
            if ds is None:
                break
            s = self._batch_args(ds, np.shape)
            if buf and s != sig:
                self._dispatch_chunk(buf)
                buf = []
            sig = s
            buf.append(ds)
            if len(buf) == chunk:
                self._dispatch_chunk(buf)
                buf = []
        if buf:
            self._dispatch_chunk(buf)

    def _dispatch_chunk(self, batches):
        """Run len(batches) steps in one XLA execution (lax.scan over the
        fused step), then replay listeners with per-iteration scores."""
        if len(batches) == 1:
            self.fit_batch(batches[0])
            return
        from deeplearning4j_tpu.nn.multistep import get_multi_batch_step
        tracer = _get_tracer()
        with tracer.span("host_dispatch", steps=len(batches)):
            jitted = get_multi_batch_step(self)
            trees = [self._batch_args(b) for b in batches]
            stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *trees)
            it0 = jnp.asarray(self.iteration, jnp.int32)
            steps = jnp.arange(len(batches), dtype=jnp.int32)
        with tracer.span("device_step", steps=len(batches)):
            args = (self.params, self.state, self.opt_state, it0,
                    self._rng_key, steps, stacked)
            _opindex.register(jitted, args, args[6])
            (self.params, self.state, self.opt_state, self._rng_key,
             scores) = jitted(*args)
            # inside the span: the first slice of each chunk length
            # compiles (a dynamic_slice and a squeeze)
            self.score_value = scores[-1]
        start = self.iteration
        self.iteration += len(batches)
        self.last_batch_examples = batches[-1].num_examples
        _goodput.observe_steps(len(batches))  # one dispatch, k real steps
        # pre-stack arrays already have the per-step shape; slicing the
        # stacked device arrays here would dispatch (and first-call
        # compile) an XLA gather outside the flops_derive span
        self._maybe_derive_flops(jitted, args, trees[0])
        with tracer.span("score_sync", steps=len(batches)):
            self._replay_listeners(start, scores,
                                   [b.num_examples for b in batches])

    def _replay_listeners(self, start: int, scores, examples):
        """Post-chunk iteration_done replay: every listener here declared
        needs_per_iteration=False, so it sees the same (iteration, score)
        stream as per-batch dispatch — score_value stays a lazy device
        slice until a listener's own cadence floats it."""
        if not self.listeners:
            return
        for j in range(len(examples)):
            self.score_value = scores[j]
            self.last_batch_examples = examples[j]
            for l in self.listeners:
                l.iteration_done(self, start + j + 1, self.epoch)
        self.score_value = scores[-1]
        self.last_batch_examples = examples[-1]
