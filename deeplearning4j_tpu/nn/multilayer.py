"""MultiLayerNetwork — a sequential stack with fit()/output()/evaluate().

Parity: nn/multilayer/MultiLayerNetwork.java (2,590 LoC): init() :903,
fit(DataSetIterator) :947, output :1512, feedForward :675, evaluate :2413.

TPU-native design (SURVEY.md §7): instead of the reference's per-op JNI
dispatch through Solver -> StochasticGradientDescent -> per-layer
backpropGradient (call stack §3.1), ``fit`` compiles ONE jitted train step:
forward + loss + autodiff backward + gradient normalization + updater +
parameter update fused into a single XLA program. Parameters/optimizer state
are pytrees keyed by layer name. Optional distribution: pass a
``jax.sharding.Mesh`` and the same step is sharded over the 'data' axis
(gradients all-reduced by XLA over ICI) — see parallel/.
"""

from __future__ import annotations

import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterator import (
    ArrayDataSetIterator,
    AsyncDataSetIterator,
    DataSetIterator,
    DevicePrefetchIterator,
    ListDataSetIterator,
)
from deeplearning4j_tpu.nn.conf.core import MultiLayerConfiguration
from deeplearning4j_tpu.observability import goodput as _goodput
from deeplearning4j_tpu.observability import metrics as _obs_metrics
from deeplearning4j_tpu.observability import opindex as _opindex
from deeplearning4j_tpu.observability.trace import get_tracer as _get_tracer
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf import layers as layer_confs
from deeplearning4j_tpu.nn.conf.preprocessors import (
    CnnToFeedForward,
    FeedForwardToCnn,
    RnnToFeedForward,
)
from deeplearning4j_tpu.nn import precision
from deeplearning4j_tpu.nn.updater import apply_layer_updates


def _auto_preprocessor(input_type: InputType, conf):
    """Automatic shape-adapter insertion between mismatched layer families
    (parity: MultiLayerConfiguration setInputType preprocessor inference)."""
    kind = input_type.kind
    is_ff = isinstance(conf, layer_confs.FeedForwardLayerConfig)
    wants_cnn = getattr(conf, "expects_cnn_input", False)
    wants_rnn = getattr(conf, "expects_rnn_input", False)
    if kind == "convolutional" and is_ff and not wants_cnn and not wants_rnn:
        return CnnToFeedForward(input_type.height, input_type.width,
                                input_type.channels)
    if kind == "convolutional_flat" and wants_cnn:
        return FeedForwardToCnn(input_type.height, input_type.width,
                                input_type.channels)
    if kind == "recurrent" and is_ff and not wants_rnn and not wants_cnn:
        return RnnToFeedForward()
    return None


class MultiLayerNetwork:
    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layers = None          # runtime Layer objects
        self.preprocessors = None   # per-layer-index preprocessor or None
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

    # ------------------------------------------------------------------ init
    def init(self, seed: Optional[int] = None, *, structure_only: bool = False):
        """Build runtime layers and parameter/optimizer pytrees. With
        ``structure_only`` the trees are ShapeDtypeStructs (via jax.eval_shape)
        — used by clone()/restore, which overwrite every leaf anyway."""
        gc = self.conf.global_conf
        seed = gc.seed if seed is None else seed
        self._rng_key = jax.random.PRNGKey(seed)

        input_type = self.conf.input_type
        self.layers = []
        self.preprocessors = []
        resolved_confs = []
        for i, lc in enumerate(self.conf.layers):
            prep = self.conf.preprocessors.get(i)
            if prep is None and input_type is not None:
                prep = _auto_preprocessor(input_type, lc)
            if prep is not None and input_type is not None:
                input_type = prep.output_type(input_type)
            self.preprocessors.append(prep)
            if input_type is not None:
                lc = lc.with_n_in(input_type)
            if getattr(lc, "n_in", 1) is None:
                raise ValueError(
                    f"Layer {i} ({lc.layer_type}): n_in not set and no "
                    f"input_type provided for inference")
            if lc.name is None:
                lc = lc.replace(name=f"layer_{i}")
            resolved_confs.append(lc)
            layer = lc.make_layer(input_type, gc, gc.dtype)
            self.layers.append(layer)
            input_type = layer.output_type
        self._resolved_confs = resolved_confs

        # init params + state + per-layer optimizer state
        def init_trees(key):
            params, state = {}, {}
            for layer in self.layers:
                key_, sub = jax.random.split(key)
                key = key_
                p = layer.init_params(sub)
                if p:
                    params[layer.name] = p
                s = layer.init_state()
                if s:
                    state[layer.name] = s
            opt_state = {}
            for layer in self.layers:
                if layer.name in params:
                    upd = layer.resolve("updater")
                    opt_state[layer.name] = upd.init_state(params[layer.name])
            ls = precision.init_loss_scale_state(gc.dtype)
            if ls is not None:
                opt_state[precision.LOSS_SCALE_KEY] = ls
            return params, state, opt_state

        if structure_only:
            self.params, self.state, self.opt_state = jax.eval_shape(
                init_trees, self._rng_key)
        else:
            self.params, self.state, self.opt_state = init_trees(self._rng_key)
        self.iteration = 0
        self._train_step = None
        self._tbptt_step = None
        self._multi_steps = {}
        self._apply_fns = {}
        return self

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
        opt_state = {}
        for layer in self.layers:
            if layer.name in self.params:
                upd = layer.resolve("updater")
                opt_state[layer.name] = upd.init_state(self.params[layer.name])
        ls = precision.init_loss_scale_state(self.conf.global_conf.dtype)
        if ls is not None:
            opt_state[precision.LOSS_SCALE_KEY] = ls
        self.opt_state = opt_state

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
            self._train_step = None
            self._tbptt_step = None
            self._multi_steps = {}
        return self

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def add_listener(self, listener):
        self.listeners.append(listener)
        return self

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
        self._train_step = None
        self._tbptt_step = None
        self._multi_steps = {}
        self._apply_fns = {}
        return self

    # -------------------------------------------------------------- forward
    def _remat_spans(self, n: int) -> dict:
        """start index -> end index for maximal contiguous runs of layers
        whose names match the DL4J_TPU_REMAT prefixes (the chain-network
        rendering of ComputationGraph's block-granular selective remat —
        e.g. ``DL4J_TPU_REMAT=layer_`` remats every hidden layer, the
        long-sequence memory lever for stacked LSTMs)."""
        from deeplearning4j_tpu.nn.graph import (_remat_match,
                                                  _remat_prefixes)
        prefixes = (self.remat_prefixes if self.remat_prefixes is not None
                    else _remat_prefixes())
        spans = {}
        if not prefixes:
            return spans
        start = None
        for i in range(n):
            ok = (_remat_match(self.layers[i].name, prefixes)
                  and not hasattr(self.layers[i], "loss"))
            if ok and start is None:
                start = i
            elif not ok and start is not None:
                if i - start >= 1:
                    spans[start] = i
                start = None
        if start is not None and n - start >= 1:
            spans[start] = n
        return spans

    def _run_remat_span(self, i, end, params, state, x, fmask, rng, train):
        """Execute layers [i, end) under one jax.checkpoint: only the
        span's inputs are saved; interiors (e.g. an LSTM's per-timestep
        gate activations) are recomputed in the backward."""
        rngs = []
        for _ in range(i, end):
            lr = None
            if rng is not None:
                rng, lr = jax.random.split(rng)
            rngs.append(lr)
        sub = self.layers[i:end]
        p_sub = {ly.name: params.get(ly.name, {}) for ly in sub}
        s_sub = {ly.name: state.get(ly.name, {}) for ly in sub}

        def run_span(p_sub, s_sub, x, fmask, rngs):
            ns = {}
            for k, j in enumerate(range(i, end)):
                ly = self.layers[j]
                with _opindex.scope(ly.name):
                    if self.preprocessors[j] is not None:
                        x = self.preprocessors[j](x)
                    x, s_new = ly.apply(p_sub.get(ly.name, {}),
                                        s_sub.get(ly.name, {}), x,
                                        train=train, rng=rngs[k], mask=fmask)
                fmask = ly.feed_forward_mask(fmask)
                if s_new:
                    ns[ly.name] = s_new
            return x, fmask, ns

        return jax.checkpoint(run_span)(p_sub, s_sub, x, fmask, tuple(rngs)
                                        ), rng

    def _forward(self, params, state, x, *, train, rng, fmask=None,
                 to_layer: Optional[int] = None, collect=False):
        """Walk the stack; returns (final activation or list, new_state)."""
        acts = []
        new_state = dict(state)
        n = len(self.layers) if to_layer is None else to_layer
        # selective remat spans apply on plain training walks only
        # (collect needs every activation; eval has no backward)
        spans = self._remat_spans(n) if train and not collect else {}
        i = 0
        while i < n:
            end = spans.get(i)
            if end is not None:
                (x, fmask, ns), rng = self._run_remat_span(
                    i, end, params, state, x, fmask, rng, train)
                new_state.update(ns)
                i = end
                continue
            layer = self.layers[i]
            lrng = None
            if rng is not None:
                rng, lrng = jax.random.split(rng)
            p = params.get(layer.name, {})
            s = state.get(layer.name, {})
            with _opindex.scope(layer.name):
                if self.preprocessors[i] is not None:
                    x = self.preprocessors[i](x)
                x, s_new = layer.apply(p, s, x, train=train, rng=lrng,
                                       mask=fmask)
            fmask = layer.feed_forward_mask(fmask)
            if s_new:
                new_state[layer.name] = s_new
            if collect:
                acts.append(x)
            i += 1
        return (acts if collect else x), new_state

    def _loss(self, params, state, x, labels, fmask, lmask, rng, train=True):
        """Data loss + regularization: the scalar the jitted step autodiffs."""
        rng_fwd = lrng = None
        if rng is not None:
            rng_fwd, lrng = jax.random.split(rng)
        h, new_state = self._forward(params, state, x, train=train, rng=rng_fwd,
                                     fmask=fmask, to_layer=len(self.layers) - 1)
        out_layer = self.layers[-1]
        p_out = params.get(out_layer.name, {})
        # the output layer's own scope holds its matmul and data loss;
        # "loss" is what no layer owns: regularization and the sum
        with _opindex.scope(out_layer.name):
            if self.preprocessors[-1] is not None:
                h = self.preprocessors[-1](h)
            if getattr(out_layer, "loss_uses_state", False):
                s_out = state.get(out_layer.name, {})
                data_loss = out_layer.loss(p_out, h, labels, train=train,
                                           rng=lrng, mask=lmask, state=s_out)
                if train and hasattr(out_layer, "update_centers"):
                    new_state[out_layer.name] = out_layer.update_centers(
                        s_out, jax.lax.stop_gradient(h), labels, mask=lmask)
            else:
                data_loss = out_layer.loss(p_out, h, labels, train=train,
                                           rng=lrng, mask=lmask)
        with _opindex.scope("loss"):
            reg = jnp.zeros((), data_loss.dtype)
            for layer in self.layers:
                if layer.name in params:
                    reg = reg + layer.regularization(params[layer.name])
            return data_loss + reg, new_state

    # ---------------------------------------------------------- train step
    def _resolve_remat(self) -> tuple:
        """Read DL4J_TPU_REMAT exactly ONCE — when the first train step
        is built — and record the resolved prefixes on the model
        (``self.remat_prefixes``). The jitted step is cached, so a later
        env-var change can never take effect; resolving eagerly (and
        warning on a detected change) keeps remat experiments from
        silently measuring a stale configuration."""
        from deeplearning4j_tpu.nn.graph import _remat_prefixes
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
        gc = self.conf.global_conf

        def loss_fn(params, state, x, labels, fmask, lmask, rng):
            return self._loss(params, state, x, labels, fmask, lmask, rng)

        return precision.build_step_fn(loss_fn, self.layers, gc,
                                       self._lr_scale)

    def _build_train_step(self):
        step_fn = self._step_fn()
        if self._mesh is not None:
            from deeplearning4j_tpu.parallel.data_parallel import shard_step
            return shard_step(self, step_fn, *self._mesh)
        return jax.jit(step_fn, donate_argnums=(0, 1, 2))

    def fit_batch_repeated(self, ds: DataSet, n_steps: int):
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
        needs_tbptt = (self.conf.backprop_type == "tbptt"
                       and getattr(ds.features, "ndim", 0) == 3
                       and ds.features.shape[1] > self.conf.tbptt_fwd_length)
        if self._mesh is not None or needs_tbptt:
            # meshed execution needs shard_step's batch sharding/padding and
            # tbptt needs chunked backprop — both route through fit_batch
            # (n dispatches) to keep semantics identical
            for _ in range(n_steps):
                score = self.fit_batch(ds)
            return score
        from deeplearning4j_tpu.nn.multistep import get_multi_step
        jitted = get_multi_step(self, n_steps)
        self._rng_key, rng = jax.random.split(self._rng_key)
        x = jnp.asarray(ds.features)
        y = jnp.asarray(ds.labels)
        fmask = (None if ds.features_mask is None
                 else jnp.asarray(ds.features_mask))
        lmask = (None if ds.labels_mask is None
                 else jnp.asarray(ds.labels_mask))
        it = jnp.asarray(self.iteration, jnp.int32)
        self.params, self.state, self.opt_state, score = jitted(
            self.params, self.state, self.opt_state, it, x, y, fmask, lmask,
            rng)
        self.iteration += n_steps
        self.score_value = score
        self.last_batch_examples = ds.num_examples
        _goodput.observe_steps(n_steps)
        return score


    def step_cost_analysis(self, ds: DataSet) -> dict:
        """XLA cost-model numbers for ONE compiled train step on this
        batch shape: {"flops", "bytes_accessed"} (SURVEY.md §5.1 — feeds
        PerformanceListener(flops_per_step=...) for live MFU)."""
        self._require_init()
        if self._train_step is None:
            self._train_step = self._build_train_step()
        x = jnp.asarray(ds.features)
        y = jnp.asarray(ds.labels)
        it = jnp.asarray(self.iteration, jnp.int32)
        rng = jax.random.PRNGKey(0)
        from deeplearning4j_tpu.utils.perf import xla_step_cost
        return xla_step_cost(self._train_step, self.params, self.state,
                             self.opt_state, it, x, y, None, None, rng)

    def _require_init(self):
        if self.params is None:
            raise RuntimeError(
                "Network not initialized — call net.init() before "
                "fit()/output()/evaluate()")

    # ------------------------------------------------ recurrent state helpers
    def _set_streaming(self, flag: bool):
        from deeplearning4j_tpu.nn.layers.recurrent import set_streaming
        set_streaming(self.layers, flag)

    def _strip_carries(self, state):
        from deeplearning4j_tpu.nn.layers.recurrent import strip_carries
        return strip_carries(state)

    def rnn_clear_previous_state(self):
        """Reset streaming decode state (rnnClearPreviousState parity)."""
        self._rnn_state = None

    def rnn_time_step(self, x, mask=None):
        """Stateful streaming inference (MultiLayerNetwork.rnnTimeStep :2234):
        feed one step [b, f] or a chunk [b, t, f]; recurrent layers carry
        (h, c) across calls — attention layers carry their KV cache and
        per-row position. ``mask`` [b, t] marks real timesteps for
        right-padded one-shot prefill (the attention layers advance each
        row's position by its true length)."""
        self._require_init()
        x = jnp.asarray(x)
        single = x.ndim == 2
        if single:
            x = x[:, None, :]
        self._set_streaming(True)
        try:
            key = "stream" if mask is None else "stream_masked"
            if key not in self._apply_fns:
                def fn(params, state, xx, fmask=None):
                    return self._forward(params, state, xx, train=False,
                                         rng=None, fmask=fmask)
                self._apply_fns[key] = jax.jit(fn)
            state_in = getattr(self, "_rnn_state", None)
            if state_in is None:
                state_in = self.state
            if mask is None:
                out, new_state = self._apply_fns[key](self.params, state_in,
                                                      x)
            else:
                out, new_state = self._apply_fns[key](self.params, state_in,
                                                      x, jnp.asarray(mask))
            self._rnn_state = new_state
        finally:
            self._set_streaming(False)
        return out[:, 0, :] if single else out

    def _fit_tbptt(self, ds: DataSet):
        """Truncated BPTT (doTruncatedBPTT :1119): split the time axis into
        tbptt_fwd_length chunks; recurrent state carries across chunks inside
        the compiled step (via the state pytree) and resets per batch."""
        L = self.conf.tbptt_fwd_length
        x = jnp.asarray(ds.features)
        y = jnp.asarray(ds.labels)
        if y.ndim != 3 or y.shape[1] != x.shape[1]:
            raise ValueError(
                "tBPTT requires per-timestep labels [batch, time, out] with "
                f"the same time length as the features; got labels shape "
                f"{tuple(y.shape)} vs features {tuple(x.shape)}. For "
                "sequence-classification labels use backprop_type='standard'")
        fmask = None if ds.features_mask is None else jnp.asarray(ds.features_mask)
        lmask = None if ds.labels_mask is None else jnp.asarray(ds.labels_mask)
        self._set_streaming(True)
        try:
            if getattr(self, "_tbptt_step", None) is None:
                self._tbptt_step = self._build_train_step()
            t_total = x.shape[1]
            score_sum, weight = 0.0, 0
            _dev_span = _get_tracer().span("device_step", tbptt=True)
            _dev_span.__enter__()
            for start in range(0, t_total, L):
                sl = slice(start, min(start + L, t_total))
                self._rng_key, rng = jax.random.split(self._rng_key)
                it = jnp.asarray(self.iteration, jnp.int32)
                self.params, self.state, self.opt_state, chunk_score = \
                    self._tbptt_step(
                        self.params, self.state, self.opt_state, it,
                        x[:, sl], y[:, sl],
                        None if fmask is None else fmask[:, sl],
                        None if lmask is None else lmask[:, sl],
                        rng)
                w = sl.stop - sl.start
                # accumulate ON DEVICE: a float() here would sync the
                # pipeline once per chunk; consumers pull the final mean
                score_sum = score_sum + chunk_score * w
                weight += w
            _dev_span.__exit__(None, None, None)
            self.state = self._strip_carries(self.state)
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

    def _maybe_derive_flops(self, x, y, fmask, lmask):
        """Auto-derive per-step FLOPs from the XLA cost model on the
        *lowered* train step — tracing only, no second backend compile —
        the first time each (train-step, batch-shapes) pair is seen.
        Feeds live dl4j_mfu / dl4j_flops_per_second with zero user
        wiring; DL4J_TPU_AUTO_FLOPS=0 opts out."""
        if not _goodput.auto_flops_enabled():
            return
        key = (id(self._train_step), tuple(x.shape), tuple(y.shape),
               None if fmask is None else tuple(fmask.shape),
               None if lmask is None else tuple(lmask.shape))
        if getattr(self, "_flops_key", None) == key:
            return
        self._flops_key = key
        with _get_tracer().span("flops_derive"):
            try:
                if self._train_step is None:
                    self._train_step = self._build_train_step()
                from deeplearning4j_tpu.utils.perf import (
                    xla_step_cost_lowered,
                )
                it = jnp.asarray(self.iteration, jnp.int32)
                rng = jax.random.PRNGKey(0)
                cost = xla_step_cost_lowered(
                    self._train_step, self.params, self.state,
                    self.opt_state, it, x, y, fmask, lmask, rng)
                self.flops_per_step = cost["flops"] or None
            except NotImplementedError:
                # meshed/wrapped steps have no .lower
                self.flops_per_step = None
        _goodput.observe_flops(self.flops_per_step)

    def fit_batch(self, ds: DataSet):
        """One optimization step on one minibatch (Model.fit parity)."""
        self._require_init()
        if (self.conf.backprop_type == "tbptt"
                and getattr(ds.features, "ndim", 0) == 3
                and ds.features.shape[1] > self.conf.tbptt_fwd_length):
            return self._fit_tbptt(ds)
        if self._train_step is None:
            self._train_step = self._build_train_step()
        else:
            self._resolve_remat()  # warn if DL4J_TPU_REMAT changed since
        tracer = _get_tracer()
        with tracer.span("host_dispatch"):
            self._rng_key, rng = jax.random.split(self._rng_key)
            x = jnp.asarray(ds.features)
            y = jnp.asarray(ds.labels)
            fmask = None if ds.features_mask is None else jnp.asarray(ds.features_mask)
            lmask = None if ds.labels_mask is None else jnp.asarray(ds.labels_mask)
            it = jnp.asarray(self.iteration, jnp.int32)
        with tracer.span("device_step"):
            args = (self.params, self.state, self.opt_state, it, x, y, fmask,
                    lmask, rng)
            if self._mesh is None:  # a meshed step registers its inner jit
                _opindex.register(self._train_step, args, args[4:8])
            self.params, self.state, self.opt_state, score = self._train_step(
                *args)
        self.iteration += 1
        self.score_value = score
        self.last_batch_examples = ds.num_examples
        _goodput.observe_steps(1)
        # after the dispatch: self.params holds fresh (undonated) outputs
        # and x/y were not donated, so lowering for cost analysis is safe
        self._maybe_derive_flops(x, y, fmask, lmask)
        if self.listeners:
            t0 = time.perf_counter()
            for l in self.listeners:
                l.iteration_done(self, self.iteration, self.epoch)
            t1 = time.perf_counter()
            tracer.record("score_sync", t0, t1)
            _obs_metrics.observe_dispatch_lag(t1 - t0)
        return score

    def fit(self, data, labels=None, *, epochs: int = 1, batch_size: int = 32,
            async_prefetch: bool = True, device_prefetch="auto",
            multi_step="auto"):
        """Train. Accepts a DataSetIterator, a DataSet, or (features, labels)
        arrays (MultiLayerNetwork.fit overloads parity; iterator is wrapped
        in an async prefetcher like MultiLayerNetwork.java:951).

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
        if isinstance(data, DataSetIterator):
            it = data
        elif isinstance(data, DataSet):
            it = ListDataSetIterator([data])
        else:
            it = ArrayDataSetIterator(data, labels, batch_size=batch_size)
        chunk = self._resolve_multi_step(multi_step)
        device_prefetch = self._resolve_device_prefetch(device_prefetch)
        _obs_metrics.install_runtime_metrics()
        from deeplearning4j_tpu.compilecache import ensure_configured
        ensure_configured()  # JAX_COMPILATION_CACHE_DIR, if set
        tracer = _get_tracer()
        ledger = _goodput.start_run("fit", net=self)
        from deeplearning4j_tpu.observability import distributed as _obs_dist
        _obs_dist.stamp_run_marker("fit")
        status = "completed"
        try:
            for epoch in range(epochs):
                source = AsyncDataSetIterator(it) if async_prefetch else it
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
                if not getattr(it, "auto_epochs", False):
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

    _FIT_CHUNK_DEFAULT = 8

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
            s = (tuple(ds.features.shape), tuple(ds.labels.shape),
                 None if ds.features_mask is None
                 else tuple(ds.features_mask.shape),
                 None if ds.labels_mask is None
                 else tuple(ds.labels_mask.shape))
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
            xs = jnp.stack([jnp.asarray(b.features) for b in batches])
            ys = jnp.stack([jnp.asarray(b.labels) for b in batches])
            fmask = (None if batches[0].features_mask is None else
                     jnp.stack([jnp.asarray(b.features_mask) for b in batches]))
            lmask = (None if batches[0].labels_mask is None else
                     jnp.stack([jnp.asarray(b.labels_mask) for b in batches]))
            it0 = jnp.asarray(self.iteration, jnp.int32)
            steps = jnp.arange(len(batches), dtype=jnp.int32)
        with tracer.span("device_step", steps=len(batches)):
            args = (self.params, self.state, self.opt_state, it0,
                    self._rng_key, steps, (xs, ys, fmask, lmask))
            _opindex.register(jitted, args, args[6])
            (self.params, self.state, self.opt_state, self._rng_key,
             scores) = jitted(*args)
        start = self.iteration
        self.iteration += len(batches)
        self.score_value = scores[-1]
        self.last_batch_examples = batches[-1].num_examples
        _goodput.observe_steps(len(batches))  # one dispatch, k real steps
        # pre-stack arrays already have the per-step shape; slicing the
        # stacked device arrays here would dispatch (and first-call
        # compile) an XLA gather outside the flops_derive span
        b0 = batches[0]
        self._maybe_derive_flops(b0.features, b0.labels,
                                 b0.features_mask, b0.labels_mask)
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

    # ------------------------------------------------------------- pretrain
    def pretrain(self, data, *, epochs: int = 1, batch_size: int = 32):
        """Layer-wise unsupervised pretraining (MultiLayerNetwork.pretrain
        :963): each pretrainable layer (VAE/AutoEncoder/RBM) trains on the
        activations of the layers below it."""
        self._require_init()
        if isinstance(data, DataSetIterator):
            it = data
        elif isinstance(data, DataSet):
            it = ListDataSetIterator([data])
        else:
            it = ArrayDataSetIterator(data, None, batch_size=batch_size)
        for i, layer in enumerate(self.layers):
            if getattr(layer, "is_pretrainable", False):
                self.pretrain_layer(i, it, epochs=epochs)
        return self

    def pretrain_layer(self, idx: int, iterator, *, epochs: int = 1):
        """Pretrain one layer on its (preprocessed) input activations; the
        loss is the layer's own unsupervised objective
        (pretrain_loss: -ELBO for VAE, reconstruction for AE, CD free-energy
        difference for RBM), compiled into one jitted step."""
        layer = self.layers[idx]
        if not getattr(layer, "is_pretrainable", False):
            raise ValueError(f"Layer {idx} ({layer.conf.layer_type}) is not "
                             f"pretrainable")
        gc = self.conf.global_conf
        name = layer.name

        def step(params, opt_state, itc, x, rng):
            def loss_fn(p):
                return layer.pretrain_loss(p[name], x, rng)
            loss, grads = jax.value_and_grad(loss_fn)(params)
            new_params, new_opt = apply_layer_updates(
                [layer], gc, params, grads, opt_state, itc)
            return new_params, new_opt, loss

        jitted = jax.jit(step, donate_argnums=(0, 1))
        # material copies: the jitted step donates these buffers, and the
        # net's own trees must never alias donated (deleted) arrays — an
        # exception mid-loop would otherwise corrupt the whole net
        params_sub = {name: jax.tree_util.tree_map(jnp.copy,
                                                   self.params[name])}
        opt_sub = {name: jax.tree_util.tree_map(jnp.copy,
                                                self.opt_state[name])}
        last = None
        iteration = self.iteration
        for _ in range(epochs):
            for ds in iterator:
                x = jnp.asarray(ds.features)
                # activations of the stack below + this layer's preprocessor
                if idx > 0:
                    x, _ = self._forward(self.params, self.state, x,
                                         train=False, rng=None, to_layer=idx)
                if self.preprocessors[idx] is not None:
                    x = self.preprocessors[idx](x)
                self._rng_key, rng = jax.random.split(self._rng_key)
                itc = jnp.asarray(iteration, jnp.int32)
                params_sub, opt_sub, last = jitted(params_sub, opt_sub, itc,
                                                   x, rng)
                iteration += 1
            iterator.reset()
        self.iteration = iteration
        self.params = {**self.params, name: params_sub[name]}
        self.opt_state = {**self.opt_state, name: opt_sub[name]}
        self.score_value = last
        return self

    # ------------------------------------------------------------ inference
    def _get_apply(self, collect=False, train=False):
        key = (collect, train)
        if key not in self._apply_fns:
            def apply_fn(params, state, x, rng, fmask):
                out, _ = self._forward(params, state, x, train=train, rng=rng,
                                       fmask=fmask, collect=collect)
                return out
            self._apply_fns[key] = jax.jit(apply_fn)
        return self._apply_fns[key]

    def _inference_rng(self, train):
        if not train:
            return None
        self._rng_key, rng = jax.random.split(self._rng_key)
        return rng

    def output(self, x, train: bool = False, mask=None):
        """Forward pass -> final layer activations
        (MultiLayerNetwork.output :1512). ``mask`` is the per-timestep
        features mask for variable-length sequences."""
        self._require_init()
        fn = self._get_apply(collect=False, train=train)
        return fn(self.params, self.state, jnp.asarray(x),
                  self._inference_rng(train),
                  None if mask is None else jnp.asarray(mask))

    def feed_forward(self, x, train: bool = False, mask=None) -> List[jnp.ndarray]:
        """All layer activations (feedForward :675)."""
        self._require_init()
        fn = self._get_apply(collect=True, train=train)
        return fn(self.params, self.state, jnp.asarray(x),
                  self._inference_rng(train),
                  None if mask is None else jnp.asarray(mask))

    def score(self, ds: DataSet, train: bool = False):
        """Loss on one dataset (MultiLayerNetwork.score parity)."""
        self._require_init()
        loss, _ = self._loss(
            self.params, self.state, jnp.asarray(ds.features),
            jnp.asarray(ds.labels),
            None if ds.features_mask is None else jnp.asarray(ds.features_mask),
            None if ds.labels_mask is None else jnp.asarray(ds.labels_mask),
            rng=None, train=train)
        return float(loss)

    def evaluate(self, iterator):
        """Classification evaluation over an iterator (evaluate :2413)."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        ev = Evaluation()
        if isinstance(iterator, DataSet):
            iterator = ListDataSetIterator([iterator])
        for ds in iterator:
            out = np.asarray(self.output(ds.features, mask=ds.features_mask))
            ev.eval(ds.labels, out, mask=ds.labels_mask)
        return ev

    def evaluate_regression(self, iterator):
        from deeplearning4j_tpu.eval.regression import RegressionEvaluation
        ev = RegressionEvaluation()
        if isinstance(iterator, DataSet):
            iterator = ListDataSetIterator([iterator])
        for ds in iterator:
            out = np.asarray(self.output(ds.features, mask=ds.features_mask))
            ev.eval(ds.labels, out, mask=ds.labels_mask)
        return ev

    # ---------------------------------------------------------------- misc
    def num_params(self) -> int:
        return sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(self.params))

    def summary(self) -> str:
        lines = ["=" * 70]
        lines.append(f"{'name':<18}{'type':<16}{'out type':<22}{'params':>10}")
        lines.append("-" * 70)
        for layer in self.layers:
            p = self.params.get(layer.name, {})
            n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(p))
            lines.append(
                f"{layer.name:<18}{layer.conf.layer_type:<16}"
                f"{str(layer.output_type.kind):<22}{n:>10}")
        lines.append("-" * 70)
        lines.append(f"total params: {self.num_params()}")
        lines.append("=" * 70)
        return "\n".join(lines)

    def clone(self):
        """Deep copy (Model.clone parity) — used by transfer learning.
        Leaves are materially copied (jnp.copy): the jitted train step
        donates its input buffers, so an aliasing clone would be invalidated
        by the next fit_batch on either net."""
        net = MultiLayerNetwork(self.conf)
        net.init(structure_only=True)
        net.params = jax.tree_util.tree_map(jnp.copy, self.params)
        net.state = jax.tree_util.tree_map(jnp.copy, self.state)
        net.opt_state = jax.tree_util.tree_map(jnp.copy, self.opt_state)
        net.iteration = self.iteration
        net.epoch = self.epoch
        return net
