"""ComputationGraph — DAG network with multi-input/multi-output training.

Parity: nn/graph/ComputationGraph.java (2,447 LoC): init() :273,
topologicalSortOrder() :888 (here on the config), feedForward :1089 (walk
topo order), calcBackpropGradients :1224 (here JAX autodiff through the DAG
— fan-in epsilon accumulation falls out of reverse-mode AD), fit :701.

Like MultiLayerNetwork, ``fit`` compiles ONE jitted train step (forward over
the whole DAG + loss sum over output layers + backward + updaters fused into
a single XLA program). Multi-output losses are summed (the reference
accumulates output-layer scores the same way).
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.observability import goodput as _goodput
from deeplearning4j_tpu.observability import metrics as _obs_metrics
from deeplearning4j_tpu.observability import opindex as _opindex
from deeplearning4j_tpu.observability.trace import get_tracer as _get_tracer
from deeplearning4j_tpu.datasets.iterator import DataSetIterator
from deeplearning4j_tpu.nn.conf.graph_conf import ComputationGraphConfiguration
from deeplearning4j_tpu.nn.conf.layers import BaseLayerConfig
from deeplearning4j_tpu.nn import precision
from deeplearning4j_tpu.nn.updater import apply_layer_updates

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


class ComputationGraph:
    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self.topo = conf.topological_order()
        self.layers = None          # runtime layer objects (layer vertices)
        self.vertex_kind = None     # name -> "layer" | "vertex"
        self.params = None
        self.state = None
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

    def set_lr_scale(self, scale: float):
        """Scale every layer's scheduled learning rate by ``scale`` from
        the next step on (resilience/supervisor.py backs off the rate
        after a NaN rollback). Baked into the compiled step — every
        cached step variant is invalidated, so expect one recompile per
        change."""
        scale = float(scale)
        if scale <= 0.0:
            raise ValueError(f"lr scale must be > 0, got {scale}")
        if scale != self._lr_scale:
            self._lr_scale = scale
            self._train_step = None
            self._tbptt_step = None
            self._multi_steps = {}
        return self

    def resilient_fit(self, data, labels=None, *, checkpoint_dir: str,
                      epochs: int = 1, batch_size: int = 32, **supervisor_kw):
        """Supervised ``fit`` with checkpoint/resume, retry, NaN rollback
        and preemption handling — see resilience/supervisor.py."""
        from deeplearning4j_tpu.resilience import resilient_fit
        return resilient_fit(self, data, labels,
                             checkpoint_dir=checkpoint_dir, epochs=epochs,
                             batch_size=batch_size, **supervisor_kw)

    # ------------------------------------------------------------------ init
    def init(self, seed: Optional[int] = None, *, structure_only: bool = False):
        gc = self.conf.global_conf
        seed = gc.seed if seed is None else seed
        self._rng_key = jax.random.PRNGKey(seed)

        # resolve InputTypes through the DAG
        input_types: Dict[str, object] = {}
        if self.conf.input_types is not None:
            for name, it in zip(self.conf.network_inputs, self.conf.input_types):
                input_types[name] = it

        self.layers = []
        self._layer_by_name = {}
        self.vertex_kind = {}
        self._resolved_confs = {}
        for name in self.topo:
            conf = self.conf.vertices[name]
            in_names = self.conf.vertex_inputs[name]
            in_types = [input_types.get(i) for i in in_names]
            if isinstance(conf, BaseLayerConfig):
                self.vertex_kind[name] = "layer"
                if len(in_names) != 1:
                    raise ValueError(
                        f"Layer vertex '{name}' must have exactly 1 input, "
                        f"got {in_names} (merge first — MergeVertex)")
                it = in_types[0]
                if it is not None:
                    conf = conf.with_n_in(it)
                if getattr(conf, "n_in", 1) is None:
                    raise ValueError(
                        f"Layer vertex '{name}': n_in not set and no "
                        f"input type available for inference")
                layer = conf.make_layer(it, gc, gc.dtype)
                self.layers.append(layer)
                self._layer_by_name[name] = layer
                self._resolved_confs[name] = conf
                input_types[name] = layer.output_type
            else:
                self.vertex_kind[name] = "vertex"
                self._resolved_confs[name] = conf
                if all(t is not None for t in in_types):
                    input_types[name] = conf.output_type(*in_types)
                else:
                    input_types[name] = None

        # block-fusion pass: pattern-match bottleneck tails on the RESOLVED
        # configs (nn/fusion.py); applied in _walk for training walks only
        from deeplearning4j_tpu.nn import fusion as _fusion
        self._fusion_plans = _fusion.find_fusable_chains(
            self._resolved_confs, self.conf.vertex_inputs,
            self.conf.network_outputs,
            default_activation=gc.activation or "sigmoid")
        self._fusion_interior = _fusion.interior_vertices(self._fusion_plans)

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
        self._rnn_state = None
        return self

    def materialize_state(self):
        state = {}
        for layer in self.layers:
            s = layer.init_state()
            if s:
                state[layer.name] = s
        self.state = state

    def materialize_opt_state(self):
        opt_state = {}
        for layer in self.layers:
            if layer.name in self.params:
                upd = layer.resolve("updater")
                opt_state[layer.name] = upd.init_state(self.params[layer.name])
        ls = precision.init_loss_scale_state(self.conf.global_conf.dtype)
        if ls is not None:
            opt_state[precision.LOSS_SCALE_KEY] = ls
        self.opt_state = opt_state

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def use_mesh(self, mesh, data_axis: str = "data",
                 model_axis: str | None = None, tp_rules=None):
        """Sharded training over a Mesh: data-parallel by default;
        ``model_axis`` additionally shards weights column-parallel over
        that axis (dp x tp — see parallel/tensor.py)."""
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
        moving a single leaf (see MultiLayerNetwork._mark_meshed — the
        elastic restore path in utils/checkpoint.py places leaves
        directly into their target NamedShardings first)."""
        self._mesh = (mesh, data_axis)
        self._mesh_detail = {"model_axis": model_axis, "tp_rules": tp_rules}
        self._train_step = None
        self._tbptt_step = None
        self._multi_steps = {}
        self._apply_fns = {}
        self._rnn_state = None
        return self


    def step_cost_analysis(self, mds) -> dict:
        """XLA cost-model numbers for ONE compiled train step on this
        batch shape: {"flops", "bytes_accessed"} (feeds
        PerformanceListener(flops_per_step=...) for live MFU)."""
        self._require_init()
        mds = self._coerce(mds)
        if self._train_step is None:
            self._train_step = self._build_train_step()
        from deeplearning4j_tpu.utils.perf import xla_step_cost
        inputs, fmasks = self._prepare_inputs(mds.features,
                                              mds.features_masks)
        labels = [jnp.asarray(l) for l in mds.labels]
        it = jnp.asarray(self.iteration, jnp.int32)
        rng = jax.random.PRNGKey(0)
        return xla_step_cost(self._train_step, self.params, self.state,
                             self.opt_state, it, inputs, labels, fmasks,
                             None, rng)

    def _require_init(self):
        if self.params is None:
            raise RuntimeError("Call init() before fit()/output()/evaluate()")

    # -------------------------------------------------- selective remat
    def _remat_spans(self, prefixes, skip: set) -> Dict[str, list]:
        """Maximal contiguous topo runs of prefix-matching vertices,
        keyed by first vertex. Excludes loss-bearing layers, vertices the
        caller needs inputs of, and the named-input rnn vertices (their
        mask wiring is not replicated inside a span)."""
        from deeplearning4j_tpu.nn.conf.vertices import (
            DuplicateToTimeSeriesVertex, LastTimeStepVertex)
        spans: Dict[str, list] = {}
        run: list = []

        def close():
            if run:
                spans[run[0]] = list(run)
                run.clear()

        for name in self.topo:
            conf = self._resolved_confs[name]
            layer = self._layer_by_name.get(name)
            ok = (_remat_match(name, prefixes)
                  and name not in skip
                  and not (layer is not None and hasattr(layer, "loss"))
                  and not isinstance(conf, (LastTimeStepVertex,
                                            DuplicateToTimeSeriesVertex)))
            if ok:
                run.append(name)
            else:
                close()
        close()
        return spans

    def _span_ext_inputs(self, span: list) -> list:
        span_set = set(span)
        ext = []
        for v in span:
            for src in self.conf.vertex_inputs[v]:
                if src not in span_set and src not in ext:
                    ext.append(src)
        return ext

    def _run_remat_span(self, span, params, state, acts, masks, new_state,
                        rng):
        """Execute one contiguous vertex span under jax.checkpoint.
        Mutates acts/masks/new_state; returns (advanced rng, span len)."""
        span_set = set(span)
        ext = {src: acts[src] for src in self._span_ext_inputs(span)}
        # span outputs: vertices consumed outside the span (or network
        # outputs); these are the only activations that leave the
        # checkpoint boundary — everything interior is recomputed
        consumed_outside = set(self.conf.network_outputs)
        for v, ins in self.conf.vertex_inputs.items():
            if v not in span_set:
                consumed_outside.update(ins)
        outs = [v for v in span if v in consumed_outside] or [span[-1]]
        rngs = {}
        if rng is not None:
            for v in span:
                if self.vertex_kind[v] == "layer":
                    rng, lr = jax.random.split(rng)
                    rngs[v] = lr
        p_sub = {v: params[v] for v in span if v in params}
        s_sub = {v: state[v] for v in span if v in state}

        def run_span(p_sub, s_sub, ext, rngs):
            local = dict(ext)
            ns = {}
            for v in span:
                conf = self._resolved_confs[v]
                xs = [local[i] for i in self.conf.vertex_inputs[v]]
                if self.vertex_kind[v] == "layer":
                    layer = self._layer_by_name[v]
                    with _opindex.scope(v):
                        y, s_new = layer.apply(
                            p_sub.get(v, {}), s_sub.get(v, {}), xs[0],
                            train=True, rng=rngs.get(v), mask=None)
                    if s_new:
                        ns[v] = s_new
                    local[v] = y
                else:
                    with _opindex.scope(v):
                        local[v] = conf.forward(*xs, masks=[None] * len(xs))
            return {v: local[v] for v in outs}, ns

        out_acts, ns = jax.checkpoint(run_span)(p_sub, s_sub, ext, rngs)
        acts.update(out_acts)
        for v in span:
            masks[v] = None
        new_state.update(ns)
        return rng, len(span)

    # -------------------------------------------------------------- forward
    def _walk(self, params, state, inputs: Dict, *, train, rng,
              fmasks: Optional[Dict] = None, need_inputs_of=()):
        """Walk the DAG in topo order. Returns (activations dict, per-vertex
        input activations for ``need_inputs_of``, masks dict, new_state)."""
        acts = dict(inputs)
        masks = dict(fmasks or {})
        saved_inputs = {}
        new_state = dict(state)
        from deeplearning4j_tpu.nn.conf.vertices import (
            DuplicateToTimeSeriesVertex, LastTimeStepVertex)
        # training walks route matched bottleneck tails through the fused
        # op (nn/fusion.py); eval walks use the per-vertex path (running
        # statistics, no batch stats)
        plans = getattr(self, "_fusion_plans", None) or {}
        if not train:
            plans = {}
        interior = self._fusion_interior if plans else frozenset()
        # selective block remat: maximal contiguous topo runs of vertices
        # matching DL4J_TPU_REMAT prefixes execute under one
        # jax.checkpoint (span inputs saved, interiors recomputed in the
        # backward). Plain path only: fusion plans and masked inputs
        # fall back to inline execution.
        remat = ((self.remat_prefixes if self.remat_prefixes is not None
                  else _remat_prefixes()) if train else ())
        spans = (self._remat_spans(remat, set(need_inputs_of))
                 if remat and not plans else {})
        topo_i = 0
        topo = self.topo
        while topo_i < len(topo):
            name = topo[topo_i]
            span = spans.get(name)
            if span is not None and not any(
                    masks.get(e) is not None
                    for e in self._span_ext_inputs(span)):
                rng, step = self._run_remat_span(
                    span, params, state, acts, masks, new_state, rng)
                topo_i += step
                continue
            topo_i += 1
            if name in interior:
                continue
            if name in plans:
                from deeplearning4j_tpu.nn import fusion as _fusion
                fb = plans[name]
                with _opindex.scope(name):
                    y, bn_state_new = _fusion.execute_fused_tail(
                        fb, self, params, state, acts)
                acts[name] = y
                masks[name] = None
                new_state[fb.bn] = bn_state_new
                continue
            conf = self._resolved_confs[name]
            in_names = self.conf.vertex_inputs[name]
            xs = [acts[i] for i in in_names]
            in_masks = [masks.get(i) for i in in_names]
            # named-input wiring for the rnn vertices (reference API:
            # LastTimeStepVertex(maskArrayInput), DuplicateToTimeSeriesVertex
            # (inputName)) — the named vertex supplies the mask / time length
            if isinstance(conf, LastTimeStepVertex) and conf.mask_input:
                in_masks = [masks.get(conf.mask_input)]
            if (isinstance(conf, DuplicateToTimeSeriesVertex)
                    and conf.seq_input):
                xs = [xs[0], acts[conf.seq_input]]
                in_masks = [in_masks[0], masks.get(conf.seq_input)]
            if name in need_inputs_of:
                saved_inputs[name] = (xs, in_masks)
            if self.vertex_kind[name] == "layer":
                layer = self._layer_by_name[name]
                lrng = None
                if rng is not None:
                    rng, lrng = jax.random.split(rng)
                p = params.get(name, {})
                s = state.get(name, {})
                with _opindex.scope(name):
                    y, s_new = layer.apply(p, s, xs[0], train=train,
                                           rng=lrng, mask=in_masks[0])
                if s_new:
                    new_state[name] = s_new
                acts[name] = y
                masks[name] = layer.feed_forward_mask(in_masks[0])
            else:
                with _opindex.scope(name):
                    acts[name] = conf.forward(*xs, masks=in_masks)
                masks[name] = conf.feed_forward_mask(*in_masks)
        return acts, saved_inputs, masks, new_state

    def _prepare_inputs(self, features: List, fmasks: Optional[List]):
        inputs = {n: jnp.asarray(f)
                  for n, f in zip(self.conf.network_inputs, features)}
        md = {}
        if fmasks is not None:
            for n, m in zip(self.conf.network_inputs, fmasks):
                if m is not None:
                    md[n] = jnp.asarray(m)
        return inputs, md

    def _loss(self, params, state, inputs, labels, fmasks, lmasks, rng,
              train=True):
        """Sum of output-layer losses + regularization (the scalar the
        jitted step autodiffs)."""
        rng_fwd = lrng = None
        if rng is not None:
            rng_fwd, lrng = jax.random.split(rng)
        outs = self.conf.network_outputs
        acts, saved, masks, new_state = self._walk(
            params, state, inputs, train=train, rng=rng_fwd, fmasks=fmasks,
            need_inputs_of=set(outs))
        total = None
        for i, name in enumerate(outs):
            layer = self._layer_by_name.get(name)
            if layer is None or not hasattr(layer, "loss"):
                raise ValueError(
                    f"Network output '{name}' is not a loss-bearing layer "
                    f"(Output/RnnOutput/LossLayer)")
            xs, in_masks = saved[name]
            this_rng = None
            if lrng is not None:
                lrng, this_rng = jax.random.split(lrng)
            lm = None if lmasks is None else lmasks[i]
            # the output layer's own scope holds its matmul and data loss;
            # "loss" is what no layer owns: regularization and the sum
            with _opindex.scope(name):
                if getattr(layer, "loss_uses_state", False):
                    s_out = state.get(name, {})
                    l = layer.loss(params.get(name, {}), xs[0], labels[i],
                                   train=train, rng=this_rng, mask=lm,
                                   state=s_out)
                    if train and hasattr(layer, "update_centers"):
                        new_state[name] = layer.update_centers(
                            s_out, jax.lax.stop_gradient(xs[0]), labels[i],
                            mask=lm)
                else:
                    l = layer.loss(params.get(name, {}), xs[0], labels[i],
                                   train=train, rng=this_rng, mask=lm)
            total = l if total is None else total + l
        with _opindex.scope("loss"):
            for layer in self.layers:
                if layer.name in params:
                    total = total + layer.regularization(params[layer.name])
        return total, new_state

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
        gc = self.conf.global_conf

        def loss_fn(params, state, inputs, labels, fmasks, lmasks, rng):
            return self._loss(params, state, inputs, labels, fmasks, lmasks,
                              rng)

        return precision.build_step_fn(loss_fn, self.layers, gc,
                                       self._lr_scale)

    def _build_train_step(self):
        step_fn = self._step_fn()
        if self._mesh is not None:
            from deeplearning4j_tpu.parallel.data_parallel import (
                shard_step_multi)
            return shard_step_multi(self, step_fn, *self._mesh)
        return jax.jit(step_fn, donate_argnums=(0, 1, 2))

    def fit_batch_repeated(self, mds, n_steps: int):
        """Run ``n_steps`` optimization steps on one minibatch inside a
        SINGLE XLA execution (``lax.scan`` over the fused train step) —
        one host dispatch instead of n. See
        MultiLayerNetwork.fit_batch_repeated."""
        self._require_init()
        if n_steps < 1:
            raise ValueError(f"n_steps must be >= 1, got {n_steps}")
        mds = self._coerce(mds)
        if self._mesh is not None or self.conf.backprop_type == "tbptt":
            # meshed execution needs shard_step_multi's batch handling;
            # tbptt needs chunked backprop — both route through fit_batch
            # (n dispatches) to keep semantics identical
            for _ in range(n_steps):
                score = self.fit_batch(mds)
            return score
        from deeplearning4j_tpu.nn.multistep import get_multi_step
        jitted = get_multi_step(self, n_steps)
        self._rng_key, rng = jax.random.split(self._rng_key)
        inputs, fmasks = self._prepare_inputs(mds.features, mds.features_masks)
        labels = [jnp.asarray(l) for l in mds.labels]
        lmasks = [None if m is None else jnp.asarray(m)
                  for m in mds.labels_masks]
        if all(m is None for m in lmasks):
            lmasks = None
        it = jnp.asarray(self.iteration, jnp.int32)
        self.params, self.state, self.opt_state, score = jitted(
            self.params, self.state, self.opt_state, it, inputs, labels,
            fmasks, lmasks, rng)
        self.iteration += n_steps
        self.score_value = score
        _goodput.observe_steps(n_steps)
        return score

    @staticmethod
    def _coerce(data) -> MultiDataSet:
        if isinstance(data, MultiDataSet):
            return data
        if isinstance(data, DataSet):
            return MultiDataSet.from_dataset(data)
        raise TypeError(f"Expected DataSet or MultiDataSet, got {type(data)}")

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

    def rnn_time_step(self, *features, masks=None):
        """Stateful streaming inference (ComputationGraph.rnnTimeStep
        parity): feed one step [b, f] or a chunk [b, t, f] per network
        input; recurrent layer vertices carry (h, c) across calls."""
        self._require_init()
        feats = [jnp.asarray(f) for f in features]
        # single-step mode: no input carries a time axis. Recurrent-typed
        # inputs are expanded to [b, 1, f]; static 2d inputs (e.g. the
        # non-sequence side of DuplicateToTimeSeries) are left alone.
        single = all(f.ndim == 2 for f in feats)
        if single:
            # untyped inputs default to time-series (matching the
            # MultiLayerNetwork behavior); only inputs explicitly typed
            # non-recurrent (e.g. the static side of
            # DuplicateToTimeSeries) stay 2d
            its = self.conf.input_types or [None] * len(feats)
            feats = [f[:, None, :]
                     if (it is None or it.kind == "recurrent")
                     else f
                     for f, it in zip(feats, its)]
        self._set_streaming(True)
        try:
            key = "stream"
            if key not in self._apply_fns:
                def fn(params, state, inputs, fmasks):
                    acts, _, _, new_state = self._walk(
                        params, state, inputs, train=False, rng=None,
                        fmasks=fmasks)
                    return (tuple(acts[o]
                                  for o in self.conf.network_outputs),
                            new_state)
                self._apply_fns[key] = jax.jit(fn)
            inputs, fmasks = self._prepare_inputs(feats, masks)
            state_in = getattr(self, "_rnn_state", None)
            if state_in is None:
                state_in = self.state
            outs, new_state = self._apply_fns[key](self.params, state_in,
                                                   inputs, fmasks)
            self._rnn_state = new_state
        finally:
            self._set_streaming(False)
        if single:
            outs = tuple(o[:, 0, :] if o.ndim == 3 else o for o in outs)
        return outs[0] if len(outs) == 1 else outs

    # ------------------------------------------------------------- training
    def _fit_tbptt(self, mds):
        """Truncated BPTT on the DAG (ComputationGraphConfiguration tBPTT /
        ComputationGraph.doTruncatedBPTT parity): split the time axis of
        every time-series input/label into tbptt_fwd_length chunks;
        recurrent vertices carry (h, c) across chunks via the state pytree,
        reset per batch. Static (2d) inputs are fed whole to every chunk."""
        L = self.conf.tbptt_fwd_length
        feats = [jnp.asarray(f) for f in mds.features]
        labels = [jnp.asarray(l) for l in mds.labels]
        if any(l.ndim == 2 for l in labels):
            raise ValueError(
                "tBPTT requires per-timestep labels [batch, time, out]; got "
                "a 2d (sequence-classification) label — use "
                "backprop_type='standard' for sequence classification")
        t_lens = {f.shape[1] for f in feats if f.ndim == 3}
        t_lens |= {l.shape[1] for l in labels if l.ndim == 3}
        if len(t_lens) != 1:
            raise ValueError(
                "tBPTT requires all time-series inputs AND per-timestep "
                "labels to share one time length; got time lengths "
                f"{sorted(t_lens)} (sequence-classification labels need "
                "backprop_type='standard')")
        t_total = t_lens.pop()
        fmasks = [None if m is None else jnp.asarray(m)
                  for m in mds.features_masks]
        lmasks = [None if m is None else jnp.asarray(m)
                  for m in mds.labels_masks]

        def chunk(a, sl, time_like):
            if a is None:
                return None
            return a[:, sl] if time_like(a) else a

        self._set_streaming(True)
        try:
            if getattr(self, "_tbptt_step", None) is None:
                self._tbptt_step = self._build_train_step()
            score_sum, weight = 0.0, 0
            _dev_span = _get_tracer().span("device_step", tbptt=True)
            _dev_span.__enter__()
            for start in range(0, t_total, L):
                sl = slice(start, min(start + L, t_total))
                inputs = {n: chunk(f, sl, lambda a: a.ndim == 3)
                          for n, f in zip(self.conf.network_inputs, feats)}
                lab = [chunk(l, sl, lambda a: a.ndim == 3) for l in labels]
                fm = {n: chunk(m, sl, lambda a: a.ndim == 2)
                      for n, m in zip(self.conf.network_inputs, fmasks)
                      if m is not None}
                lm = [chunk(m, sl, lambda a: a.ndim == 2) for m in lmasks]
                if all(m is None for m in lm):
                    lm = None
                self._rng_key, rng = jax.random.split(self._rng_key)
                it = jnp.asarray(self.iteration, jnp.int32)
                (self.params, self.state, self.opt_state,
                 chunk_score) = self._tbptt_step(
                    self.params, self.state, self.opt_state, it, inputs,
                    lab, fm, lm, rng)
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
        self.last_batch_examples = mds.num_examples
        _goodput.observe_steps(1)
        with _get_tracer().span("score_sync"):
            for l in self.listeners:
                l.iteration_done(self, self.iteration, self.epoch)
        return score

    def fit_batch(self, mds):
        """One optimization step on one (Multi)DataSet minibatch
        (ComputationGraph.fit parity)."""
        self._require_init()
        mds = self._coerce(mds)
        if self.conf.backprop_type == "tbptt":
            t_dims = {f.shape[1] for f in mds.features
                      if getattr(f, "ndim", 0) == 3}
            if t_dims and max(t_dims) > self.conf.tbptt_fwd_length:
                return self._fit_tbptt(mds)
        if self._train_step is None:
            self._train_step = self._build_train_step()
        else:
            self._resolve_remat()  # warn if DL4J_TPU_REMAT changed since
        tracer = _get_tracer()
        with tracer.span("host_dispatch"):
            self._rng_key, rng = jax.random.split(self._rng_key)
            inputs, fmasks = self._prepare_inputs(mds.features, mds.features_masks)
            labels = [jnp.asarray(l) for l in mds.labels]
            lmasks = [None if m is None else jnp.asarray(m)
                      for m in mds.labels_masks]
            if all(m is None for m in lmasks):
                lmasks = None
            it = jnp.asarray(self.iteration, jnp.int32)
        with tracer.span("device_step"):
            args = (self.params, self.state, self.opt_state, it, inputs,
                    labels, fmasks, lmasks, rng)
            if self._mesh is None:  # a meshed step registers its inner jit
                _opindex.register(self._train_step, args, args[4:8])
            self.params, self.state, self.opt_state, score = self._train_step(
                *args)
        self.iteration += 1
        self.score_value = score
        self.last_batch_examples = mds.num_examples
        _goodput.observe_steps(1)
        # post-dispatch: params hold fresh (undonated) outputs; inputs
        # and labels were not donated, so lowering for cost is safe
        self._maybe_derive_flops(inputs, labels, fmasks, lmasks)
        if self.listeners:
            t0 = time.perf_counter()
            for l in self.listeners:
                l.iteration_done(self, self.iteration, self.epoch)
            t1 = time.perf_counter()
            tracer.record("score_sync", t0, t1)
            _obs_metrics.observe_dispatch_lag(t1 - t0)
        return score

    def _maybe_derive_flops(self, inputs, labels, fmasks, lmasks):
        """Auto-derive per-step FLOPs from the XLA cost model on the
        *lowered* train step — tracing only, no backend compile — once
        per (train-step, batch-shapes) pair. See
        MultiLayerNetwork._maybe_derive_flops."""
        if not _goodput.auto_flops_enabled():
            return
        key = (id(self._train_step),
               tuple(sorted((n, tuple(v.shape)) for n, v in inputs.items())),
               tuple(tuple(l.shape) for l in labels),
               tuple(sorted((n, tuple(v.shape))
                            for n, v in (fmasks or {}).items())),
               None if lmasks is None else tuple(
                   None if m is None else tuple(m.shape) for m in lmasks))
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
                    self.opt_state, it, inputs, labels, fmasks, lmasks, rng)
                self.flops_per_step = cost["flops"] or None
            except NotImplementedError:
                # meshed/wrapped steps have no .lower
                self.flops_per_step = None
        _goodput.observe_flops(self.flops_per_step)

    def fit(self, data, *, epochs: int = 1, async_prefetch: bool = True,
            device_prefetch="auto", multi_step="auto"):
        """Train on an iterator of DataSet/MultiDataSet, or a single one.
        Iterators are wrapped in a background prefetch thread
        (AsyncDataSetIterator auto-wrap parity, MultiLayerNetwork.java:951 /
        ComputationGraph.java:701).

        Async runtime (bit-identity-preserving, see
        MultiLayerNetwork.fit): ``device_prefetch`` overlaps the
        host→device copy of batch N+1 with step N ("auto" = accelerator
        backends only); ``multi_step`` drives chunks of k steps through
        one jitted scan when no attached listener needs per-iteration
        values ("auto" = 8 on accelerators)."""
        if isinstance(data, (DataSet, MultiDataSet)):
            _obs_metrics.install_runtime_metrics()
            from deeplearning4j_tpu.compilecache import ensure_configured
            ensure_configured()  # JAX_COMPILATION_CACHE_DIR, if set
            ledger = _goodput.start_run("fit", net=self)
            from deeplearning4j_tpu.observability import (
                distributed as _obs_dist)
            _obs_dist.stamp_run_marker("fit")
            status = "completed"
            try:
                items = [data]
                for _ in range(epochs):
                    for d in items:
                        self.fit_batch(d)
                    self.epoch += 1
            except BaseException:
                status = "failed"
                raise
            finally:
                self.last_run_report = _goodput.end_run(ledger, status=status)
            return self
        from deeplearning4j_tpu.datasets.iterator import (
            AsyncDataSetIterator, DevicePrefetchIterator)
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
            for _ in range(epochs):
                source = data
                if async_prefetch and hasattr(data, "reset"):
                    source = AsyncDataSetIterator(data)
                if device_prefetch:
                    source = DevicePrefetchIterator(
                        source, sharding=self._prefetch_sharding())
                it0, t0 = self.iteration, time.perf_counter()
                if chunk > 1:
                    self._fit_epoch_chunked(source, chunk)
                else:
                    stream = iter(source)
                    while True:
                        with tracer.span("data_wait"):
                            d = next(stream, None)
                        if d is None:
                            break
                        self.fit_batch(d)
                _obs_metrics.observe_rate(self.iteration - it0,
                                          time.perf_counter() - t0)
                if hasattr(data, "reset") and not getattr(data, "auto_epochs",
                                                          False):
                    # datapipe Pipelines advance their own epoch state
                    # (seed + epoch shuffle orders); reset() would rewind
                    # them to epoch 0 every pass
                    data.reset()
                for l in self.listeners:
                    l.on_epoch_end(self)
                self.epoch += 1
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
        "auto" also resolves to 1 on the CPU backend — see
        MultiLayerNetwork._resolve_multi_step; an explicit int is always
        honored."""
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
        """"auto" = accelerator backends only — see
        MultiLayerNetwork._resolve_device_prefetch."""
        if device_prefetch == "auto":
            return jax.default_backend() != "cpu"
        return bool(device_prefetch)

    def _prefetch_sharding(self):
        """Target sharding for prefetched batches (None = default device);
        multi-process meshes keep host batches for shard_step_multi."""
        if self._mesh is None:
            return None
        if jax.process_count() > 1:
            return None
        from jax.sharding import NamedSharding, PartitionSpec
        mesh, axis = self._mesh
        return NamedSharding(mesh, PartitionSpec(axis))

    def _fit_epoch_chunked(self, source, chunk: int):
        """Group consecutive same-shape minibatches and dispatch each group
        as ONE jitted scan over distinct batches (bit-identical to the
        per-batch loop, including the rng chain — see multistep.py)."""
        self._require_init()

        def signature(m):
            return (tuple(tuple(f.shape) for f in m.features),
                    tuple(tuple(l.shape) for l in m.labels),
                    tuple(None if x is None else tuple(x.shape)
                          for x in m.features_masks),
                    tuple(None if x is None else tuple(x.shape)
                          for x in m.labels_masks))

        tracer = _get_tracer()
        buf, sig = [], None
        stream = iter(source)
        while True:
            with tracer.span("data_wait"):
                d = next(stream, None)
            if d is None:
                break
            m = self._coerce(d)
            s = signature(m)
            if buf and s != sig:
                self._dispatch_chunk(buf)
                buf = []
            sig = s
            buf.append(m)
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
            prepared = [self._prepare_inputs(m.features, m.features_masks)
                        for m in batches]
            inputs = {n: jnp.stack([p[0][n] for p in prepared])
                      for n in prepared[0][0]}
            fmasks = {n: jnp.stack([p[1][n] for p in prepared])
                      for n in prepared[0][1]}
            labels = [jnp.stack([jnp.asarray(m.labels[i]) for m in batches])
                      for i in range(len(batches[0].labels))]
            lmasks = [None if batches[0].labels_masks[i] is None else
                      jnp.stack([jnp.asarray(m.labels_masks[i])
                                 for m in batches])
                      for i in range(len(batches[0].labels_masks))]
            if all(m is None for m in lmasks):
                lmasks = None
            it0 = jnp.asarray(self.iteration, jnp.int32)
            steps = jnp.arange(len(batches), dtype=jnp.int32)
        with tracer.span("device_step", steps=len(batches)):
            args = (self.params, self.state, self.opt_state, it0,
                    self._rng_key, steps, (inputs, labels, fmasks, lmasks))
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
        self._maybe_derive_flops(
            prepared[0][0], list(batches[0].labels), prepared[0][1],
            None if lmasks is None else list(batches[0].labels_masks))
        with tracer.span("score_sync", steps=len(batches)):
            self._replay_listeners(start, scores,
                                   [m.num_examples for m in batches])

    def _replay_listeners(self, start: int, scores, examples):
        """Post-chunk iteration_done replay with per-iteration lazy score
        slices (every listener here declared needs_per_iteration=False)."""
        if not self.listeners:
            return
        for j in range(len(examples)):
            self.score_value = scores[j]
            self.last_batch_examples = examples[j]
            for l in self.listeners:
                l.iteration_done(self, start + j + 1, self.epoch)
        self.score_value = scores[-1]
        self.last_batch_examples = examples[-1]

    # ------------------------------------------------------------- pretrain
    def pretrain(self, data, *, epochs: int = 1):
        """Layer-wise unsupervised pretraining over the DAG
        (ComputationGraph.pretrain parity): each pretrainable layer vertex
        (VAE/AutoEncoder/RBM) trains on the activations its input vertices
        produce under the current parameters, in topological order."""
        self._require_init()
        for name in self.topo:
            layer = self._layer_by_name.get(name) if self.vertex_kind[
                name] == "layer" else None
            if layer is not None and getattr(layer, "is_pretrainable", False):
                self.pretrain_layer(name, data, epochs=epochs)
        return self

    def pretrain_layer(self, name: str, data, *, epochs: int = 1):
        """Pretrain one layer vertex on its featurized input (the
        pretrainLayer(String, DataSetIterator) overload)."""
        self._require_init()
        layer = self._layer_by_name.get(name)
        if layer is None or not getattr(layer, "is_pretrainable", False):
            raise ValueError(f"Vertex '{name}' is not a pretrainable layer")
        gc = self.conf.global_conf

        def step(params, opt_state, itc, x, rng):
            def loss_fn(p):
                return layer.pretrain_loss(p[name], x, rng)
            loss, grads = jax.value_and_grad(loss_fn)(params)
            new_params, new_opt = apply_layer_updates(
                [layer], gc, params, grads, opt_state, itc)
            return new_params, new_opt, loss

        jitted = jax.jit(step, donate_argnums=(0, 1))

        def featurize(params, state, inputs, fmasks):
            _, saved, _, _ = self._walk(params, state, inputs, train=False,
                                        rng=None, fmasks=fmasks,
                                        need_inputs_of=(name,))
            return saved[name][0][0]

        feat_fn = jax.jit(featurize)
        # material copies: the jitted step donates these buffers, and the
        # net's own trees must never alias donated (deleted) arrays — an
        # exception mid-loop would otherwise corrupt the whole net
        params_sub = {name: jax.tree_util.tree_map(jnp.copy,
                                                   self.params[name])}
        opt_sub = {name: jax.tree_util.tree_map(jnp.copy,
                                                self.opt_state[name])}
        last = None
        iteration = self.iteration
        items = ([data] if isinstance(data, (DataSet, MultiDataSet))
                 else data)
        for _ in range(epochs):
            for d in items:
                mds = self._coerce(d)
                inputs, fmasks = self._prepare_inputs(mds.features,
                                                      mds.features_masks)
                x = feat_fn(self.params, self.state, inputs, fmasks)
                self._rng_key, rng = jax.random.split(self._rng_key)
                itc = jnp.asarray(iteration, jnp.int32)
                params_sub, opt_sub, last = jitted(params_sub, opt_sub, itc,
                                                   x, rng)
                iteration += 1
            if hasattr(items, "reset"):
                items.reset()
        self.iteration = iteration
        self.params = {**self.params, name: params_sub[name]}
        self.opt_state = {**self.opt_state, name: opt_sub[name]}
        self.score_value = last
        return self

    # ------------------------------------------------------------ inference
    def output(self, *features, masks=None, train: bool = False):
        """Forward pass -> tuple of network-output activations (single array
        if the graph has one output)."""
        self._require_init()
        feats = [jnp.asarray(f) for f in features]
        key = ("out", train, masks is not None)
        if key not in self._apply_fns:
            def fn(params, state, inputs, fmasks):
                acts, _, _, _ = self._walk(params, state, inputs, train=train,
                                           rng=None, fmasks=fmasks)
                return tuple(acts[o] for o in self.conf.network_outputs)
            self._apply_fns[key] = jax.jit(fn)
        inputs, fmasks = self._prepare_inputs(
            feats, masks if masks is not None else None)
        outs = self._apply_fns[key](self.params, self.state, inputs, fmasks)
        return outs[0] if len(outs) == 1 else outs

    def feed_forward(self, *features, masks=None, train: bool = False):
        """All vertex activations as a dict (feedForward :1089 parity)."""
        self._require_init()
        feats = [jnp.asarray(f) for f in features]
        inputs, fmasks = self._prepare_inputs(feats, masks)
        acts, _, _, _ = self._walk(self.params, self.state, inputs,
                                   train=train, rng=None, fmasks=fmasks)
        return acts

    def score(self, mds, train: bool = False):
        self._require_init()
        mds = self._coerce(mds)
        inputs, fmasks = self._prepare_inputs(mds.features, mds.features_masks)
        labels = [jnp.asarray(l) for l in mds.labels]
        lmasks = [None if m is None else jnp.asarray(m)
                  for m in mds.labels_masks]
        if all(m is None for m in lmasks):
            lmasks = None
        loss, _ = self._loss(self.params, self.state, inputs, labels, fmasks,
                             lmasks, rng=None, train=train)
        return float(loss)

    def _evaluate_with(self, ev, iterator, what: str):
        """Shared single-output eval loop for evaluate/evaluate_regression."""
        if len(self.conf.network_outputs) != 1:
            raise ValueError(f"{what}() requires a single-output graph")
        if isinstance(iterator, (DataSet, MultiDataSet)):
            iterator = [iterator]
        for d in iterator:
            mds = self._coerce(d)
            out = self.output(*mds.features, masks=(
                mds.features_masks
                if any(m is not None for m in mds.features_masks) else None))
            ev.eval(mds.labels[0], np.asarray(out), mask=mds.labels_masks[0])
        return ev

    def evaluate(self, iterator):
        """Classification eval for single-output graphs (evaluate parity)."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        return self._evaluate_with(Evaluation(), iterator, "evaluate")

    def evaluate_regression(self, iterator):
        """Regression eval for single-output graphs (evaluateRegression
        parity)."""
        from deeplearning4j_tpu.eval.regression import RegressionEvaluation
        return self._evaluate_with(RegressionEvaluation(), iterator,
                                   "evaluate_regression")

    # ---------------------------------------------------------------- misc
    def num_params(self) -> int:
        return sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(self.params))

    def summary(self) -> str:
        lines = ["=" * 78]
        lines.append(f"{'name':<20}{'kind':<16}{'inputs':<28}{'params':>10}")
        lines.append("-" * 78)
        for name in self.topo:
            kind = self.vertex_kind[name]
            t = (self._resolved_confs[name].layer_type if kind == "layer"
                 else self._resolved_confs[name].vertex_type)
            p = self.params.get(name, {})
            n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(p))
            ins = ",".join(self.conf.vertex_inputs[name])
            lines.append(f"{name:<20}{t:<16}{ins:<28}{n:>10}")
        lines.append("-" * 78)
        lines.append(f"total params: {self.num_params()}")
        lines.append("=" * 78)
        return "\n".join(lines)

    def clone(self):
        net = ComputationGraph(self.conf)
        net.init(structure_only=True)
        net.params = jax.tree_util.tree_map(jnp.copy, self.params)
        net.state = jax.tree_util.tree_map(jnp.copy, self.state)
        net.opt_state = jax.tree_util.tree_map(jnp.copy, self.opt_state)
        net.iteration = self.iteration
        net.epoch = self.epoch
        return net
