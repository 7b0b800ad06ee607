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

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.observability import opindex as _opindex
from deeplearning4j_tpu.observability.trace import get_tracer
from deeplearning4j_tpu.nn.conf.graph_conf import ComputationGraphConfiguration
from deeplearning4j_tpu.nn.conf.layers import BaseLayerConfig
from deeplearning4j_tpu.nn.trainer import (
    Trainer,
    _remat_match,
    _remat_prefixes,
)
from deeplearning4j_tpu.nn.updater import apply_layer_updates


class ComputationGraph(Trainer):
    def __init__(self, conf: ComputationGraphConfiguration):
        super().__init__(conf)
        self.topo = conf.topological_order()
        self.vertex_kind = None     # name -> "layer" | "vertex"

    # ------------------------------------------------------------------ init
    def init(self, seed: Optional[int] = None, *, structure_only: bool = False):
        gc = self.conf.global_conf
        seed = gc.seed if seed is None else seed

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

        self._init_trees(seed, structure_only)
        return self

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
        # selective block remat: maximal contiguous topo runs of vertices
        # matching DL4J_TPU_REMAT prefixes execute under one
        # jax.checkpoint (span inputs saved, interiors recomputed in the
        # backward). Masked inputs fall back to inline execution.
        remat = ((self.remat_prefixes if self.remat_prefixes is not None
                  else _remat_prefixes()) if train else ())
        spans = self._remat_spans(remat, set(need_inputs_of)) if remat else {}
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

    def _prepare_inputs(self, features: List, fmasks: Optional[List],
                        leaf=jnp.asarray):
        inputs = {n: leaf(f)
                  for n, f in zip(self.conf.network_inputs, features)}
        md = {}
        if fmasks is not None:
            for n, m in zip(self.conf.network_inputs, fmasks):
                if m is not None:
                    md[n] = leaf(m)
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

    # ------------------------------------------------ the trainer's adapter
    @staticmethod
    def _coerce(data) -> MultiDataSet:
        if isinstance(data, MultiDataSet):
            return data
        if isinstance(data, DataSet):
            return MultiDataSet.from_dataset(data)
        raise TypeError(f"Expected DataSet or MultiDataSet, got {type(data)}")

    def _batch_args(self, data, leaf=jnp.asarray):
        """One minibatch as the step's ``(inputs{}, labels[], fmasks{},
        lmasks[])``; ``lmasks`` is None when no output has a mask."""
        mds = self._coerce(data)
        inputs, fmasks = self._prepare_inputs(mds.features,
                                              mds.features_masks, leaf)
        labels = [leaf(l) for l in mds.labels]
        lmasks = [None if m is None else leaf(m) for m in mds.labels_masks]
        if all(m is None for m in lmasks):
            lmasks = None
        return inputs, labels, fmasks, lmasks

    def _needs_tbptt(self, data) -> bool:
        if self.conf.backprop_type != "tbptt":
            return False
        t_dims = {f.shape[1] for f in self._coerce(data).features
                  if getattr(f, "ndim", 0) == 3}
        return bool(t_dims) and max(t_dims) > self.conf.tbptt_fwd_length

    def _repeat_per_batch(self, data) -> bool:
        # every tBPTT-configured graph, whatever the batch's length: a
        # short batch has always stepped through fit_batch here, and the
        # scan would split the rng key once more
        return self._mesh is not None or self.conf.backprop_type == "tbptt"

    def _tbptt_length(self, inputs, labels) -> int:
        """tBPTT on the DAG (ComputationGraphConfiguration tBPTT parity):
        every time-series input and label shares one time length."""
        if any(l.ndim == 2 for l in labels):
            raise ValueError(
                "tBPTT requires per-timestep labels [batch, time, out]; got "
                "a 2d (sequence-classification) label — use "
                "backprop_type='standard' for sequence classification")
        t_lens = {a.shape[1] for a in (*inputs.values(), *labels)
                  if a.ndim == 3}
        if len(t_lens) != 1:
            raise ValueError(
                "tBPTT requires all time-series inputs AND per-timestep "
                "labels to share one time length; got time lengths "
                f"{sorted(t_lens)} (sequence-classification labels need "
                "backprop_type='standard')")
        return t_lens.pop()

    # ---------------------------------------------------- streaming inference
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

        def build():
            def fn(params, state, inputs, fmasks):
                acts, _, _, _ = self._walk(params, state, inputs, train=train,
                                           rng=None, fmasks=fmasks)
                return tuple(acts[o] for o in self.conf.network_outputs)
            self._apply_fns[key] = jax.jit(fn)
            return self._apply_fns[key]

        inputs, fmasks = self._prepare_inputs(
            feats, masks if masks is not None else None)
        shapes = tuple((f.shape, f.dtype) for f in feats)
        if masks is not None:
            shapes += tuple(None if m is None else jnp.shape(m)
                            for m in masks)
        outs = self._first_forward(key, shapes, build, self.params,
                                   self.state, inputs, fmasks)
        return outs[0] if len(outs) == 1 else outs

    def feed_forward(self, *features, masks=None, train: bool = False):
        """All vertex activations as a dict (feedForward :1089 parity)."""
        self._require_init()
        feats = [jnp.asarray(f) for f in features]
        inputs, fmasks = self._prepare_inputs(feats, masks)
        # an eager walk: every op of a new shape is a program of its own
        with get_tracer().span("forward"):
            acts, _, _, _ = self._walk(self.params, self.state, inputs,
                                       train=train, rng=None, fmasks=fmasks)
        return acts

    def score(self, mds, train: bool = False):
        self._require_init()
        loss, _ = self._loss(self.params, self.state, *self._batch_args(mds),
                             rng=None, train=train)
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
