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

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterator import (
    ArrayDataSetIterator,
    DataSetIterator,
    ListDataSetIterator,
)
from deeplearning4j_tpu.nn.conf.core import MultiLayerConfiguration
from deeplearning4j_tpu.observability import opindex as _opindex
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf import layers as layer_confs
from deeplearning4j_tpu.nn.conf.preprocessors import (
    CnnToFeedForward,
    FeedForwardToCnn,
    RnnToFeedForward,
)
from deeplearning4j_tpu.nn.trainer import (
    Trainer,
    _remat_match,
    _remat_prefixes,
)
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


class MultiLayerNetwork(Trainer):
    def __init__(self, conf: MultiLayerConfiguration):
        super().__init__(conf)
        self.preprocessors = None   # per-layer-index preprocessor or None

    # ------------------------------------------------------------------ init
    def init(self, seed: Optional[int] = None, *, structure_only: bool = False):
        """Build runtime layers and parameter/optimizer pytrees. With
        ``structure_only`` the trees are ShapeDtypeStructs (via jax.eval_shape)
        — used by clone()/restore, which overwrite every leaf anyway."""
        gc = self.conf.global_conf
        seed = gc.seed if seed is None else seed

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
        self._init_trees(seed, structure_only)
        return self

    # -------------------------------------------------------------- forward
    @staticmethod
    def _layer_params(layer, params) -> dict:
        """What ``layer`` is handed: its own leaves and, under their local
        names, the leaves of other layers it declares in ``shares``
        (``{local: (owner layer, leaf)}``). A shared leaf is stored,
        updated, counted and saved once, with its owner; autodiff sums
        its users' gradients there."""
        own = params.get(layer.name, {})
        shares = getattr(layer, "shares", None)
        if not shares:
            return own
        return {**own, **{local: params[owner][leaf]
                          for local, (owner, leaf) in shares.items()}}

    def _remat_spans(self, n: int) -> dict:
        """start index -> end index for maximal contiguous runs of layers
        whose names match the DL4J_TPU_REMAT prefixes (the chain-network
        rendering of ComputationGraph's block-granular selective remat —
        e.g. ``DL4J_TPU_REMAT=layer_`` remats every hidden layer, the
        long-sequence memory lever for stacked LSTMs)."""
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
        p_sub = {ly.name: self._layer_params(ly, params) for ly in sub}
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
            p = self._layer_params(layer, params)
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
        p_out = self._layer_params(out_layer, params)
        # the output layer's own scope holds its matmul and data loss;
        # "loss" is what no layer owns: regularization and the sum
        with _opindex.scope(out_layer.name):
            if self.preprocessors[-1] is not None:
                h = self.preprocessors[-1](h)
            if getattr(out_layer, "loss_uses_state", False):
                s_out = state.get(out_layer.name, {})
                data_loss = out_layer.loss(p_out, h, labels, train=train,
                                           rng=lrng, mask=lmask, state=s_out)
                if getattr(out_layer, "loss_returns_state", False):
                    data_loss, new_state[out_layer.name] = data_loss
                elif train and hasattr(out_layer, "update_centers"):
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
                # a loss term a hidden layer makes of its own, handed
                # back in its new state under the key ``own_loss`` names
                own = getattr(layer, "own_loss", None)
                if own is not None and layer.name in new_state:
                    reg = reg + new_state[layer.name][own]
            return data_loss + reg, new_state

    # ------------------------------------------------ the trainer's adapter
    def _batch_args(self, ds: DataSet, leaf=jnp.asarray):
        """One minibatch as the step's ``(x, labels, fmask, lmask)``."""
        return (leaf(ds.features), leaf(ds.labels),
                None if ds.features_mask is None else leaf(ds.features_mask),
                None if ds.labels_mask is None else leaf(ds.labels_mask))

    def _needs_tbptt(self, ds: DataSet) -> bool:
        return (self.conf.backprop_type == "tbptt"
                and getattr(ds.features, "ndim", 0) == 3
                and ds.features.shape[1] > self.conf.tbptt_fwd_length)

    def _tbptt_length(self, x, y) -> int:
        if y.ndim != 3 or y.shape[1] != x.shape[1]:
            raise ValueError(
                "tBPTT requires per-timestep labels [batch, time, out] with "
                f"the same time length as the features; got labels shape "
                f"{tuple(y.shape)} vs features {tuple(x.shape)}. For "
                "sequence-classification labels use backprop_type='standard'")
        return x.shape[1]

    # ---------------------------------------------------- streaming inference
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

    def _apply(self, collect, train, x, mask):
        x = jnp.asarray(x)
        mask = None if mask is None else jnp.asarray(mask)
        return self._first_forward(
            (collect, train),
            (x.shape, x.dtype, None if mask is None else mask.shape),
            lambda: self._get_apply(collect, train),
            self.params, self.state, x, self._inference_rng(train), mask)

    def output(self, x, train: bool = False, mask=None):
        """Forward pass -> final layer activations
        (MultiLayerNetwork.output :1512). ``mask`` is the per-timestep
        features mask for variable-length sequences."""
        self._require_init()
        return self._apply(False, train, x, mask)

    def feed_forward(self, x, train: bool = False, mask=None) -> List[jnp.ndarray]:
        """All layer activations (feedForward :675)."""
        self._require_init()
        return self._apply(True, train, x, mask)

    def score(self, ds: DataSet, train: bool = False):
        """Loss on one dataset (MultiLayerNetwork.score parity)."""
        self._require_init()
        loss, _ = self._loss(self.params, self.state, *self._batch_args(ds),
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
