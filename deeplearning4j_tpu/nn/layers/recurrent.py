"""Recurrent runtime layers: Graves LSTM (+bidirectional), RNN output head,
last-time-step extraction.

Parity: nn/layers/recurrent/{GravesLSTM, GravesBidirectionalLSTM,
LSTMHelpers, RnnOutputLayer, BaseRecurrentLayer}.java. The reference's
hand-written per-timestep Java loop (LSTMHelpers.activateHelper :57 looping
:76; backprop :271) becomes a ``lax.scan`` whose backward pass is derived by
autodiff; the whole sequence compiles into the train step.

Gate math (LSTMHelpers parity, Graves formulation with peepholes):
    i = gate_act(x Wx_i + h Wh_i + p_i * c_prev + b_i)
    f = gate_act(x Wx_f + h Wh_f + p_f * c_prev + b_f)
    g = act(x Wx_g + h Wh_g + b_g)
    c = f * c_prev + i * g
    o = gate_act(x Wx_o + h Wh_o + p_o * c + b_o)
    h = o * act(c)

Masking: masked timesteps carry (h, c) through unchanged and emit zero
output (per-timestep masking semantics, GradientCheckTestsMasking parity).

Streaming (`rnnTimeStep` :2234 / BaseRecurrentLayer stateMap parity): when
``layer.streaming`` is set by the network, the final (h, c) carry is read
from / written to the layer's state subtree under "h"/"c" — used by
``MultiLayerNetwork.rnn_time_step`` and truncated BPTT.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers.base import Layer
from deeplearning4j_tpu.ops import initializers as init_mod
from deeplearning4j_tpu.ops import losses as losses_mod
from deeplearning4j_tpu.ops import lstm as _lstm  # registers lstm_sequence
from deeplearning4j_tpu.ops import registry as ops

del _lstm

# recurrent (h, c) carries plus the attention tier's KV-cache carries
# (k/v caches + per-row absolute position — nn/layers/attention.py)
CARRY_KEYS = ("h", "c", "h_bwd", "c_bwd", "k", "v", "pos")


def _lstm_scan(params, x, h0, c0, mask, gate_act, cell_act):
    """Run an LSTM over [b, t, f]; returns (y [b,t,n], hT, cT).

    Runs entirely in x.dtype (the compute dtype — bf16 under the mixed
    policy, so the recurrent matmul hits the MXU at full rate). The
    whole layer is the ``lstm_sequence`` registry op (Pallas fused kernel
    on TPU, lax.scan under autodiff elsewhere — the LSTMHelpers.java:57,
    271 seam). The op owns the input projection and the bias: one MXU
    matmul over the whole sequence ahead of the time loop, or, on the
    Pallas backend for an input no wider than the hidden state, made
    inside the forward kernel."""
    cd = x.dtype
    params = {k: v.astype(cd) for k, v in params.items()}
    x_t = jnp.moveaxis(x, 1, 0)  # [t, b, n_in]
    mask_t = None if mask is None else jnp.moveaxis(mask, 1, 0)  # [t, b]
    ys, hT, cT = ops.get("lstm_sequence")(
        x_t, params["Wx"], params["b"], h0, c0, params["Wh"], params["p"],
        mask_t, gate_act=gate_act, cell_act=cell_act)
    return jnp.moveaxis(ys, 0, 1), hT, cT


class GravesLSTMLayer(Layer):
    is_recurrent_stateful = True
    streaming = False

    def _init_direction(self, key):
        n_in, n = self.conf.n_in, self.conf.n_out
        w_fn = init_mod.resolve(self.resolve("weight_init", "xavier"))
        k1, k2 = jax.random.split(key)
        Wx = w_fn(k1, (n_in, 4 * n), n_in, n, self.param_dtype)
        Wh = w_fn(k2, (n, 4 * n), n, n, self.param_dtype)
        b = jnp.zeros((4 * n,), self.param_dtype)
        # forget-gate bias init (gate order i, f, o, g)
        b = b.at[n:2 * n].set(float(self.conf.forget_gate_bias_init))
        p = jnp.zeros((3, n), self.param_dtype)
        return {"Wx": Wx, "Wh": Wh, "b": b, "p": p}

    def init_params(self, key):
        return self._init_direction(key)

    def _run(self, params, x, mask, carry, reverse=False):
        n = self.conf.n_out
        b = x.shape[0]
        if carry is None:
            h0 = jnp.zeros((b, n), x.dtype)
            c0 = jnp.zeros((b, n), x.dtype)
        else:
            h0, c0 = (carry[0].astype(x.dtype), carry[1].astype(x.dtype))
        if reverse:
            x = jnp.flip(x, axis=1)
            mask = None if mask is None else jnp.flip(mask, axis=1)
        y, hT, cT = _lstm_scan(params, x, h0, c0, mask,
                               self.conf.gate_activation,
                               self.resolve("activation", "tanh"))
        if reverse:
            y = jnp.flip(y, axis=1)
        return y, hT, cT

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self._input_dropout(x, train, rng).astype(self.compute_dtype)
        m = None
        if mask is not None:
            m = mask.reshape(mask.shape[0], -1).astype(x.dtype)
        carry = None
        if self.streaming and "h" in state:
            carry = (state["h"], state["c"])
        y, hT, cT = self._run(params, x, m, carry)
        new_state = dict(state)
        if self.streaming:
            new_state["h"] = hT
            new_state["c"] = cT
        return y, new_state


class GravesBidirectionalLSTMLayer(GravesLSTMLayer):
    def init_params(self, key):
        k1, k2 = jax.random.split(key)
        return {"fwd": self._init_direction(k1),
                "bwd": self._init_direction(k2)}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        if self.streaming:
            raise ValueError(
                "rnnTimeStep/tBPTT streaming is undefined for bidirectional "
                "LSTM (the backward pass needs the full sequence) — matching "
                "the reference's restriction")
        x = self._input_dropout(x, train, rng).astype(self.compute_dtype)
        m = None
        if mask is not None:
            m = mask.reshape(mask.shape[0], -1).astype(x.dtype)
        y_f, _, _ = self._run(params["fwd"], x, m, None)
        y_b, _, _ = self._run(params["bwd"], x, m, None, reverse=True)
        # reference sums directions (GravesBidirectionalLSTM.java:206)
        return y_f + y_b, state


class RnnOutputLayerImpl(Layer):
    """Per-timestep dense + loss (RnnOutputLayer.java parity)."""

    def init_params(self, key):
        n_in, n_out = self.conf.n_in, self.conf.n_out
        w_fn = init_mod.resolve(self.resolve("weight_init", "xavier"))
        params = {"W": w_fn(key, (n_in, n_out), n_in, n_out, self.param_dtype)}
        if self.conf.has_bias:
            params["b"] = jnp.full(
                (n_out,), float(self.resolve("bias_init", 0.0)),
                self.param_dtype)
        return params

    @property
    def loss_fn(self) -> losses_mod.Loss:
        return losses_mod.get(self.conf.loss)

    def preout(self, params, x):
        cd = self.compute_dtype
        z = jnp.einsum("btf,fg->btg", x.astype(cd), params["W"].astype(cd))
        if "b" in params:
            z = z + params["b"].astype(cd)
        return z

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        # Head activation in param dtype, mirroring the loss path, so
        # per-timestep serving outputs are full precision under any
        # policy (see OutputLayer.apply).
        x = self._input_dropout(x, train, rng)
        z = self.preout(params, x).astype(self.param_dtype)
        return self.activation_fn(z), state

    def loss(self, params, x, labels, *, train=False, rng=None, mask=None):
        x = self._input_dropout(x, train, rng)
        # loss math in param dtype (f32) for stability
        z = self.preout(params, x).astype(self.param_dtype)
        n_out = z.shape[-1]
        z2 = z.reshape(-1, n_out)
        labels2 = labels.reshape(-1, n_out)
        m2 = None if mask is None else mask.reshape(-1)
        return self.loss_fn.score(labels2, z2, self.activation_fn, m2)


class TimeDistributedDenseLayer(RnnOutputLayerImpl):
    """Per-timestep dense, no loss head (Keras TimeDistributed(Dense) /
    the reference's KerasLayer.java:206-212 mapping)."""

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        # Mid-network layer: unlike the RnnOutput head, its activation
        # stays in compute dtype between layers.
        x = self._input_dropout(x, train, rng)
        return self.activation_fn(self.preout(params, x)), state

    def loss(self, *args, **kwargs):
        raise ValueError(
            "TimeDistributedDense has no loss head — use RnnOutput as the "
            "terminal layer")


class LastTimeStepLayer(Layer):
    """[b, t, f] -> [b, f]: last step, or last *unmasked* step per example
    (LastTimeStepVertex.java parity)."""

    def feed_forward_mask(self, mask):
        return None

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.ops.sequence import last_unmasked_step
        return last_unmasked_step(x, mask), state


def set_streaming(layers, flag: bool):
    """Toggle stateful (h, c) carry on every recurrent layer — shared by
    MultiLayerNetwork and ComputationGraph streaming/tBPTT paths."""
    for layer in layers:
        if getattr(layer, "is_recurrent_stateful", False):
            layer.streaming = flag


def strip_carries(state):
    """Drop recurrent (h, c) carries from a state pytree (batch-boundary
    reset after tBPTT / streaming)."""
    out = {}
    for name, sub in state.items():
        kept = {k: v for k, v in sub.items() if k not in CARRY_KEYS}
        if kept:
            out[name] = kept
    return out
