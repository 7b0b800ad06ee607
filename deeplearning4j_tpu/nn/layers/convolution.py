"""Convolution / pooling / padding runtime layers.

Parity: nn/layers/convolution/ConvolutionLayer.java (the reference's forward
is im2col+GEMM at :281-300 or cuDNN via the helper seam at :69-76; here the
op registry resolves to lax.conv_general_dilated, which XLA lowers directly
onto the MXU — no im2col materialization), SubsamplingLayer.java,
ZeroPaddingLayer.java. Backprop is JAX autodiff.
"""

from __future__ import annotations

import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers.base import Layer
from deeplearning4j_tpu.ops import initializers as init_mod
from deeplearning4j_tpu.ops import registry as ops
from deeplearning4j_tpu.ops.convolution import pair as _pair
from deeplearning4j_tpu.ops.convolution import spatial_padding


class ConvolutionLayer(Layer):
    def init_params(self, key):
        kh, kw = _pair(self.conf.kernel)
        c_in, c_out = self.conf.n_in, self.conf.n_out
        fan_in = c_in * kh * kw
        fan_out = c_out * kh * kw
        w_fn = init_mod.resolve(self.resolve("weight_init", "xavier"))
        W = w_fn(key, (kh, kw, c_in, c_out), fan_in, fan_out, self.param_dtype)
        params = {"W": W}
        if self.conf.has_bias:
            params["b"] = jnp.full(
                (c_out,), float(self.resolve("bias_init", 0.0)), self.param_dtype)
        return params

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self._input_dropout(x, train, rng)
        kh, kw = _pair(self.conf.kernel)
        sh, sw = _pair(self.conf.stride)
        dh, dw = _pair(self.conf.dilation)
        pads = spatial_padding(
            (x.shape[1], x.shape[2]), (kh, kw), (sh, sw),
            _pair(self.conf.padding), self.conf.mode, (dh, dw))
        cd = self.compute_dtype
        z = ops.get("conv2d")(
            x.astype(cd), params["W"].astype(cd), strides=(sh, sw),
            padding=pads, dilation=(dh, dw))
        if "b" in params:
            z = z + params["b"].astype(cd)
        # stay in compute dtype (bf16 activations end-to-end under the
        # mixed policy — halves HBM traffic and residual memory)
        return self.activation_fn(z), state


class Convolution1DLayerImpl(Layer):
    def feed_forward_mask(self, mask):
        c = self.conf
        eff_k = (c.kernel - 1) * c.dilation + 1
        return _downsample_time_mask(mask, eff_k, c.stride, c.padding, c.mode)

    def init_params(self, key):
        k = int(self.conf.kernel)
        c_in, c_out = self.conf.n_in, self.conf.n_out
        fan_in, fan_out = c_in * k, c_out * k
        w_fn = init_mod.resolve(self.resolve("weight_init", "xavier"))
        W = w_fn(key, (k, c_in, c_out), fan_in, fan_out, self.param_dtype)
        params = {"W": W}
        if self.conf.has_bias:
            params["b"] = jnp.full(
                (c_out,), float(self.resolve("bias_init", 0.0)), self.param_dtype)
        return params

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self._input_dropout(x, train, rng)
        c = self.conf
        pads = spatial_padding(
            (x.shape[1],), (c.kernel,), (c.stride,), (c.padding,), c.mode,
            (c.dilation,))
        cd = self.compute_dtype
        z = ops.get("conv1d")(
            x.astype(cd), params["W"].astype(cd),
            stride=c.stride, padding=pads, dilation=c.dilation)
        if "b" in params:
            z = z + params["b"].astype(cd)
        return self.activation_fn(z), state


def _pool2d(x, *, kernel, strides, padding, pooling, pnorm):
    """Dispatch to the registered pooling op (shared by the 2D and 1D
    subsampling layers)."""
    if pooling == "max":
        return ops.get("max_pool2d")(x, kernel=kernel, strides=strides,
                                     padding=padding)
    if pooling == "avg":
        return ops.get("avg_pool2d")(x, kernel=kernel, strides=strides,
                                     padding=padding)
    if pooling == "pnorm":
        return ops.get("pnorm_pool2d")(x, kernel=kernel, strides=strides,
                                       padding=padding, p=pnorm)
    raise ValueError(f"Unknown pooling type: {pooling}")


class SubsamplingLayerImpl(Layer):
    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        c = self.conf
        kernel, strides = _pair(c.kernel), _pair(c.stride)
        pads = spatial_padding(
            (x.shape[1], x.shape[2]), kernel, strides, _pair(c.padding), c.mode)
        y = _pool2d(x, kernel=kernel, strides=strides, padding=pads,
                    pooling=c.pooling, pnorm=c.pnorm)
        return y, state


def _downsample_time_mask(mask, kernel, stride, padding, mode):
    """Downsample a [b, t] mask with a conv/pool's geometry: an output step
    is valid if ANY contributing input step is valid
    (Layer.feedForwardMaskArray parity for time-shrinking layers)."""
    if mask is None:
        return None
    m = mask.reshape(mask.shape[0], -1)[:, :, None, None].astype(jnp.float32)
    pads = spatial_padding((m.shape[1],), (kernel,), (stride,), (padding,),
                           mode) + [(0, 0)]
    out = ops.get("max_pool2d")(m, kernel=(kernel, 1), strides=(stride, 1),
                                padding=pads)
    return out[:, :, 0, 0]


class Subsampling1DLayerImpl(Layer):
    """1D pooling on [b, t, f]: runs the 2D kernels with a unit W dim."""

    def feed_forward_mask(self, mask):
        c = self.conf
        return _downsample_time_mask(mask, c.kernel, c.stride, c.padding,
                                     c.mode)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        c = self.conf
        pads = spatial_padding((x.shape[1],), (c.kernel,), (c.stride,),
                               (c.padding,), c.mode) + [(0, 0)]
        y = _pool2d(x[:, :, None, :], kernel=(c.kernel, 1),
                    strides=(c.stride, 1), padding=pads, pooling=c.pooling,
                    pnorm=c.pnorm)
        return y[:, :, 0, :], state


class ZeroPaddingLayerImpl(Layer):
    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        t, b, l, r = self.conf.pad
        return jnp.pad(x, [(0, 0), (t, b), (l, r), (0, 0)]), state
