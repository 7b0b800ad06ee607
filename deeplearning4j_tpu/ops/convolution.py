"""Convolution / pooling / LRN ops, registered in the op-lowering registry.

These are the TPU-native equivalents of the reference's cuDNN helper surface
(deeplearning4j-cuda: CudnnConvolutionHelper.java:49,
CudnnSubsamplingHelper.java, CudnnLocalResponseNormalizationHelper.java) and
of the im2col+GEMM CPU path (nn/layers/convolution/ConvolutionLayer.java:287).
On TPU there is no im2col: ``lax.conv_general_dilated`` lowers straight to
MXU convolutions, and pooling lowers to ``lax.reduce_window``.

Layouts are NHWC / HWIO (TPU-preferred; the reference is NCHW — the layout
difference is absorbed here and in the preprocessors, never exposed to
kernels). Padding follows the reference's ConvolutionMode semantics
(nn/conf/ConvolutionMode.java): ``truncate`` floors partial windows,
``strict`` requires exact fit, ``same`` pads to ceil(in/stride).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.ops import registry


# ---------------------------------------------------------------------------
# ConvolutionMode shape math (shared by configs and runtime)
# ---------------------------------------------------------------------------

def pair(v):
    """Normalize an int-or-pair spec to a (h, w) tuple."""
    if isinstance(v, (tuple, list)):
        return int(v[0]), int(v[1])
    return int(v), int(v)


def out_size(in_size: int, kernel: int, stride: int, pad: int,
             mode: str, dilation: int = 1) -> int:
    """Output length along one spatial dim for a ConvolutionMode."""
    eff_k = (kernel - 1) * dilation + 1
    if mode == "same":
        return -(-in_size // stride)  # ceil
    n = in_size + 2 * pad - eff_k
    if mode == "strict":
        if n % stride != 0:
            raise ValueError(
                f"ConvolutionMode=strict: (in={in_size} + 2*pad={pad} - "
                f"kernel={eff_k}) = {n} is not divisible by stride={stride}. "
                f"Use mode='truncate' or 'same', or adjust the geometry "
                f"(ConvolutionMode.java parity)")
        return n // stride + 1
    if n < 0:
        raise ValueError(
            f"Kernel {eff_k} larger than padded input {in_size + 2 * pad}")
    return n // stride + 1  # truncate


def _same_pads(in_size: int, kernel: int, stride: int, dilation: int = 1):
    eff_k = (kernel - 1) * dilation + 1
    out = -(-in_size // stride)
    total = max((out - 1) * stride + eff_k - in_size, 0)
    return total // 2, total - total // 2


def spatial_padding(in_sizes, kernels, strides, pads, mode, dilations=None):
    """Per-dim (lo, hi) padding pairs implementing a ConvolutionMode."""
    dilations = dilations or [1] * len(in_sizes)
    if mode == "same":
        return [
            _same_pads(i, k, s, d)
            for i, k, s, d in zip(in_sizes, kernels, strides, dilations)
        ]
    return [(p, p) for p in pads]


# ---------------------------------------------------------------------------
# Convolution
# ---------------------------------------------------------------------------

@registry.register("conv2d", backend="xla")
def conv2d_xla(x, w, *, strides, padding, dilation=(1, 1)):
    """x: [N,H,W,C], w: [kH,kW,C_in,C_out], padding: [(lo,hi),(lo,hi)]."""
    return lax.conv_general_dilated(
        x, w,
        window_strides=strides,
        padding=padding,
        rhs_dilation=dilation,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


@registry.register("conv1d", backend="xla")
def conv1d_xla(x, w, *, stride, padding, dilation=1):
    """x: [N,T,C], w: [k,C_in,C_out], padding: [(lo,hi)]."""
    return lax.conv_general_dilated(
        x, w,
        window_strides=(stride,),
        padding=padding,
        rhs_dilation=(dilation,),
        dimension_numbers=("NWC", "WIO", "NWC"),
    )


# ---------------------------------------------------------------------------
# Pooling (SubsamplingLayer.java semantics)
# ---------------------------------------------------------------------------

def _pool_dims(kernel, strides):
    return (1, *kernel, 1), (1, *strides, 1)


@registry.register("max_pool2d", backend="xla")
def max_pool2d_xla(x, *, kernel, strides, padding):
    window, strd = _pool_dims(kernel, strides)
    pads = [(0, 0), *padding, (0, 0)]
    neg = (-jnp.inf if jnp.issubdtype(x.dtype, jnp.floating)
           else jnp.iinfo(x.dtype).min)
    return lax.reduce_window(x, neg, lax.max, window, strd, pads)


@registry.register("avg_pool2d", backend="xla")
def avg_pool2d_xla(x, *, kernel, strides, padding):
    """Average pooling dividing by the FULL kernel size (including padding),
    matching the reference's AVG pooling (SubsamplingLayer divides by
    kernel area, not by the valid-element count)."""
    window, strd = _pool_dims(kernel, strides)
    pads = [(0, 0), *padding, (0, 0)]
    summed = lax.reduce_window(x, 0.0, lax.add, window, strd, pads)
    return summed / float(np.prod(kernel))


@registry.register("pnorm_pool2d", backend="xla")
def pnorm_pool2d_xla(x, *, kernel, strides, padding, p, eps=1e-8):
    """P-norm pooling: (sum |x|^p)^(1/p) (PoolingType.PNORM parity)."""
    window, strd = _pool_dims(kernel, strides)
    pads = [(0, 0), *padding, (0, 0)]
    powed = jnp.abs(x) ** p
    summed = lax.reduce_window(powed, 0.0, lax.add, window, strd, pads)
    return (summed + eps) ** (1.0 / p)


# ---------------------------------------------------------------------------
# Local response normalization (LocalResponseNormalization.java /
# CudnnLocalResponseNormalizationHelper.java parity)
# ---------------------------------------------------------------------------

@registry.register("lrn", backend="xla")
def lrn_xla(x, *, k=2.0, n=5, alpha=1e-4, beta=0.75):
    """Across-channel LRN on NHWC: y = x / (k + alpha*sum_{window n} x^2)^beta."""
    half = n // 2
    sq = x * x
    window = (1, 1, 1, n)
    strides = (1, 1, 1, 1)
    pads = [(0, 0), (0, 0), (0, 0), (half, n - 1 - half)]
    ssum = lax.reduce_window(sq, 0.0, lax.add, window, strides, pads)
    return x / (k + alpha * ssum) ** beta
