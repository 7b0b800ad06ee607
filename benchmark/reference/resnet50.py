"""ResNet-50 v1 (He et al. 2015, arXiv:1512.03385, Table 1, 50-layer).

7x7/2 convolution, batch norm, ReLU, 3x3/2 max pool; four stages of
[3, 4, 6, 3] bottleneck blocks (1x1 reduce, 3x3, 1x1 expand x4, each
followed by batch norm, ReLU after the first two and after the shortcut
addition), the stride of a stage's first block on its first 1x1 and on
its projection shortcut; global average pool; dense layer.

Departures from the paper, which are the program's and are followed here
so that the two compute the same function: every convolution and the
max pool pad "same" (TensorFlow rule: the extra pixel goes after), and no
convolution has a bias. Batch norm: eps 1e-5, biased batch variance in
training mode, running statistics otherwise.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

STAGES = (3, 4, 6, 3)
BN_EPS = 1e-5

# The system computes in bf16 (8 bits of mantissa) with float32
# parameters and a float32 head; the reference in float32 throughout.
# Both checks run on the seeded initial parameters, so neither moves with
# the length of the window or with how far it memorised the ring.
#
# LOSS_RTOL, training path: the first step's loss on its whole batch was
# 0.1e-3 to 2.2e-3 from the reference's (v5e, my chip runs, PR 22, 41
# seeds over both cells); the bound is three times the worst seen.
#
# LOGITS_RTOL, inference path (running statistics), 32 seeded images,
# entry by entry: 4.7e-3 to 6.9e-3 of the logits' spread from the
# reference's (v5e, my chip runs, PR 22, 15 seeds on one chip; the four-
# chip cell runs the same parameters on the same images). The bound is
# three times the worst seen.
# The training-mode forward is not compared entry by entry: on noise
# images the batch statistics take away what the images share and leave
# differences that bf16 rounding moves by 13 to 15% of the logits' spread
# at the seeded init (the program on the CPU, PR 22: arithmetic, not a
# device measurement); it is the loss above, a mean over the batch, that
# holds that path.
#
# What the bound of 0.02 separates, tried on the float32 reference with
# the fault put in (CPU, PR 22: arithmetic, not a device measurement;
# seed 1, against the reference left alone). Caught: the residual branch
# of one block dropped, 0.13 (s1b1) to 0.30 (s3b2); the stem's stride-2
# padding put before instead of after, 0.047. Not caught: every
# convolution's input rounded to bf16 with its weights 0.003, to int8
# with one scale a tensor 0.004, to fp8 e4m3 0.012: rounding errors
# average out over a fan-in of hundreds, so this check cannot tell a
# narrower activation type from bf16 (PERF.md section 7).
LOGITS_RTOL = 0.02      # max|log p - log_softmax(reference)| / spread of the reference logits
LOSS_RTOL = 6.5e-3      # first training step's loss, relative


def _conv(x, w, stride):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, p, s, train):
    if train:
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    else:
        mean, var = s["mean"], s["var"]
    return (x - mean) * jax.lax.rsqrt(var + BN_EPS) * p["gamma"] + p["beta"]


def logits(params, state, x, train: bool):
    def conv_bn(name, x, stride, relu=True):
        y = _bn(_conv(x, params[f"{name}_conv"]["W"], stride),
                params[f"{name}_bn"], state[f"{name}_bn"], train)
        return jax.nn.relu(y) if relu else y

    x = conv_bn("stem", x.astype(jnp.float32), 2)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    for stage, n_blocks in enumerate(STAGES):
        for block in range(n_blocks):
            name = f"s{stage}b{block}"
            stride = 2 if stage > 0 and block == 0 else 1
            y = conv_bn(f"{name}_a", x, stride)
            y = conv_bn(f"{name}_b", y, 1)
            y = conv_bn(f"{name}_c", y, 1, relu=False)
            if block == 0:
                x = conv_bn(f"{name}_proj", x, stride, relu=False)
            x = jax.nn.relu(x + y)
    x = jnp.mean(x, axis=(1, 2))
    return x @ params["fc"]["W"] + params["fc"]["b"]
