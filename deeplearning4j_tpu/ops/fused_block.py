"""Fused bottleneck-tail op: 1x1 expand conv + batch norm + residual add
+ ReLU in one lowering, with a recompute-based two-pass Pallas schedule.

Parity: the reference routes conv and BN through fused native kernels
(deeplearning4j-cuda/.../CudnnConvolutionHelper.java:49,
CudnnBatchNormalizationHelper.java) precisely because the composed
formulation is memory-bound. This op goes one step further than cuDNN's
per-layer helpers: it fuses the whole residual-block tail

    y = relu((x @ W - mean) * inv * gamma + beta + shortcut)

where mean/var are the BATCH statistics of the conv output z = x @ W.

Why recompute: BN needs all of z before it can normalize any of it, so a
single-pass fusion is impossible; the standard schedule (XLA's) therefore
materializes z to HBM (write) and re-reads it for the normalize+add+relu
fusion. On an HBM-bound step whose operational intensity sits ~10x below
the MXU ridge point, FLOPs are free and bytes are not: this kernel never
materializes z at all — a stats pass reads x and computes only the
per-channel sums, then an apply pass re-reads x, recomputes z on the MXU,
and writes the final block output directly. For an expand conv
(C_out = 4*C_in in ResNet bottlenecks) the extra read of x costs M*K
bytes and saves 2*M*N — profitable whenever 2*N > K. The backward applies
the same trick twice (reduction pass for the BN sums, then a pass emitting
dx/dW/dshortcut), so the conv output is never stored as an autodiff
residual either — the activation-memory saving is what the write-traffic
saving is.

The ``xla`` backend is the composed reference semantics (dot ->
ops.normalization.batch_norm_train -> add -> relu); the ``pallas`` backend
is equivalence-tested against it in tests/test_fused_block.py (the
CuDNNGradientChecks.java analogue for this kernel).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops import registry
from deeplearning4j_tpu.ops.normalization import batch_norm_train

# f32 intermediate tile cap: TM*TN*4 bytes <= 2 MiB
_TN_MAX = 512
_TM_CANDIDATES = (1024, 512, 256, 128, 64, 32, 16, 8)


# ------------------------------------------------------------------ xla
@registry.register("conv1x1_bn_add_relu", backend="xla")
def conv1x1_bn_add_relu_xla(x, W, gamma, beta, shortcut, *, shift, eps,
                            relu=True):
    """Composed reference semantics: z = x @ W (1x1 conv over the trailing
    channel axis); (zn, mean, var) = batch-norm(z); out = relu(zn +
    shortcut). Returns (out, mean, var) — mean/var feed the BN layer's
    running-statistics update exactly as in the unfused path."""
    K = x.shape[-1]
    N = W.shape[-1]
    z = jax.lax.dot_general(
        x.reshape(-1, K), W.reshape(K, N),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=x.dtype).reshape(x.shape[:-1] + (N,))
    zn, mean, var = batch_norm_train(z, gamma, beta, shift, eps)
    out = zn + shortcut
    if relu:
        out = jnp.maximum(out, 0)
    return out, mean, var


# --------------------------------------------------------------- pallas
_interpret = registry.pallas_interpret

# VMEM budget for one grid step of the heaviest pass (backward apply):
# the resident full [K, N] f32 dW accumulator + double-buffered tiles +
# f32 intermediates must fit comfortably in the ~16 MiB of VMEM
_VMEM_BUDGET = 12 * 1024 * 1024


def _footprint(tm, tn, K, N, itemsize):
    """Conservative VMEM estimate for the backward-apply grid step."""
    dw_acc = K * N * 4
    x_tiles = 2 * tm * K * itemsize + tm * K * (itemsize + 4)  # in+out+scr
    mn_tiles = 3 * 2 * tm * tn * itemsize        # dy, y, dsc double-buffered
    f32_inter = 3 * tm * tn * 4                  # z, xhat, dz
    return dw_acc + x_tiles + mn_tiles + f32_inter


def _pick_tm(M, dtype, K=64, N=128):
    sub = 16 if dtype == jnp.bfloat16 else 8
    itemsize = 2 if dtype == jnp.bfloat16 else 4
    tn = min(N, _TN_MAX)
    for tm in _TM_CANDIDATES:
        if (tm >= sub and M % tm == 0
                and _footprint(tm, tn, K, N, itemsize) <= _VMEM_BUDGET):
            return tm
    return None


def pallas_supported(x, W, shortcut=None):
    if x.dtype not in (jnp.bfloat16, jnp.float32):
        return False
    K, N = W.shape[-2], W.shape[-1]
    if K % 64 != 0 or N % 128 != 0:
        return False
    if shortcut is not None and shortcut.shape != x.shape[:-1] + (N,):
        # the xla backend broadcasts; the kernel needs a full-shape
        # shortcut — fall back rather than mis-tile
        return False
    M = 1
    for d in x.shape[:-1]:
        M *= d
    if _pick_tm(M, x.dtype, K, N) is None:
        return False
    if not _interpret() and jax.default_backend() != "tpu":
        return False
    return True


def _round_trip(z, cd):
    """Round the recomputed f32 conv output through the compute dtype so
    every pass (and the backward) sees the SAME values the unfused path
    would have materialized — keeps recompute bit-consistent across
    passes."""
    return z.astype(cd).astype(jnp.float32)


def _dot_f32(a, b):
    return jax.lax.dot_general(
        a, b, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


# forward pass 1: per-channel sum / sum-of-squares of z = x @ W
def _stats_kernel(x_ref, w_ref, shift_ref, s1_ref, s2_ref):
    import jax.experimental.pallas as pl

    m = pl.program_id(1)
    z = _round_trip(_dot_f32(x_ref[:], w_ref[:]), x_ref.dtype)
    zs = z - shift_ref[:]
    p1 = jnp.sum(zs, axis=0, keepdims=True)
    p2 = jnp.sum(zs * zs, axis=0, keepdims=True)

    @pl.when(m == 0)
    def _():
        s1_ref[:] = p1
        s2_ref[:] = p2

    @pl.when(m != 0)
    def _():
        s1_ref[:] += p1
        s2_ref[:] += p2


# forward pass 2: recompute z, apply affine + shortcut + relu, write out
def _apply_kernel(x_ref, w_ref, scale_ref, sh_ref, sc_ref, y_ref, *, relu):
    z = _round_trip(_dot_f32(x_ref[:], w_ref[:]), x_ref.dtype)
    o = z * scale_ref[:] + sh_ref[:] + sc_ref[:].astype(jnp.float32)
    if relu:
        o = jnp.maximum(o, 0.0)
    y_ref[:] = o.astype(y_ref.dtype)


# backward pass 1: a = sum(g), b = sum(g * xhat) with g = dy * relu-mask
def _bwd_stats_kernel(x_ref, w_ref, mean_ref, inv_ref, dy_ref, y_ref,
                      a_ref, b_ref, *, relu):
    import jax.experimental.pallas as pl

    m = pl.program_id(1)
    z = _round_trip(_dot_f32(x_ref[:], w_ref[:]), x_ref.dtype)
    xhat = (z - mean_ref[:]) * inv_ref[:]
    g = dy_ref[:].astype(jnp.float32)
    if relu:
        g = jnp.where(y_ref[:].astype(jnp.float32) > 0, g, 0.0)
    pa = jnp.sum(g, axis=0, keepdims=True)
    pb = jnp.sum(g * xhat, axis=0, keepdims=True)

    @pl.when(m == 0)
    def _():
        a_ref[:] = pa
        b_ref[:] = pb

    @pl.when(m != 0)
    def _():
        a_ref[:] += pa
        b_ref[:] += pb


# backward pass 2: dz = scale*(g - a/M - xhat*b/M); dx += dz @ W^T;
# dW += x^T @ dz; dshortcut = g.  Grid is (MT, NT): m outer so the dx
# accumulator (and its out block) stays resident across the inner n loop;
# dW is a single full-size f32 block accumulated across the whole grid.
def _bwd_apply_kernel(x_ref, w_ref, mean_ref, inv_ref, scale_ref, ca_ref,
                      cb_ref, dy_ref, y_ref, dx_ref, dw_ref, dsc_ref,
                      dx_scr, *, relu, n_blocks, tn):
    import jax.experimental.pallas as pl

    m = pl.program_id(0)
    n = pl.program_id(1)
    cd = x_ref.dtype

    z = _round_trip(_dot_f32(x_ref[:], w_ref[:]), cd)
    xhat = (z - mean_ref[:]) * inv_ref[:]
    g = dy_ref[:].astype(jnp.float32)
    if relu:
        g = jnp.where(y_ref[:].astype(jnp.float32) > 0, g, 0.0)
    dz = scale_ref[:] * (g - ca_ref[:] - xhat * cb_ref[:])
    dz_cd = dz.astype(cd)

    dsc_ref[:] = g.astype(dsc_ref.dtype)

    # dx contribution: dz @ W^T (contract the N-block dim)
    dx_part = jax.lax.dot_general(
        dz_cd, w_ref[:], dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(n == 0)
    def _():
        dx_scr[:] = dx_part

    @pl.when(n != 0)
    def _():
        dx_scr[:] += dx_part

    @pl.when(n == n_blocks - 1)
    def _():
        dx_ref[:] = dx_scr[:].astype(dx_ref.dtype)

    # dW contribution: x^T @ dz into the n-th column block of the full
    # [K, N] f32 accumulator (resident for the whole grid; flushed once)
    dw_part = jax.lax.dot_general(
        x_ref[:], dz_cd, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(m == 0, n == 0))
    def _():
        dw_ref[:] = jnp.zeros_like(dw_ref)

    dw_ref[:, pl.dslice(n * tn, tn)] += dw_part


def _grids(M, K, N, dtype):
    tm = _pick_tm(M, dtype, K, N)
    tn = min(N, _TN_MAX)
    return tm, tn, M // tm, N // tn


def _vec(v):
    """[N] -> [1, N] f32 (TPU-friendly 2D vector block)."""
    return jnp.asarray(v, jnp.float32).reshape(1, -1)


def _fwd_impl(x2, W, gamma, beta, sc2, shift, eps, relu):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, K = x2.shape
    N = W.shape[-1]
    tm, tn, mt, nt = _grids(M, K, N, x2.dtype)
    vspec = lambda: pl.BlockSpec((1, tn), lambda n, m: (0, n),
                                 memory_space=pltpu.VMEM)
    x_spec = pl.BlockSpec((tm, K), lambda n, m: (m, 0),
                          memory_space=pltpu.VMEM)
    w_spec = pl.BlockSpec((K, tn), lambda n, m: (0, n),
                          memory_space=pltpu.VMEM)

    s1, s2 = pl.pallas_call(
        _stats_kernel,
        grid=(nt, mt),
        in_specs=[x_spec, w_spec, vspec()],
        out_specs=(vspec(), vspec()),
        out_shape=(jax.ShapeDtypeStruct((1, N), jnp.float32),
                   jax.ShapeDtypeStruct((1, N), jnp.float32)),
        interpret=_interpret(),
    )(x2, W, _vec(shift))

    k = jnp.asarray(shift, jnp.float32)
    m1 = s1[0] / M
    mean = m1 + k
    var = jnp.maximum(s2[0] / M - m1 * m1, 0.0)
    inv = jax.lax.rsqrt(var + eps)
    scale = jnp.asarray(gamma, jnp.float32) * inv
    sh = jnp.asarray(beta, jnp.float32) - mean * scale

    y = pl.pallas_call(
        functools.partial(_apply_kernel, relu=relu),
        grid=(nt, mt),
        in_specs=[x_spec, w_spec, vspec(), vspec(),
                  pl.BlockSpec((tm, tn), lambda n, m: (m, n),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((tm, tn), lambda n, m: (m, n),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((M, N), x2.dtype),
        interpret=_interpret(),
    )(x2, W, _vec(scale), _vec(sh), sc2)
    return y, mean, var, inv, scale


def _bwd_impl(x2, W, mean, inv, scale, dy2, y2, relu):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, K = x2.shape
    N = W.shape[-1]
    tm, tn, mt, nt = _grids(M, K, N, x2.dtype)
    vspec_nm = lambda: pl.BlockSpec((1, tn), lambda n, m: (0, n),
                                    memory_space=pltpu.VMEM)
    a, b = pl.pallas_call(
        functools.partial(_bwd_stats_kernel, relu=relu),
        grid=(nt, mt),
        in_specs=[
            pl.BlockSpec((tm, K), lambda n, m: (m, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((K, tn), lambda n, m: (0, n),
                         memory_space=pltpu.VMEM),
            vspec_nm(), vspec_nm(),
            pl.BlockSpec((tm, tn), lambda n, m: (m, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tm, tn), lambda n, m: (m, n),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(vspec_nm(), vspec_nm()),
        out_shape=(jax.ShapeDtypeStruct((1, N), jnp.float32),
                   jax.ShapeDtypeStruct((1, N), jnp.float32)),
        interpret=_interpret(),
    )(x2, W, _vec(mean), _vec(inv), dy2, y2)

    ca = a[0] / M
    cb = b[0] / M

    vspec_mn = lambda: pl.BlockSpec((1, tn), lambda m, n: (0, n),
                                    memory_space=pltpu.VMEM)
    dx, dW, dsc = pl.pallas_call(
        functools.partial(_bwd_apply_kernel, relu=relu, n_blocks=nt, tn=tn),
        grid=(mt, nt),
        in_specs=[
            pl.BlockSpec((tm, K), lambda m, n: (m, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((K, tn), lambda m, n: (0, n),
                         memory_space=pltpu.VMEM),
            vspec_mn(), vspec_mn(), vspec_mn(), vspec_mn(), vspec_mn(),
            pl.BlockSpec((tm, tn), lambda m, n: (m, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tm, tn), lambda m, n: (m, n),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((tm, K), lambda m, n: (m, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((K, N), lambda m, n: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tm, tn), lambda m, n: (m, n),
                         memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((M, K), x2.dtype),
            jax.ShapeDtypeStruct((K, N), jnp.float32),
            jax.ShapeDtypeStruct((M, N), x2.dtype),
        ),
        scratch_shapes=[pltpu.VMEM((tm, K), jnp.float32)],
        interpret=_interpret(),
    )(x2, W, _vec(mean), _vec(inv), _vec(scale), _vec(ca), _vec(cb),
      dy2, y2)

    dgamma = b[0]
    dbeta = a[0]
    return dx, dW, dgamma, dbeta, dsc


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _fused_pallas(x2, W, gamma, beta, sc2, eps, relu, shift):
    y, mean, var, _, _ = _fwd_impl(x2, W, gamma, beta, sc2, shift, eps, relu)
    return y, mean, var


def _fused_fwd(x2, W, gamma, beta, sc2, eps, relu, shift):
    y, mean, var, inv, scale = _fwd_impl(x2, W, gamma, beta, sc2, shift, eps,
                                         relu)
    return (y, mean, var), (x2, W, gamma, mean, inv, scale, y)


def _fused_bwd(eps, relu, res, cts):
    dy = cts[0]  # mean/var feed only the (undifferentiated) running update
    x2, W, gamma, mean, inv, scale, y = res
    dx, dW, dgamma, dbeta, dsc = _bwd_impl(
        x2, W, mean, inv, scale, dy.astype(x2.dtype), y, relu)
    return (dx, dW.astype(W.dtype), dgamma.astype(gamma.dtype),
            dbeta.astype(gamma.dtype), dsc, None)


_fused_pallas.defvjp(_fused_fwd, _fused_bwd)


@registry.register("conv1x1_bn_add_relu", backend="pallas")
def conv1x1_bn_add_relu_pallas(x, W, gamma, beta, shortcut, *, shift, eps,
                               relu=True):
    """Two-pass recompute Pallas schedule (see module docstring); silently
    delegates to the composed xla backend for configurations the kernel
    does not cover — the same graceful fallback the reference's helper
    loading performs when cuDNN is absent (ConvolutionLayer.java:69-76)."""
    if not pallas_supported(x, W, shortcut):
        return conv1x1_bn_add_relu_xla(x, W, gamma, beta, shortcut,
                                       shift=shift, eps=eps, relu=relu)
    K = x.shape[-1]
    N = W.shape[-1]
    x2 = x.reshape(-1, K)
    sc2 = shortcut.astype(x.dtype).reshape(-1, N)
    y, mean, var = _fused_pallas(x2, W.reshape(K, N).astype(x.dtype),
                                 jnp.asarray(gamma, jnp.float32),
                                 jnp.asarray(beta, jnp.float32),
                                 sc2, float(eps), bool(relu),
                                 jnp.asarray(shift, jnp.float32))
    return y.reshape(shortcut.shape), mean, var


# ------------------------------------------------------- xla recompute
# The schedule the Pallas kernel above implements, expressed as pure XLA:
# on the previous software stack Pallas DMA streamed at 15-60 GB/s
# against XLA's ~700 GB/s (PERF.md Findings, rounds 3-5; not re-measured
# on the installed one), so the SAME two-pass recompute is lowered
# through XLA convs instead. Key facts this relies on (verified via
# compiled cost analysis on the v5e, same rounds):
# - a conv whose output feeds ONLY sibling reductions fuses them into
#   its epilogue WITHOUT materializing the conv output (the stats pass
#   reads x and writes two [N] vectors — nothing else);
# - elementwise chains do NOT output-fuse into convs on this XLA, so
#   the composed formulation materializes z and re-reads it; the
#   recompute apply pass pays one z materialization but the stats pass
#   pays none, and z is never an autodiff residual;
# - jax.lax.optimization_barrier on x blocks CSE from merging the stats
#   conv with the apply conv (a merge would re-serialize the chain and
#   restore the status-quo schedule).


def _conv1x1(x, W):
    """1x1 conv over the trailing channel axis as a convolution HLO (NOT a
    dot: only the conv fuses sibling reductions into its epilogue on this
    XLA). Accepts any leading shape; non-4D inputs ride through a [M,1,1,K]
    view."""
    K, N = W.shape[-2], W.shape[-1]
    x4 = x if x.ndim == 4 else x.reshape(-1, 1, 1, K)
    z = jax.lax.conv_general_dilated(
        x4, W.reshape(1, 1, K, N), (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return z if x.ndim == 4 else z.reshape(x.shape[:-1] + (N,))


def _chan_stats(z, shift):
    """Per-channel mean/var over all-but-last axes, f32 accumulation,
    shifted single-pass variance (see ops/normalization._stats)."""
    axes = tuple(range(z.ndim - 1))
    n = 1
    for a in axes:
        n *= z.shape[a]
    k = jax.lax.stop_gradient(jnp.asarray(shift, jnp.float32))
    zs = z.astype(jnp.float32) - k
    s1 = jnp.sum(zs, axis=axes)
    s2 = jnp.sum(zs * zs, axis=axes)
    m1 = s1 / n
    mean = m1 + k
    var = jnp.maximum(s2 / n - m1 * m1, 0.0)
    return mean, var, n


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def conv1x1_bn_add_relu_recompute(x, W, gamma, beta, shortcut, eps, relu,
                                  shift):
    y, mean, var, _, _ = _recompute_fwd_impl(x, W, gamma, beta, shortcut,
                                             eps, relu, shift)
    return y, mean, var


def _recompute_fwd_impl(x, W, gamma, beta, shortcut, eps, relu, shift):
    cd = x.dtype
    # stats pass: conv consumed ONLY by the fused reductions
    mean, var, _ = _chan_stats(_conv1x1(x, W), shift)
    inv = jax.lax.rsqrt(var + eps)
    scale = jnp.asarray(gamma, jnp.float32) * inv
    sh = jnp.asarray(beta, jnp.float32) - mean * scale
    # apply pass: recompute the conv (barrier blocks CSE with the stats
    # conv) and write the block output directly
    z2 = _conv1x1(jax.lax.optimization_barrier(x), W)
    o = z2 * scale.astype(cd) + sh.astype(cd) + shortcut.astype(cd)
    if relu:
        o = jnp.maximum(o, 0)
    return o, mean, var, inv, scale


def _recompute_fwd(x, W, gamma, beta, shortcut, eps, relu, shift):
    y, mean, var, inv, scale = _recompute_fwd_impl(
        x, W, gamma, beta, shortcut, eps, relu, shift)
    return (y, mean, var), (x, W, gamma, mean, inv, scale, y)


def _recompute_bwd(eps, relu, res, cts):
    g = cts[0]  # stats outputs feed only the running update: zero cotangent
    x, W, gamma, mean, inv, scale, y = res
    cd = x.dtype
    g = g.astype(cd)
    if relu:
        g = jnp.where(y > 0, g, jnp.zeros_like(g))
    axes = tuple(range(g.ndim - 1))
    n = 1
    for a in axes:
        n *= g.shape[a]

    meanc = mean.astype(cd)
    invc = inv.astype(cd)

    # reduction pass: recompute z, all reductions fuse into the conv
    z1 = _conv1x1(jax.lax.optimization_barrier(x), W)
    xhat1 = (z1 - meanc) * invc
    a = jnp.sum(g.astype(jnp.float32), axis=axes)
    b = jnp.sum((g * xhat1).astype(jnp.float32), axis=axes)

    # dz pass: recompute z again (second barrier keeps it separate), form
    # the BN input-cotangent in compute dtype (ops/normalization._bn_bwd
    # arithmetic), then the two matmuls
    z2 = _conv1x1(jax.lax.optimization_barrier(x), W)
    xhat2 = (z2 - meanc) * invc
    dz = scale.astype(cd) * (
        g - (a / n).astype(cd) - xhat2 * (b / n).astype(cd))

    K, N = W.shape[-2], W.shape[-1]
    dx = _conv1x1(dz, jnp.swapaxes(W, -1, -2))
    dW = jax.lax.dot_general(
        x.reshape(-1, K), dz.reshape(-1, N),
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dgamma = b.astype(gamma.dtype)
    dbeta = a.astype(gamma.dtype)
    return dx, dW.astype(W.dtype), dgamma, dbeta, g, None


conv1x1_bn_add_relu_recompute.defvjp(_recompute_fwd, _recompute_bwd)


@registry.register("conv1x1_bn_add_relu", backend="xla_recompute")
def conv1x1_bn_add_relu_xla_recompute(x, W, gamma, beta, shortcut, *,
                                      shift, eps, relu=True):
    """Two-pass recompute schedule lowered through XLA (the backend the
    block-fusion pass uses on TPU). Same signature/semantics as the
    composed backend; equivalence-tested in tests/test_fused_block.py."""
    W2 = W.reshape(W.shape[-2], W.shape[-1]).astype(x.dtype)
    sc = jnp.broadcast_to(shortcut, x.shape[:-1] + (W2.shape[-1],))
    y, mean, var = conv1x1_bn_add_relu_recompute(
        x, W2, jnp.asarray(gamma, jnp.float32),
        jnp.asarray(beta, jnp.float32), sc, float(eps), bool(relu),
        jnp.asarray(shift, jnp.float32))
    return y, mean, var
