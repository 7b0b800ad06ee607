"""The gated short convolution of a convolution-operator decoder layer
(nn/layers/decoder.py ``_ShortConvOperator``): for the input
projection's output ``[B | C | x~]`` (three slices of ``d`` columns, in
this order) and a depthwise causal filter ``w`` of ``K`` positions,

    g_t   = B_t * x~_t                                  (elementwise)
    s_t,c = sum_{k < K} w_c,k g_{t-K+1+k, c}            (g before position 0 is zero)
    y_t   = C_t * s_t

``gated_short_conv(bcx, w)`` takes the projection's output ``[b, L, 3 d]``
as it lies (no slice of it is copied to memory) and ``w`` ``[d, K]``, and
returns ``[b, L, d]`` in ``bcx``'s dtype, the sum in float32.

Two executors, chosen by what a call shows (platform and shapes; no
knob):

- ``xla``: the formula, pad and ``K`` shifted reads, autodiff for the
  backward. The CPU's path and what any shape the kernels refuse falls
  to.
- ``pallas`` (a TPU, or the tests' interpreter): one forward and one
  backward kernel over a grid of (batch, time tile). A grid step holds
  a tile of up to 256 positions (``_TIME_TILES``) of ALL ``3 d``
  columns, one contiguous block of ``bcx``, and the ``HALO`` rows
  before it (the backward: and after it)
  as a second, small block of the same array, so every step is
  independent of the others, each of ``B``, ``C``, ``x~`` (and ``dy``)
  is read once and each result written once; the three slices are lane
  offsets into the block, whole lane tiles, never a copy. Inside a step
  the columns are walked in slabs of ``_SLAB`` so the float32
  temporaries stay small. The backward

      ds = dy * C      dC = dy * s      dg_t = sum_k w_k ds_{t+K-1-k}
      dB = dg * x~     dx~ = dg * B     dw_k = sum_t ds_t g_{t-K+1+k}

  writes ``d_bcx`` as one ``[b, L, 3 d]`` array and sums ``dw`` over the
  grid in one float32 ``[K, d]`` block that stays in VMEM.
  ``short_conv_supported`` says which calls the kernels take.

``dl4j_short_conv_calls_total{direction, backend}`` counts the traces.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops import registry

HALO = 16           # rows of the block before (after) a tile: one bf16 tile
_TIME_TILES = (256, 128, 64, 32, 16)
_SLAB = 512         # columns a pass of the kernel's inner loop holds
_VMEM_LIMIT = 64 * 1024 * 1024

_interpret = registry.pallas_interpret


def _count(direction: str, backend: str) -> None:
    from deeplearning4j_tpu.ops.attention import _count_calls

    _count_calls("dl4j_short_conv_calls_total", "Gated short convolution",
                 direction, backend)


def _check(bcx, w):
    if bcx.ndim != 3 or w.ndim != 2 or bcx.shape[2] != 3 * w.shape[0]:
        raise ValueError(
            f"gated_short_conv takes [b, L, 3 d] and a filter [d, K]; got "
            f"{tuple(bcx.shape)} and {tuple(w.shape)}")


def _formula(bcx, w):
    d, k = w.shape
    length = bcx.shape[1]
    f32 = jnp.float32
    b, c, x = (bcx[..., i * d:(i + 1) * d].astype(f32) for i in range(3))
    g = jnp.pad(b * x, ((0, 0), (k - 1, 0), (0, 0)))
    wf = w.astype(f32)
    s = sum(g[:, i:i + length] * wf[:, i] for i in range(k))
    return (c * s).astype(bcx.dtype)


@registry.register("gated_short_conv", backend="xla")
def gated_short_conv_xla(bcx, w):
    _count("forward", "xla")
    return _formula(bcx, w)


# ------------------------------------------------------------------ pallas
def _time_tile(length: int):
    return next((t for t in _TIME_TILES if length % t == 0), None)


def short_conv_supported(bcx, w) -> bool:
    """Whether the kernels cover this call: whole time tiles (a sequence
    of whole blocks of 16 positions), ``d`` of whole lane tiles, a filter
    no longer than the halo, bfloat16 or float32, and a TPU (or the
    tests' interpret mode) to run them."""
    d, k = w.shape
    if bcx.dtype not in (jnp.bfloat16, jnp.float32):
        return False
    if d % 128 or not 1 <= k <= HALO or _time_tile(bcx.shape[1]) is None:
        return False
    return _interpret() or jax.default_backend() == "tpu"


def _slabs(d: int):
    step = next(s for s in (_SLAB, 256, 128) if d % s == 0)
    return [(c0, step) for c0 in range(0, d, step)]


def _shifted(ext, j, lo, n):
    """Rows ``lo - j .. lo - j + n - 1`` of ``ext`` (``j`` of either
    sign, not 0): a rotation down the sublanes and an aligned slice,
    never an unaligned one."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.roll(ext, j % ext.shape[0], 0)[lo:lo + n]


def _gate(ref, c0, cw, d):
    """``B * x~`` of a ``[1, rows, 3 d]`` block's columns, float32."""
    f32 = jnp.float32
    return (ref[0, :, c0:c0 + cw].astype(f32)
            * ref[0, :, 2 * d + c0:2 * d + c0 + cw].astype(f32))


def _fwd_kernel(w_ref, main_ref, before_ref, y_ref, *, d, k):
    import jax.experimental.pallas as pl

    f32 = jnp.float32
    t = main_ref.shape[1]
    first = (pl.program_id(1) > 0).astype(f32)      # no rows before tile 0
    for c0, cw in _slabs(d):
        g = _gate(main_ref, c0, cw, d)
        ext = jnp.concatenate(
            [_gate(before_ref, c0, cw, d) * first, g], axis=0)
        s = w_ref[k - 1:k, c0:c0 + cw] * g
        for j in range(1, k):
            s = s + w_ref[k - 1 - j:k - j, c0:c0 + cw] * _shifted(
                ext, j, HALO, t)
        y_ref[0, :, c0:c0 + cw] = (
            main_ref[0, :, d + c0:d + c0 + cw].astype(f32) * s
        ).astype(y_ref.dtype)


def _bwd_kernel(w_ref, main_ref, before_ref, after_ref, dy_ref,
                dy_after_ref, dbcx_ref, dw_ref, *, d, k):
    import jax.experimental.pallas as pl

    f32 = jnp.float32
    t = main_ref.shape[1]
    bi, ti = pl.program_id(0), pl.program_id(1)
    first = (ti > 0).astype(f32)
    last = (ti < pl.num_programs(1) - 1).astype(f32)

    @pl.when((bi == 0) & (ti == 0))
    def _():
        dw_ref[:] = jnp.zeros_like(dw_ref)

    for c0, cw in _slabs(d):
        cols = slice(c0, c0 + cw)
        b = main_ref[0, :, c0:c0 + cw].astype(f32)
        c = main_ref[0, :, d + c0:d + c0 + cw].astype(f32)
        x = main_ref[0, :, 2 * d + c0:2 * d + c0 + cw].astype(f32)
        dy = dy_ref[0, :, cols].astype(f32)
        g = b * x
        ext = jnp.concatenate(
            [_gate(before_ref, c0, cw, d) * first, g], axis=0)
        ds = dy * c
        ds_ext = jnp.concatenate(
            [ds, dy_after_ref[0, :, cols].astype(f32)
             * after_ref[0, :, d + c0:d + c0 + cw].astype(f32) * last],
            axis=0)
        s = w_ref[k - 1:k, cols] * g
        dg = w_ref[k - 1:k, cols] * ds
        dw_ref[k - 1:k, cols] += jnp.sum(ds * g, axis=0, keepdims=True)
        for j in range(1, k):
            wj = w_ref[k - 1 - j:k - j, cols]
            before = _shifted(ext, j, HALO, t)              # g_{t-j}
            s = s + wj * before
            dg = dg + wj * _shifted(ds_ext, -j, 0, t)       # ds_{t+j}
            dw_ref[k - 1 - j:k - j, cols] += jnp.sum(
                ds * before, axis=0, keepdims=True)
        out = dbcx_ref.dtype
        dbcx_ref[0, :, c0:c0 + cw] = (dg * x).astype(out)
        dbcx_ref[0, :, d + c0:d + c0 + cw] = (dy * s).astype(out)
        dbcx_ref[0, :, 2 * d + c0:2 * d + c0 + cw] = (dg * b).astype(out)


def _specs(length, d, t):
    """Block specs of a ``[b, L, width]`` array by what a block follows:
    the step's tile, the ``HALO`` rows before it (clamped at the first
    tile, where the kernel zeroes them) or after it (at the last)."""
    import jax.experimental.pallas as pl

    per, blocks = t // HALO, length // HALO

    def main(width):
        return pl.BlockSpec((1, t, width), lambda bi, ti: (bi, ti, 0))

    def before(width):
        return pl.BlockSpec(
            (1, HALO, width),
            lambda bi, ti: (bi, jnp.maximum(ti * per - 1, 0), 0))

    def after(width):
        return pl.BlockSpec(
            (1, HALO, width),
            lambda bi, ti: (bi, jnp.minimum((ti + 1) * per, blocks - 1), 0))

    return main, before, after


def _call(kernel, grid, in_specs, out_specs, out_shape, semantics):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret())


def _filter_spec(d, k):
    import jax.experimental.pallas as pl

    return pl.BlockSpec((k, d), lambda bi, ti: (0, 0))


def _tiled_forward(bcx, w):
    bs, length, _ = bcx.shape
    d, k = w.shape
    t = _time_tile(length)
    main, before, _ = _specs(length, d, t)
    return _call(
        functools.partial(_fwd_kernel, d=d, k=k), (bs, length // t),
        [_filter_spec(d, k), main(3 * d), before(3 * d)], main(d),
        jax.ShapeDtypeStruct((bs, length, d), bcx.dtype),
        ("parallel", "parallel"))(w.astype(jnp.float32).T, bcx, bcx)


def _tiled_backward(bcx, w, dy):
    bs, length, _ = bcx.shape
    d, k = w.shape
    t = _time_tile(length)
    main, before, after = _specs(length, d, t)
    dbcx, dw = _call(
        functools.partial(_bwd_kernel, d=d, k=k), (bs, length // t),
        [_filter_spec(d, k), main(3 * d), before(3 * d), after(3 * d),
         main(d), after(d)],
        [main(3 * d), _filter_spec(d, k)],
        [jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
         jax.ShapeDtypeStruct((k, d), jnp.float32)],
        # dw is summed over the whole grid in one resident block
        ("arbitrary", "arbitrary"))(
            w.astype(jnp.float32).T, bcx, bcx, bcx, dy, dy)
    return dbcx, dw.T.astype(w.dtype)


@jax.custom_vjp
def _tiled(bcx, w):
    return _tiled_forward(bcx, w)


def _tiled_fwd(bcx, w):
    _count("forward", "pallas")
    return _tiled_forward(bcx, w), (bcx, w)


def _tiled_bwd(residuals, dy):
    _count("backward", "pallas")
    return _tiled_backward(*residuals, dy)


_tiled.defvjp(_tiled_fwd, _tiled_bwd)


@registry.register("gated_short_conv", backend="pallas")
def gated_short_conv_pallas(bcx, w):
    """The two kernels; delegates to the xla backend for calls
    ``short_conv_supported`` refuses."""
    if not short_conv_supported(bcx, w):
        return gated_short_conv_xla(bcx, w)
    return _tiled(bcx, w)


def gated_short_conv(bcx, w):
    """``C * conv_K(B * x~)`` of ``bcx`` = ``[B | C | x~]`` [b, L, 3 d]
    and ``w`` [d, K] -> [b, L, d] in ``bcx``'s dtype, differentiable in
    both: resolve the registered backend order and apply."""
    _check(bcx, w)
    return registry.get("gated_short_conv")(bcx, w)
