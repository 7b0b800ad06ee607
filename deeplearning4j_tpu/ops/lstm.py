"""Fused LSTM sequence op: XLA scan backend + Pallas TPU kernel backend.

Parity: the reference's hand-fused LSTM
(deeplearning4j-nn/.../recurrent/LSTMHelpers.java:57 activateHelper,
:271 backpropGradientHelper) whose perf bar is the cuDNN fused LSTM. The
registry seam (ops/registry.py) mirrors the reference's Helper loading
(ConvolutionLayer.java:69-76): ``lstm_sequence`` has an ``xla`` backend
(lax.scan of the cell — what autodiff differentiates) and a ``pallas``
backend (this file's hand-written forward+backward kernels), equivalence
-tested against each other in tests/test_backend_equivalence.py — the
CuDNNGradientChecks.java analogue.

Why a Pallas kernel: the scan path issues ~10 small XLA ops per timestep
and re-reads the recurrent weight Wh from HBM every step (measured 88us
per timestep on a v5e for batch 32, hidden 512 — 0.7% MFU). The Pallas
kernel runs the WHOLE time loop in one kernel launch with Wh and the
(h, c) carry resident in VMEM, streaming xz[t] in and the carried hidden
state h[t], the gates G[t] and c_prev[t] out — the cuDNN-class schedule.
h[t] is the one hidden stream: it is the output (times the mask, taken
outside the kernel), and the backward reads it one block back as
h_prev[t] = h[t-1], with h0 at t = 0. Without a mask (known at trace
time) neither kernel takes a mask operand or does mask arithmetic.

Gate math (Graves formulation with peepholes, order i, f, o, g):
    i = sigmoid(zi + p_i * c_prev)      f = sigmoid(zf + p_f * c_prev)
    g = tanh(zg)                        c = f * c_prev + i * g
    o = sigmoid(zo + p_o * c)           h = o * tanh(c)
Masked steps carry (h, c) through unchanged and emit zero output.

The op consumes the PRE-PROJECTED input xw[t] = x[t] @ Wx (one big MXU
matmul outside the time loop) and the bias b, which it adds itself:
xz[t] = xw[t] + b. The Pallas backend adds b in plain jnp ahead of the
forward kernel, so XLA keeps the add in the projection matmul's epilogue;
its backward kernel emits dxz, from which the caller's autodiff recovers
dWx/dx with dense matmuls, and db = sum over (t, rows) of dz, accumulated
in VMEM beside dWh and dp. Nothing reads dxz a second time to reduce it.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops import activations as act_mod
from deeplearning4j_tpu.ops import registry


# ------------------------------------------------------------------ xla
def _cell_step(Wh, p, gate_act, cell_act, carry, inp):
    h_prev, c_prev = carry
    z, m = inp
    n = h_prev.shape[-1]
    z = z + h_prev @ Wh
    zi, zf, zo, zg = (z[:, :n], z[:, n:2 * n], z[:, 2 * n:3 * n],
                      z[:, 3 * n:])
    i = gate_act(zi + p[0] * c_prev)
    f = gate_act(zf + p[1] * c_prev)
    g = cell_act(zg)
    c = f * c_prev + i * g
    o = gate_act(zo + p[2] * c)
    h = o * cell_act(c)
    if m is None:
        return (h, c), h
    mcol = m[:, None]
    h_keep = jnp.where(mcol > 0, h, h_prev)
    c_keep = jnp.where(mcol > 0, c, c_prev)
    return (h_keep, c_keep), h * mcol


@registry.register("lstm_sequence", backend="xla")
def lstm_sequence_xla(xw_t, bias, h0, c0, Wh, p, mask_t, *,
                      gate_act="sigmoid", cell_act="tanh"):
    """Time-major LSTM over pre-projected inputs.

    xw_t: [t, b, 4n], x @ Wx without the bias; bias: [4n]; h0, c0: [b, n];
    Wh: [n, 4n]; p: [3, n] peepholes; mask_t: [t, b] or None. Returns
    (y_t [t, b, n], hT, cT)."""
    ga = act_mod.get(gate_act) if isinstance(gate_act, str) else gate_act
    ca = act_mod.get(cell_act) if isinstance(cell_act, str) else cell_act
    xz_t = xw_t + bias
    step = partial(_cell_step, Wh, p, ga, ca)
    if mask_t is None:
        (hT, cT), ys = jax.lax.scan(
            lambda carry, z: step(carry, (z, None)), (h0, c0), xz_t)
    else:
        (hT, cT), ys = jax.lax.scan(step, (h0, c0), (xz_t, mask_t))
    return ys, hT, cT


# --------------------------------------------------------------- pallas
_interpret = registry.pallas_interpret


def _pallas_supported(xw_t, h0, gate_act, cell_act):
    if gate_act != "sigmoid" or cell_act != "tanh":
        return False
    if xw_t.dtype not in (jnp.bfloat16, jnp.float32):
        return False
    b, n = h0.shape[-2], h0.shape[-1]
    sublane = 16 if xw_t.dtype == jnp.bfloat16 else 8
    if n % 128 != 0 or b % sublane != 0:
        return False
    if not _interpret() and jax.default_backend() != "tpu":
        return False
    return True


def _fwd_kernel(*refs, masked):
    import jax.experimental.pallas as pl

    m_ref, refs = (refs[0], refs[1:]) if masked else (None, refs)
    (xz_ref, h0_ref, c0_ref, Wh_ref, p_ref,
     hk_ref, hT_ref, cT_ref, G_ref, cprev_ref, h_scr, c_scr) = refs
    t = pl.program_id(0)
    T = pl.num_programs(0)

    @pl.when(t == 0)
    def _():
        h_scr[:] = h0_ref[:].astype(jnp.float32)
        c_scr[:] = c0_ref[:].astype(jnp.float32)

    h_prev = h_scr[:]
    c_prev = c_scr[:]
    cd = xz_ref.dtype
    n = h_prev.shape[-1]

    z = xz_ref[0].astype(jnp.float32) + jnp.dot(
        h_prev.astype(cd), Wh_ref[:], preferred_element_type=jnp.float32)
    pvec = p_ref[:].astype(jnp.float32)
    i = jax.nn.sigmoid(z[:, :n] + pvec[0:1, :] * c_prev)
    f = jax.nn.sigmoid(z[:, n:2 * n] + pvec[1:2, :] * c_prev)
    g = jnp.tanh(z[:, 3 * n:])
    c = f * c_prev + i * g
    o = jax.nn.sigmoid(z[:, 2 * n:3 * n] + pvec[2:3, :] * c)
    h = o * jnp.tanh(c)

    if masked:
        keep = m_ref[0].astype(jnp.float32) > 0
        h = jnp.where(keep, h, h_prev)
        c = jnp.where(keep, c, c_prev)

    hk_ref[0] = h.astype(cd)
    G_ref[0] = jnp.concatenate([i, f, o, g], axis=-1).astype(cd)
    cprev_ref[0] = c_prev.astype(cd)
    h_scr[:] = h
    c_scr[:] = c

    @pl.when(t == T - 1)
    def _():
        hT_ref[:] = h.astype(cd)
        cT_ref[:] = c.astype(cd)


def _bwd_kernel(*refs, masked):
    import jax.experimental.pallas as pl

    m_ref, refs = (refs[0], refs[1:]) if masked else (None, refs)
    (G_ref, hk_ref, cprev_ref, h0_ref, Wh_ref, p_ref,
     dhk_ref, dhT_ref, dcT_ref,
     dxz_ref, dh0_ref, dc0_ref, dWh_ref, dp_ref, db_ref,
     dh_scr, dc_scr, dWh_scr, dp_scr, db_scr) = refs
    pid = pl.program_id(0)
    T = pl.num_programs(0)

    @pl.when(pid == 0)
    def _():
        dh_scr[:] = dhT_ref[:].astype(jnp.float32)
        dc_scr[:] = dcT_ref[:].astype(jnp.float32)
        dWh_scr[:] = jnp.zeros_like(dWh_scr)
        dp_scr[:] = jnp.zeros_like(dp_scr)
        db_scr[:] = jnp.zeros_like(db_scr)

    cd = G_ref.dtype
    n = cprev_ref.shape[-1]
    G = G_ref[0].astype(jnp.float32)
    i, f, o, g = (G[:, :n], G[:, n:2 * n], G[:, 2 * n:3 * n], G[:, 3 * n:])
    c_prev = cprev_ref[0].astype(jnp.float32)
    pvec = p_ref[:].astype(jnp.float32)

    c = f * c_prev + i * g
    tc = jnp.tanh(c)

    # what reaches the carried (h, c) of this step: a kept row hands it
    # to the cell, a masked row hands it on to the step before
    dh = dh_scr[:] + dhk_ref[0].astype(jnp.float32)
    dc = dc_scr[:]
    if masked:
        keep = m_ref[0].astype(jnp.float32) > 0
        dh_skip = jnp.where(keep, 0.0, dh)
        dc_skip = jnp.where(keep, 0.0, dc)
        dh = jnp.where(keep, dh, 0.0)
        dc = jnp.where(keep, dc, 0.0)

    do = dh * tc
    dzo = do * o * (1.0 - o)
    dc_in = dc + dh * o * (1.0 - tc * tc) + dzo * pvec[2:3, :]
    di = dc_in * g
    df = dc_in * c_prev
    dg = dc_in * i
    dzi = di * i * (1.0 - i)
    dzf = df * f * (1.0 - f)
    dzg = dg * (1.0 - g * g)

    dz = jnp.concatenate([dzi, dzf, dzo, dzg], axis=-1)
    dz_cd = dz.astype(cd)

    # dh_prev = dz @ Wh^T  (contract the 4n dim)
    dh_prev = jax.lax.dot_general(
        dz_cd, Wh_ref[:], dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    dc_prev = dc_in * f + dzi * pvec[0:1, :] + dzf * pvec[1:2, :]
    if masked:
        dh_prev = dh_prev + dh_skip
        dc_prev = dc_prev + dc_skip

    # dWh += h_prev^T @ dz  (contract the batch dim). h_prev of step t is
    # the block the forward wrote at t - 1, which hk_ref holds here, and
    # h0 at t = 0, the last grid step. A select, not two pl.when bodies:
    # on the chip a branch round this matmul cost 0.65 us a grid step
    # (b=256, n=512: 7.59 ms a call against 6.92)
    h_prev = jnp.where(pid == T - 1, h0_ref[:], hk_ref[0])
    dWh_scr[:] += jax.lax.dot_general(
        h_prev, dz_cd, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dp_scr[0:1, :] += jnp.sum(dzi * c_prev, axis=0, keepdims=True)
    dp_scr[1:2, :] += jnp.sum(dzf * c_prev, axis=0, keepdims=True)
    dp_scr[2:3, :] += jnp.sum(dzo * c, axis=0, keepdims=True)
    # db += column sums of the f32 dz (zero in masked rows), kept as eight
    # sublane partials: the rows fold onto one [8, 4n] tile with
    # whole-register adds, and the sublanes are reduced once, at the end
    db_scr[:] += jnp.sum(dz.reshape(-1, 8, 4 * n), axis=0)

    dxz_ref[0] = dz_cd
    dh_scr[:] = dh_prev
    dc_scr[:] = dc_prev

    @pl.when(pid == T - 1)
    def _():
        dh0_ref[:] = dh_prev.astype(cd)
        dc0_ref[:] = dc_prev.astype(cd)
        dWh_ref[:] = dWh_scr[:].astype(cd)
        dp_ref[:] = dp_scr[:].astype(cd)
        db_ref[:] = jnp.sum(db_scr[:], axis=0, keepdims=True).astype(cd)


# Mosaic's default scoped-VMEM limit on a v5e is 16 MiB, and the backward
# at b=256, n=512 in bf16 asks for 16.06 MiB (measured on the chip:
# RESOURCE_EXHAUSTED by 64 KiB). So each call states what it needs, and
# never less than the default. The largest request the chip has been
# seen to grant is that shape's masked backward, 36.5 MiB (36.4 without
# a mask; the forward asks 25.0); the cap is not verified.
_VMEM_DEFAULT = 16 * 1024 * 1024
_VMEM_CAP = 96 * 1024 * 1024


def _compiler_params(arrays, scratch):
    """``vmem_limit_bytes`` from the call's own operands. Every 3-d
    ``[T, ...]`` array streams one ``[1, ...]`` block per grid step and
    every other array is one block at a constant index; Pallas
    double-buffers both kinds. ``scratch`` is resident once, and the
    gate math keeps about four f32 temporaries as wide as the widest
    streamed block ([b, 4n]) live. 25% headroom over that sum for
    Mosaic's own spills."""
    from jax.experimental.pallas import tpu as pltpu

    def nbytes(shape, dtype):       # the last dim pads to a 128 lane
        return (math.prod(shape[:-1]) * -(-shape[-1] // 128) * 128
                * jnp.dtype(dtype).itemsize)

    blocks = [nbytes(a.shape[1:] if len(a.shape) == 3 else a.shape, a.dtype)
              for a in arrays]
    widest = max(math.prod(a.shape[1:]) for a in arrays
                 if len(a.shape) == 3)
    need = (2 * sum(blocks) + sum(nbytes(r.shape, r.dtype) for r in scratch)
            + 4 * widest * 4)
    limit = min(_VMEM_CAP, max(_VMEM_DEFAULT, need + need // 4))
    return pltpu.CompilerParams(vmem_limit_bytes=int(limit))


def _fwd_call(xz_t, h0, c0, Wh, p, mask_t):
    """(hk, hT, cT, G, c_prev): hk[t] is the hidden state carried out of
    step t, which is the step's output where the mask keeps the row.
    ``mask_t`` None leaves the mask operand and its arithmetic out."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, b, n4 = xz_t.shape
    n = n4 // 4
    cd = xz_t.dtype
    masked = mask_t is not None
    sds = jax.ShapeDtypeStruct
    out_shapes = (
        sds((T, b, n), cd),    # hk
        sds((b, n), cd),       # hT
        sds((b, n), cd),       # cT
        sds((T, b, n4), cd),   # G (gates i,f,o,g)
        sds((T, b, n), cd),    # c_prev per step
    )
    t_block = lambda width: pl.BlockSpec(
        (1, b, width), lambda t: (t, 0, 0), memory_space=pltpu.VMEM)
    fixed2 = lambda r, cdim: pl.BlockSpec(
        (r, cdim), lambda t: (0, 0), memory_space=pltpu.VMEM)
    args = (xz_t, h0, c0, Wh, p)
    in_specs = [
        t_block(n4),                                         # xz
        fixed2(b, n), fixed2(b, n),                          # h0, c0
        fixed2(n, n4),                                       # Wh
        fixed2(3, n),                                        # p
    ]
    if masked:
        args = (mask_t[:, :, None],) + args
        in_specs.insert(0, t_block(1))                       # mask [t,b,1]
    scratch = [pltpu.VMEM((b, n), jnp.float32),
               pltpu.VMEM((b, n), jnp.float32)]
    return pl.pallas_call(
        partial(_fwd_kernel, masked=masked),
        grid=(T,),
        in_specs=in_specs,
        out_specs=(
            t_block(n),                                      # hk
            fixed2(b, n), fixed2(b, n),                      # hT, cT
            t_block(n4),                                     # G
            t_block(n),                                      # c_prev
        ),
        out_shape=out_shapes,
        scratch_shapes=scratch,
        compiler_params=_compiler_params(args + out_shapes, scratch),
        interpret=_interpret(),
    )(*args)


def _bwd_call(res, cts):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    G, hk, cprev, h0, mask_t, Wh, p = res
    dhk, dhT, dcT = cts
    T, b, n = hk.shape
    n4 = 4 * n
    cd = G.dtype
    masked = mask_t is not None
    dhk = dhk.astype(cd)
    dhT = dhT.astype(cd)
    dcT = dcT.astype(cd)
    sds = jax.ShapeDtypeStruct
    out_shapes = (
        sds((T, b, n4), cd),   # dxz
        sds((b, n), cd),       # dh0
        sds((b, n), cd),       # dc0
        sds((n, n4), cd),      # dWh
        sds((3, n), cd),       # dp
        sds((1, n4), cd),      # db
    )
    rev = lambda width: pl.BlockSpec(
        (1, b, width), lambda i: (T - 1 - i, 0, 0), memory_space=pltpu.VMEM)
    fixed2 = lambda r, cdim: pl.BlockSpec(
        (r, cdim), lambda i: (0, 0), memory_space=pltpu.VMEM)
    args = (G, hk, cprev, h0, Wh, p, dhk, dhT, dcT)
    in_specs = [
        rev(n4),                                             # G
        # the hidden state carried INTO step t is hk's block t - 1; the
        # last grid step (t = 0) reads h0 instead and the block it is
        # given here, block 0 again, costs no new DMA
        pl.BlockSpec((1, b, n), lambda i: (jnp.maximum(T - 2 - i, 0), 0, 0),
                     memory_space=pltpu.VMEM),               # hk, one back
        rev(n),                                              # c_prev
        fixed2(b, n),                                        # h0
        fixed2(n, n4),                                       # Wh
        fixed2(3, n),                                        # p
        rev(n),                                              # dhk
        fixed2(b, n), fixed2(b, n),                          # dhT, dcT
    ]
    if masked:
        args = (mask_t[:, :, None],) + args
        in_specs.insert(0, rev(1))                           # mask [t,b,1]
    scratch = [pltpu.VMEM((b, n), jnp.float32),
               pltpu.VMEM((b, n), jnp.float32),
               pltpu.VMEM((n, n4), jnp.float32),
               pltpu.VMEM((3, n), jnp.float32),
               pltpu.VMEM((8, n4), jnp.float32)]
    return pl.pallas_call(
        partial(_bwd_kernel, masked=masked),
        grid=(T,),
        in_specs=in_specs,
        out_specs=(
            rev(n4),                                         # dxz
            fixed2(b, n), fixed2(b, n),                      # dh0, dc0
            fixed2(n, n4),                                   # dWh
            fixed2(3, n),                                    # dp
            fixed2(1, n4),                                   # db
        ),
        out_shape=out_shapes,
        scratch_shapes=scratch,
        compiler_params=_compiler_params(args + out_shapes, scratch),
        interpret=_interpret(),
    )(*args)


@jax.custom_vjp
def _lstm_seq_kernels(xw_t, bias, h0, c0, Wh, p, mask_t):
    return _lstm_seq_fwd(xw_t, bias, h0, c0, Wh, p, mask_t)[0]


def _lstm_seq_fwd(xw_t, bias, h0, c0, Wh, p, mask_t):
    # the bias add stays in plain jnp, ahead of the kernel: XLA fuses it
    # into the projection matmul that made xw_t
    hk, hT, cT, G, cprev = _fwd_call(xw_t + bias, h0, c0, Wh, p, mask_t)
    return (hk, hT, cT), (G, hk, cprev, h0, mask_t, Wh, p)


def _lstm_seq_bwd(res, cts):
    dxz, dh0, dc0, dWh, dp, db = _bwd_call(res, cts)
    return dxz, db[0], dh0, dc0, dWh, dp, None


_lstm_seq_kernels.defvjp(_lstm_seq_fwd, _lstm_seq_bwd)


def _lstm_seq_pallas(xw_t, bias, h0, c0, Wh, p, mask_t):
    """The two kernels under one custom VJP give the carried hidden
    state; a masked step's output is zero, and that product is plain jnp
    so that autodiff hands the kernel ``dy * mask``. ``mask_t`` is
    [t, b] in the dtype of ``xw_t``, or None."""
    hk, hT, cT = _lstm_seq_kernels(xw_t, bias, h0, c0, Wh, p, mask_t)
    y = hk if mask_t is None else hk * mask_t[:, :, None]
    return y, hT, cT


@registry.register("lstm_sequence", backend="pallas")
def lstm_sequence_pallas(xw_t, bias, h0, c0, Wh, p, mask_t, *,
                         gate_act="sigmoid", cell_act="tanh"):
    """Pallas-fused LSTM sequence; silently delegates to the xla backend
    for configurations the kernel does not cover (non-sigmoid/tanh
    activations, unaligned shapes, non-TPU platforms) — the same graceful
    fallback the reference's helper loading performs when cuDNN is absent
    (ConvolutionLayer.java:69-76)."""
    if not _pallas_supported(xw_t, h0, gate_act, cell_act):
        return lstm_sequence_xla(xw_t, bias, h0, c0, Wh, p, mask_t,
                                 gate_act=gate_act, cell_act=cell_act)
    if mask_t is not None:
        mask_t = mask_t.astype(xw_t.dtype)
    return _lstm_seq_pallas(xw_t, bias, h0, c0, Wh, p, mask_t)
