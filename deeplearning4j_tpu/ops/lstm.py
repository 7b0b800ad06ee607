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
(h, c) carry resident in VMEM, streaming the input rows in and the
carried hidden state h[t], the gates G[t] and c_prev[t] out — the
cuDNN-class schedule. h[t] is the one hidden stream: it is the output
(times the mask, taken outside the kernel), and the backward reads it
one row back as h_prev[t] = h[t-1], with h0 at t = 0. Without a mask
(known at trace time) neither kernel takes a mask operand or does mask
arithmetic.

A grid step is Tb consecutive timesteps, not one: every streamed operand
and result moves in (Tb, b, width) blocks, the backward walks the blocks
and the timesteps inside a block in reverse, and the carry goes through
the f32 scratch between grid steps only. The body runs the Tb timesteps
as one straight-line block, a Python loop over static indices with the
carry as values and no branch inside it (the ``pl.when`` set-up and
write-out stand before and after it). A basic block ends at every grid
step and at every branch, and nothing is scheduled across that edge: on
a v5e at b=256, n=512 one branch round one matmul cost 0.65 us a grid
step, and the backward, whose dWh matmul and gate derivatives wait for
no dh, runs four timesteps a grid step in 6.00 us each where one takes
6.71. Tb is the longest of 4, 2, 1 that divides T and whose blocks fit
the VMEM cap (``_time_block``; why not 8 is told at ``_TIME_BLOCKS``):
a function of the call's shapes, not an option. T = 1
(``rnn_time_step``, decode) and a prime T get Tb = 1, the kernel of one
timestep a grid step; which Tb a call got is counted in
``dl4j_lstm_kernel_calls_total{direction, time_block}``.

Gate math (Graves formulation with peepholes, order i, f, o, g):
    i = sigmoid(zi + p_i * c_prev)      f = sigmoid(zf + p_f * c_prev)
    g = tanh(zg)                        c = f * c_prev + i * g
    o = sigmoid(zo + p_o * c)           h = o * tanh(c)
Masked steps carry (h, c) through unchanged and emit zero output.

The op owns the input projection and the bias: it takes x [t, b, n_in],
Wx [n_in, 4n] and b, and xz[t] = x[t] @ Wx + b is its own first step. On
the xla backend that is one einsum over the whole sequence, the bias
added, ahead of the scan. The Pallas backend chooses by the shapes of
the call (``_kernel_projects``). An input no wider than the hidden state
(one-hot characters, an embedding, a stacked layer of the same width) is
projected by the forward kernel itself: xz would be 4n / n_in times the
bytes of x, written once by a matmul and read once by the kernel (1.07
GB a step each way at b=256, n=512, T=1,024), so the kernel streams the
(Tb, b, n_in) block of x instead, holds Wx and b in VMEM beside Wh, and
forms z = x[t] @ Wx + b + h_prev @ Wh in f32 (xz no longer passes
through the compute dtype). x[t] @ Wx waits for no h: it stands in the
straight-line block off the recurrence's chain, where the MXU is idle
while the gate math runs. A wider input (n_in > n), or weights that do
not fit VMEM together, is projected by one matmul outside the time loop,
with the bias add as its epilogue, and the forward kernel streams xz in.
The backward kernel is the same whoever projected: it emits dxz, and
db = sum over (t, rows) of dz, accumulated in VMEM beside dWh and dp
(nothing reads dxz a second time to reduce it); the custom VJP then
takes dWx = x^T dxz and dx = dxz Wx^T as dense matmuls over the whole
sequence. Which way a call went is counted in
``dl4j_lstm_kernel_calls_total{projection}``.
"""

from __future__ import annotations

import math
from functools import lru_cache, partial

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


def _project(x_t, Wx, bias):
    """xz_t [t, b, 4n] = x_t @ Wx + bias: one matmul over the whole
    sequence, outside the time loop, with the bias add as its epilogue."""
    return jnp.einsum("tbf,fg->tbg", x_t, Wx) + bias


@registry.register("lstm_sequence", backend="xla")
def lstm_sequence_xla(x_t, Wx, bias, h0, c0, Wh, p, mask_t, *,
                      gate_act="sigmoid", cell_act="tanh"):
    """Time-major LSTM over a sequence of inputs.

    x_t: [t, b, n_in]; Wx: [n_in, 4n]; bias: [4n]; h0, c0: [b, n];
    Wh: [n, 4n]; p: [3, n] peepholes; mask_t: [t, b] or None. Returns
    (y_t [t, b, n], hT, cT)."""
    ga = act_mod.get(gate_act) if isinstance(gate_act, str) else gate_act
    ca = act_mod.get(cell_act) if isinstance(cell_act, str) else cell_act
    xz_t = _project(x_t, Wx, bias)
    step = partial(_cell_step, Wh, p, ga, ca)
    if mask_t is None:
        (hT, cT), ys = jax.lax.scan(
            lambda carry, z: step(carry, (z, None)), (h0, c0), xz_t)
    else:
        (hT, cT), ys = jax.lax.scan(step, (h0, c0), (xz_t, mask_t))
    return ys, hT, cT


# --------------------------------------------------------------- pallas
_interpret = registry.pallas_interpret


def _pallas_supported(x_t, h0, gate_act, cell_act):
    if gate_act != "sigmoid" or cell_act != "tanh":
        return False
    if x_t.dtype not in (jnp.bfloat16, jnp.float32):
        return False
    b, n = h0.shape[-2], h0.shape[-1]
    sublane = 16 if x_t.dtype == jnp.bfloat16 else 8
    if n % 128 != 0 or b % sublane != 0:
        return False
    if not _interpret() and jax.default_backend() != "tpu":
        return False
    return True


def _fwd_kernel(*refs, masked, projected, Tb):
    import jax.experimental.pallas as pl

    m_ref, refs = (refs[0], refs[1:]) if masked else (None, refs)
    # the streamed input: xz rows, or the x rows the kernel projects
    x_ref, refs = refs[0], refs[1:]
    Wx_ref, b_ref, refs = (refs[0], refs[1], refs[2:]) if projected else (
        None, None, refs)
    (h0_ref, c0_ref, Wh_ref, p_ref,
     hk_ref, hT_ref, cT_ref, G_ref, cprev_ref, h_scr, c_scr) = refs
    t = pl.program_id(0)
    T = pl.num_programs(0)

    @pl.when(t == 0)
    def _():
        h_scr[:] = h0_ref[:].astype(jnp.float32)
        c_scr[:] = c0_ref[:].astype(jnp.float32)

    h = h_scr[:]
    c = c_scr[:]
    cd = x_ref.dtype
    n = h.shape[-1]
    pvec = p_ref[:].astype(jnp.float32)
    if projected:
        bias = b_ref[:].astype(jnp.float32)

    # the block's Tb timesteps, straight-line: (h, c) are values from one
    # to the next, and no branch stands between them
    for s in range(Tb):
        h_prev, c_prev = h, c
        if projected:
            # x[s] @ Wx + b waits for no h: it stands in the block beside
            # the recurrence's chain, not on it
            xz = jnp.dot(x_ref[s], Wx_ref[:],
                         preferred_element_type=jnp.float32) + bias
        else:
            xz = x_ref[s].astype(jnp.float32)
        z = xz + jnp.dot(
            h_prev.astype(cd), Wh_ref[:], preferred_element_type=jnp.float32)
        i = jax.nn.sigmoid(z[:, :n] + pvec[0:1, :] * c_prev)
        f = jax.nn.sigmoid(z[:, n:2 * n] + pvec[1:2, :] * c_prev)
        g = jnp.tanh(z[:, 3 * n:])
        c = f * c_prev + i * g
        o = jax.nn.sigmoid(z[:, 2 * n:3 * n] + pvec[2:3, :] * c)
        h = o * jnp.tanh(c)

        if masked:
            keep = m_ref[s].astype(jnp.float32) > 0
            h = jnp.where(keep, h, h_prev)
            c = jnp.where(keep, c, c_prev)

        hk_ref[s] = h.astype(cd)
        G_ref[s] = jnp.concatenate([i, f, o, g], axis=-1).astype(cd)
        cprev_ref[s] = c_prev.astype(cd)

    h_scr[:] = h
    c_scr[:] = c

    @pl.when(t == T - 1)
    def _():
        hT_ref[:] = h.astype(cd)
        cT_ref[:] = c.astype(cd)


def _bwd_kernel(*refs, masked, Tb):
    import jax.experimental.pallas as pl

    m_ref, refs = (refs[0], refs[1:]) if masked else (None, refs)
    G_ref, refs = refs[0], refs[1:]
    # the time block's own rows of the hidden stream, which a block of one
    # timestep does not read
    hk_ref, refs = (refs[0], refs[1:]) if Tb > 1 else (None, refs)
    (hback_ref, cprev_ref, h0_ref, Wh_ref, p_ref,
     dhk_ref, dhT_ref, dcT_ref,
     dxz_ref, dh0_ref, dc0_ref, dWh_ref, dp_ref, db_ref,
     dh_scr, dc_scr, dWh_scr, dp_scr, db_scr) = refs
    pid = pl.program_id(0)
    nb = pl.num_programs(0)

    @pl.when(pid == 0)
    def _():
        dh_scr[:] = dhT_ref[:].astype(jnp.float32)
        dc_scr[:] = dcT_ref[:].astype(jnp.float32)
        dWh_scr[:] = jnp.zeros_like(dWh_scr)
        dp_scr[:] = jnp.zeros_like(dp_scr)
        db_scr[:] = jnp.zeros_like(db_scr)

    cd = G_ref.dtype
    n = cprev_ref.shape[-1]
    pvec = p_ref[:].astype(jnp.float32)
    dh_next = dh_scr[:]
    dc_next = dc_scr[:]
    acc = None

    # the block's Tb timesteps from the last to the first, straight-line:
    # (dh, dc) are values from one to the next, and no branch stands
    # between them
    for s in reversed(range(Tb)):
        G = G_ref[s].astype(jnp.float32)
        i, f, o, g = (G[:, :n], G[:, n:2 * n], G[:, 2 * n:3 * n],
                      G[:, 3 * n:])
        c_prev = cprev_ref[s].astype(jnp.float32)

        c = f * c_prev + i * g
        tc = jnp.tanh(c)

        # what reaches the carried (h, c) of this step: a kept row hands
        # it to the cell, a masked row hands it on to the step before
        dh = dh_next + dhk_ref[s].astype(jnp.float32)
        dc = dc_next
        if masked:
            keep = m_ref[s].astype(jnp.float32) > 0
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
        dh_next = jax.lax.dot_general(
            dz_cd, Wh_ref[:], dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dc_next = dc_in * f + dzi * pvec[0:1, :] + dzf * pvec[1:2, :]
        if masked:
            dh_next = dh_next + dh_skip
            dc_next = dc_next + dc_skip

        # the sums a timestep adds to dp and db are values until the block
        # ends: Tb read-modify-writes of the scratch in one body ran the
        # backward at 7.88 ms a call where this runs at 6.10 (Tb = 8).
        # db: column sums of the f32 dz (zero in masked rows), kept as
        # eight sublane partials: the rows fold onto one [8, 4n] tile with
        # whole-register adds, and the sublanes are reduced once, at the
        # end
        sums = [jnp.sum(dzi * c_prev, axis=0, keepdims=True),
                jnp.sum(dzf * c_prev, axis=0, keepdims=True),
                jnp.sum(dzo * c, axis=0, keepdims=True),
                jnp.sum(dz.reshape(-1, 8, 4 * n), axis=0)]
        acc = sums if acc is None else [a + x for a, x in zip(acc, sums)]

        dxz_ref[s] = dz_cd

    # dWh += h_prev^T @ dz  (contract the batch dim), the block's Tb terms
    # at once. h_prev of a timestep is the row the forward wrote one
    # timestep before. For the block's later timesteps that is the block's
    # own rows 0 .. Tb - 2, against the dz just stored in rows 1 .. Tb - 1
    # of the dxz block: one contraction over (Tb - 1) * b rows. For its
    # first timestep (the dz still at hand) it is the last row of the time
    # block before, which hback_ref holds, or h0 at t = 0, the last grid
    # step. A select, not two pl.when bodies: on the chip a branch round
    # this matmul cost 0.65 us a grid step (b=256, n=512, Tb = 1: 7.59 ms
    # a call against 6.92)
    by_rows = (((0,), (0,)), ((), ()))
    if Tb > 1:
        rows = (Tb - 1) * dz_cd.shape[0]
        dWh_scr[:] += jax.lax.dot_general(
            hk_ref[0:Tb - 1].reshape(rows, n),
            dxz_ref[1:Tb].reshape(rows, 4 * n), dimension_numbers=by_rows,
            preferred_element_type=jnp.float32)
    h_prev = jnp.where(pid == nb - 1, h0_ref[:], hback_ref[0])
    dWh_scr[:] += jax.lax.dot_general(
        h_prev, dz_cd, dimension_numbers=by_rows,
        preferred_element_type=jnp.float32)
    for k in range(3):
        dp_scr[k:k + 1, :] += acc[k]
    db_scr[:] += acc[3]

    dh_scr[:] = dh_next
    dc_scr[:] = dc_next

    @pl.when(pid == nb - 1)
    def _():
        dh0_ref[:] = dh_next.astype(cd)
        dc0_ref[:] = dc_next.astype(cd)
        dWh_ref[:] = dWh_scr[:].astype(cd)
        dp_ref[:] = dp_scr[:].astype(cd)
        db_ref[:] = jnp.sum(db_scr[:], axis=0, keepdims=True).astype(cd)


# Mosaic's default scoped-VMEM limit on a v5e is 16 MiB, and the backward
# at b=256, n=512 in bf16 asks for 16.06 MiB (measured on the chip:
# RESOURCE_EXHAUSTED by 64 KiB). So each call states what it needs, and
# never less than the default. The largest request the chip has been
# seen to grant is 86.4 MiB (that shape's masked backward at eight
# timesteps a block; Mosaic itself needed 62.1 MiB of it), and a
# compile for a described v5e takes 127 MiB: the cap is below both.
_VMEM_DEFAULT = 16 * 1024 * 1024
_VMEM_CAP = 96 * 1024 * 1024
# Timesteps per grid step, the longest first (``_time_block``). On a v5e
# at b=256, n=512, T=1,024 in bf16 the backward takes 6,871 / 6,416 /
# 6,146 / 6,068 us a call alone at 1 / 2 / 4 / 8 and the forward 4,165 /
# 4,117 / 4,109 / 4,107 (it is held by its HBM streams). But inside the
# char-RNN step program the backward of 8 takes 7,937 us, 14 us a grid
# step more, and with a mask 7,581 even alone (7,026 at 1): the unrolled
# body of 8 timesteps is 2.9 MB of instructions. At 4 it takes 6,233 in
# the step and 6,312 masked. So the ladder starts at 4.
_TIME_BLOCKS = (4, 2, 1)


def _vmem_request(blocks, scratch):
    """What one call needs of VMEM, from its own ``blocks``, the (block
    shape, dtype) of every operand and result. Pallas double-buffers
    every block, streamed or at a constant index. ``scratch`` is resident
    once, and the gate math keeps about four f32 temporaries as wide as
    one timestep's widest streamed row ([b, 4n]) live. 25% headroom over
    that sum for Mosaic's own spills."""

    def nbytes(shape, dtype):       # the last dim pads to a 128 lane
        return (math.prod(shape[:-1]) * -(-shape[-1] // 128) * 128
                * jnp.dtype(dtype).itemsize)

    widest = max(math.prod(shape[1:]) for shape, _ in blocks
                 if len(shape) == 3)
    need = (2 * sum(nbytes(shape, dtype) for shape, dtype in blocks)
            + sum(nbytes(r.shape, r.dtype) for r in scratch)
            + 4 * widest * 4)
    return need + need // 4


def _time_block(T, request):
    """Timesteps per grid step: the longest of ``_TIME_BLOCKS`` that
    divides ``T`` and whose ``request(Tb)`` of VMEM is under the cap. A
    function of the call's shapes alone; 1 is the kernel of one timestep
    a grid step, whatever it asks."""
    for Tb in _TIME_BLOCKS:
        if Tb == 1 or (T % Tb == 0 and request(Tb) <= _VMEM_CAP):
            return Tb


def _kernel_projects(n_in, n, request):
    """Whether the forward kernel makes x @ Wx + b itself, from the call's
    shapes alone: where the input is no wider than the hidden state, and
    the kernel of one timestep a grid step with Wx resident beside Wh
    asks for no more VMEM than the cap (``request(1)``; f32 at n = 1,536
    is over it). Then the [t, b, 4n] xz that an outside matmul would
    write and the kernel read back is 4n / n_in times the x rows read
    instead, and the projection's n_in / n of the recurrent matmul's work
    finds the MXU idle while the gate math of the recurrence runs (on a
    v5e at b=256, n=512 a timestep takes 4.0 us given xz, 3.9 us with 80
    columns to project and 5.95 us with 512, where the outside matmul
    took 2.8 us more). A wider input costs the kernel more MXU time than
    its recurrence leaves idle, and stays one matmul ahead of it."""
    return n_in <= n and request(1) <= _VMEM_CAP


def _blocked_call(kernel, Tb, T, in_specs, out_specs, out_shapes, scratch,
                  interpret):
    """(call, request): the ``pallas_call`` of ``kernel`` over ``T // Tb``
    grid steps, and the VMEM it asks for. The call is jitted to be
    inlined: the layers and programs of one process that run the same
    shapes then trace and lower the kernel body once between them, where
    an unrolled body traced at every call site cost the char-RNN job 0.7 s
    of set-up."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    specs = list(in_specs) + list(out_specs)
    request = _vmem_request(
        [(spec.block_shape, out_shapes[0].dtype) for spec in specs], scratch)
    limit = min(_VMEM_CAP, max(_VMEM_DEFAULT, request))
    call = pl.pallas_call(
        partial(kernel, Tb=Tb),
        grid=(T // Tb,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=int(limit)),
        interpret=interpret,
    )
    return jax.jit(call, inline=True), request


def _count_call(direction, Tb, projected=False):
    from deeplearning4j_tpu.observability.metrics import get_registry

    get_registry().counter(
        "dl4j_lstm_kernel_calls_total",
        "Pallas LSTM kernel calls traced, by direction, by the timesteps "
        "one grid step takes and by where the input projection is made",
        ("direction", "time_block", "projection")).labels(
            direction=direction, time_block=str(Tb),
            projection="kernel" if projected else "outside").inc()


def _fixed2(r, cdim):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.BlockSpec((r, cdim), lambda t: (0, 0),
                        memory_space=pltpu.VMEM)


@lru_cache(maxsize=None)
def _fwd_blocked(T, b, n, n_in, cd, masked, *, Tb, interpret):
    """``n_in`` is the width of the x rows the kernel projects, or None
    where it is given xz."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n4 = 4 * n
    projected = n_in is not None
    sds = jax.ShapeDtypeStruct
    out_shapes = (
        sds((T, b, n), cd),    # hk
        sds((b, n), cd),       # hT
        sds((b, n), cd),       # cT
        sds((T, b, n4), cd),   # G (gates i,f,o,g)
        sds((T, b, n), cd),    # c_prev per step
    )
    t_block = lambda width: pl.BlockSpec(
        (Tb, b, width), lambda t: (t, 0, 0), memory_space=pltpu.VMEM)
    if projected:
        in_specs = [t_block(n_in),                           # x
                    _fixed2(n_in, n4), _fixed2(1, n4)]       # Wx, b
    else:
        in_specs = [t_block(n4)]                             # xz
    in_specs += [
        _fixed2(b, n), _fixed2(b, n),                        # h0, c0
        _fixed2(n, n4),                                      # Wh
        _fixed2(3, n),                                       # p
    ]
    if masked:
        in_specs.insert(0, t_block(1))                       # mask [t,b,1]
    out_specs = (
        t_block(n),                                          # hk
        _fixed2(b, n), _fixed2(b, n),                        # hT, cT
        t_block(n4),                                         # G
        t_block(n),                                          # c_prev
    )
    scratch = [pltpu.VMEM((b, n), jnp.float32),
               pltpu.VMEM((b, n), jnp.float32)]
    return _blocked_call(
        partial(_fwd_kernel, masked=masked, projected=projected), Tb, T,
        in_specs, out_specs, out_shapes, scratch, interpret)


def _fwd_blocked_for(x_t, h0, mask_t, n_in):
    """``_fwd_blocked`` of a call's shapes, with ``Tb`` left open."""
    T, b, _ = x_t.shape
    return partial(_fwd_blocked, T, b, h0.shape[-1], n_in,
                   jnp.dtype(x_t.dtype), mask_t is not None,
                   interpret=_interpret())


def _fwd_call(x_t, h0, c0, Wh, p, mask_t, Wx=None, bias=None):
    """(hk, hT, cT, G, c_prev): hk[t] is the hidden state carried out of
    step t, which is the step's output where the mask keeps the row.
    ``x_t`` is xz, or with ``Wx`` and ``bias`` the x rows the kernel
    projects itself. ``mask_t`` None leaves the mask operand and its
    arithmetic out."""
    T = x_t.shape[0]
    projected = Wx is not None
    blocked = _fwd_blocked_for(x_t, h0, mask_t,
                               x_t.shape[-1] if projected else None)
    Tb = _time_block(T, lambda k: blocked(Tb=k)[1])
    _count_call("forward", Tb, projected)
    args = (h0, c0, Wh, p)
    args = (x_t, Wx, bias[None, :]) + args if projected else (x_t,) + args
    if mask_t is not None:
        args = (mask_t[:, :, None],) + args
    return blocked(Tb=Tb)[0](*args)


@lru_cache(maxsize=None)
def _bwd_blocked(T, b, n, cd, masked, *, Tb, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n4 = 4 * n
    nb = T // Tb
    sds = jax.ShapeDtypeStruct
    out_shapes = (
        sds((T, b, n4), cd),   # dxz
        sds((b, n), cd),       # dh0
        sds((b, n), cd),       # dc0
        sds((n, n4), cd),      # dWh
        sds((3, n), cd),       # dp
        sds((1, n4), cd),      # db
    )
    # the time blocks from the last to the first
    rev = lambda width: pl.BlockSpec(
        (Tb, b, width), lambda i: (nb - 1 - i, 0, 0),
        memory_space=pltpu.VMEM)
    in_specs = [rev(n4)]                                     # G
    if Tb > 1:      # hk: h_prev of the block's later timesteps
        in_specs.append(rev(n))
    in_specs += [
        # the hidden state carried INTO a block's first timestep t0 is
        # hk's row t0 - 1, one row of the time block before; the last
        # grid step (t0 = 0) reads h0 instead and the row it is given
        # here, row 0, costs no new DMA when Tb = 1
        pl.BlockSpec(
            (1, b, n),
            lambda i: (jnp.maximum((nb - 1 - i) * Tb - 1, 0), 0, 0),
            memory_space=pltpu.VMEM),                        # hk, one back
        rev(n),                                              # c_prev
        _fixed2(b, n),                                       # h0
        _fixed2(n, n4),                                      # Wh
        _fixed2(3, n),                                       # p
        rev(n),                                              # dhk
        _fixed2(b, n), _fixed2(b, n),                        # dhT, dcT
    ]
    if masked:
        in_specs.insert(0, rev(1))                           # mask [t,b,1]
    out_specs = (
        rev(n4),                                             # dxz
        _fixed2(b, n), _fixed2(b, n),                        # dh0, dc0
        _fixed2(n, n4),                                      # dWh
        _fixed2(3, n),                                       # dp
        _fixed2(1, n4),                                      # db
    )
    scratch = [pltpu.VMEM((b, n), jnp.float32),
               pltpu.VMEM((b, n), jnp.float32),
               pltpu.VMEM((n, n4), jnp.float32),
               pltpu.VMEM((3, n), jnp.float32),
               pltpu.VMEM((8, n4), jnp.float32)]
    return _blocked_call(partial(_bwd_kernel, masked=masked), Tb, T,
                         in_specs, out_specs, out_shapes, scratch, interpret)


def _bwd_call(res, cts):
    G, hk, cprev, h0, mask_t, Wh, p = res
    T, b, n = hk.shape
    cd = G.dtype
    dhk, dhT, dcT = (ct.astype(cd) for ct in cts)
    blocked = partial(_bwd_blocked, T, b, n, jnp.dtype(cd),
                      mask_t is not None, interpret=_interpret())
    Tb = _time_block(T, lambda k: blocked(Tb=k)[1])
    _count_call("backward", Tb)
    args = (G,) + (hk,) * (Tb > 1) + (hk, cprev, h0, Wh, p, dhk, dhT, dcT)
    if mask_t is not None:
        args = (mask_t[:, :, None],) + args
    return blocked(Tb=Tb)[0](*args)


@jax.custom_vjp
def _lstm_seq_kernels(x_t, Wx, bias, h0, c0, Wh, p, mask_t):
    return _lstm_seq_fwd(x_t, Wx, bias, h0, c0, Wh, p, mask_t)[0]


def _lstm_seq_fwd(x_t, Wx, bias, h0, c0, Wh, p, mask_t):
    n_in = Wx.shape[0]
    blocked = _fwd_blocked_for(x_t, h0, mask_t, n_in)
    if _kernel_projects(n_in, h0.shape[-1], lambda k: blocked(Tb=k)[1]):
        hk, hT, cT, G, cprev = _fwd_call(x_t, h0, c0, Wh, p, mask_t,
                                         Wx, bias)
    else:
        hk, hT, cT, G, cprev = _fwd_call(_project(x_t, Wx, bias), h0, c0,
                                         Wh, p, mask_t)
    return (hk, hT, cT), ((G, hk, cprev, h0, mask_t, Wh, p), x_t, Wx)


def _lstm_seq_bwd(res, cts):
    # the backward kernel ends at dxz, whoever made xz: the projection's
    # own two gradients are dense matmuls over the whole sequence
    kernel_res, x_t, Wx = res
    dxz, dh0, dc0, dWh, dp, db = _bwd_call(kernel_res, cts)
    dx_t = jnp.einsum("tbg,fg->tbf", dxz, Wx)
    dWx = jnp.einsum("tbf,tbg->fg", x_t, dxz)
    return dx_t, dWx, db[0], dh0, dc0, dWh, dp, None


_lstm_seq_kernels.defvjp(_lstm_seq_fwd, _lstm_seq_bwd)


def _lstm_seq_pallas(x_t, Wx, bias, h0, c0, Wh, p, mask_t):
    """The two kernels under one custom VJP give the carried hidden
    state; a masked step's output is zero, and that product is plain jnp
    so that autodiff hands the kernel ``dy * mask``. ``mask_t`` is
    [t, b] in the dtype of ``x_t``, or None."""
    hk, hT, cT = _lstm_seq_kernels(x_t, Wx, bias, h0, c0, Wh, p, mask_t)
    y = hk if mask_t is None else hk * mask_t[:, :, None]
    return y, hT, cT


@registry.register("lstm_sequence", backend="pallas")
def lstm_sequence_pallas(x_t, Wx, bias, h0, c0, Wh, p, mask_t, *,
                         gate_act="sigmoid", cell_act="tanh"):
    """Pallas-fused LSTM sequence; silently delegates to the xla backend
    for configurations the kernel does not cover (non-sigmoid/tanh
    activations, unaligned shapes, non-TPU platforms) — the same graceful
    fallback the reference's helper loading performs when cuDNN is absent
    (ConvolutionLayer.java:69-76)."""
    if not _pallas_supported(x_t, h0, gate_act, cell_act):
        return lstm_sequence_xla(x_t, Wx, bias, h0, c0, Wh, p, mask_t,
                                 gate_act=gate_act, cell_act=cell_act)
    if mask_t is not None:
        mask_t = mask_t.astype(x_t.dtype)
    return _lstm_seq_pallas(x_t, Wx, bias, h0, c0, Wh, p, mask_t)
