"""The selective state-space recurrence of a Mamba-2 mixer
(nn/layers/decoder.py ``Mamba2MixerLayer``), with the depthwise causal convolution before it and
the gated group norm after it.

The recurrence, for head ``h`` of ``H`` with a state ``S`` in ``R^{P x
N}`` (``P`` the head's width, ``N`` the state size) and ``g(h) = h div
(H / G)`` the group whose ``B`` and ``C`` the head reads:

    S_t = exp(dt_t,h A_h) S_{t-1} + dt_t,h x_t,h (x) B_t,g(h)     S_{-1} = 0
    y_t,h = S_t C_t,g(h) + D_h x_t,h

``ssm_scan`` computes it in chunks of ``chunk`` positions (the
state-space duality of Dao and Gu 2024, section 6): with ``a_t = dt_t A``
and ``cs`` its running sum inside a chunk,

    inside a chunk    Y = (L o (C B^T)) (dt X)     L_ts = exp(cs_t - cs_s), s <= t
    a chunk's state   S' = sum_s exp(cs_last - cs_s) dt_s x_s (x) B_s
    between chunks    S_c = exp(cs_last) S_{c-1} + S'_c   (a scan over chunks)
    from the past     Y += exp(cs_t) C_t S_{c-1}

Four products a chunk and no step a position. The decays, their
sums and the carried state are float32; the products take operands in
the compute dtype and accumulate in float32. ``L`` is made from the
masked difference, never from a quotient of two exponentials: a chunk
whose decay underflows (a large ``dt``) gives exact zeros, not 0 / 0.

Two executors of that one form, chosen by what a call shows (platform
and shapes; no knob), each one differentiable op with a hand-written
backward:

- ``xla`` (``_chunked``): batched einsums and a scan over the chunk
  states. The CPU's path, the tests' reference, and what any shape the
  kernels refuse falls to. Its backward saves the op's arguments and
  nothing a chunk made: it computes the chunked form again and
  differentiates that, so of the ``[H, chunks, chunk, chunk]`` decay
  matrices (134 MB a layer at 64 heads and 4,096 positions) only the
  layer in hand is alive; but they are written to and read from HBM
  several times, forward and backward.
- ``pallas`` (``_tiled``; a TPU, or the tests' interpreter): a forward
  and a backward kernel over a grid of (batch, group, chunk), the chunk
  axis last and sequential, the carried state of a group's heads ``[N,
  r P]`` float32 in VMEM. A step makes ``C B^T`` once for the group and
  each head's decay tile in VMEM, where it stays. ``ssm_scan_supported``
  says which calls they take: chunks of 128, heads of 64 (two to a lane
  tile, so an even number a group) or 128, a state of whole lane tiles,
  at most 1,024 lanes a group, ``x``, ``B``, ``C`` in one of bfloat16
  and float32. The forward saves the state before every chunk (``[b, G,
  chunks, N, r P]`` float32, 67 MB a layer at the cell's shape) beside
  the op's arguments; the backward sweeps the chunks in reverse with
  ``dS`` carried as the state was, makes a chunk's decays and ``C B^T``
  again in VMEM and reads the saved state: no forward pass over HBM.
  XLA makes the running sums before the call and finishes ``d dt``,
  ``dA`` and ``dD`` after it, on ``[b, L, H]``-sized arrays.

``dl4j_ssm_scan_calls_total{direction, backend}`` counts the traces.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops import registry

CHUNK = 128


def _count_scan(direction: str, backend: str) -> None:
    from deeplearning4j_tpu.observability.metrics import get_registry

    get_registry().counter(
        "dl4j_ssm_scan_calls_total",
        "State-space scan calls traced, by direction and backend",
        ("direction", "backend")).labels(
            direction=direction, backend=backend).inc()


def causal_conv1d(x, w, b):
    """Depthwise causal convolution and silu: ``silu(b_c + sum_k w_c,k
    x_{t-K+1+k, c})`` with zeros before position 0. ``x`` [b, L, C],
    ``w`` [C, K], ``b`` [C]; the sum in float32, the result in ``x``'s
    dtype."""
    k = w.shape[1]
    length = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0))).astype(jnp.float32)
    wf = w.astype(jnp.float32)
    total = b.astype(jnp.float32)
    for i in range(k):
        total = total + padded[:, i:i + length] * wf[:, i]
    return jax.nn.silu(total).astype(x.dtype)


def gated_group_norm(y, z, g, groups: int, eps: float):
    """``GroupRMSNorm(y * silu(z))``: the gate first, then an RMS norm
    over each of ``groups`` equal slices of the last axis, one weight
    ``g`` over the whole of it. Float32, returns float32."""
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    grouped = gated.reshape(gated.shape[:-1] + (groups, -1))
    ms = jnp.mean(grouped * grouped, axis=-1, keepdims=True)
    normed = grouped * jax.lax.rsqrt(ms + float(eps))
    return normed.reshape(gated.shape) * g.astype(jnp.float32)


def _chunked(x, dt, a, b, c, d, chunk: int):
    """The chunked form of the module docstring. ``x`` [b, L, H, P],
    ``dt`` [b, L, H] float32 (after its softplus), ``a`` [H] float32
    (negative), ``b`` and ``c`` [b, L, G, N], ``d`` [H]; returns
    float32 [b, L, H, P]."""
    bs, length, heads, p = x.shape
    groups, n = b.shape[2:]
    r = heads // groups
    nc = length // chunk
    cd = x.dtype
    f32 = jnp.float32

    def dot(spec, u, v):
        return jnp.einsum(spec, u, v, preferred_element_type=f32)

    # decays with the positions of a chunk last: [b, c, g, r, t]
    dt = dt.astype(f32).reshape(bs, nc, chunk, groups, r)
    cs = jnp.cumsum(jnp.moveaxis(dt, 2, -1)
                    * a.astype(f32).reshape(groups, r, 1), axis=-1)
    xdt = (x.astype(f32).reshape(bs, nc, chunk, groups, r, p)
           * dt[..., None])
    bc = b.reshape(bs, nc, chunk, groups, n)
    cc = c.reshape(bs, nc, chunk, groups, n)

    # inside a chunk
    t = jnp.arange(chunk)
    diff = cs[..., :, None] - cs[..., None, :]             # [b,c,g,r,t,s]
    decay = jnp.exp(jnp.where(t[:, None] >= t[None, :], diff, -jnp.inf))
    cb = dot("bctgn,bcsgn->bcgts", cc, bc)
    y = dot("bcgrts,bcsgrp->bctgrp",
            (decay * cb[:, :, :, None]).astype(cd), xdt.astype(cd))

    # every chunk's own state, and the states carried between them
    to_end = jnp.exp(cs[..., -1:] - cs)                    # [b,c,g,r,s]
    own = dot("bcsgn,bcsgrp->bcgrpn", bc,
              (xdt * jnp.moveaxis(to_end, -1, 2)[..., None]).astype(cd))
    whole = jnp.exp(cs[..., -1])                           # [b,c,g,r]

    def carry_on(state, chunk_c):
        own_c, whole_c = chunk_c
        return whole_c[..., None, None] * state + own_c, state

    _, before = jax.lax.scan(
        carry_on, jnp.zeros(own.shape[:1] + own.shape[2:], f32),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(whole, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                    # [b,c,g,r,p,n]
    y = y + (dot("bctgn,bcgrpn->bctgrp", cc, before.astype(cd))
             * jnp.moveaxis(jnp.exp(cs), -1, 2)[..., None])
    y = y.reshape(bs, length, heads, p)
    return y + x.astype(f32) * d.astype(f32)[:, None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, dt, a, b, c, d, chunk):
    return _chunked(x, dt, a, b, c, d, chunk)


def _scan_fwd(x, dt, a, b, c, d, chunk):
    _count_scan("forward", "xla")
    return _chunked(x, dt, a, b, c, d, chunk), (x, dt, a, b, c, d)


def _scan_bwd(chunk, residuals, dy):
    _count_scan("backward", "xla")
    _, vjp = jax.vjp(
        lambda *args: _chunked(*args, chunk), *residuals)
    return vjp(dy)


_scan.defvjp(_scan_fwd, _scan_bwd)


@registry.register("ssm_scan", backend="xla")
def ssm_scan_xla(x, dt, a, b, c, d, *, chunk: int = CHUNK):
    return _scan(x, dt, a, b, c, d, chunk)


# ------------------------------------------------------------------ pallas
# One grid step is one chunk of one group: the heads of a group share B
# and C, so C B^T is made once, the products with the carried state are
# one matmul over the group's r P lanes, and dB, dC are summed over the
# heads in VMEM. A lane tile holds 128 / P heads (a pair at P = 64): its
# per-position factors are [chunk, 1] columns spread by a select, never a
# slice of 64 lanes.

_LANES = 128
_GROUP_LANES = 1024         # r P: the carried state is [N, r P] float32

_interpret = registry.pallas_interpret


def ssm_scan_supported(x, dt, a, b, c, d, chunk: int = CHUNK) -> bool:
    """Whether the kernels cover this call: chunks of 128 (a chunk's
    positions are the lanes of its decay tiles), heads of 64 (in pairs)
    or 128, a state of whole lane tiles, at most 1,024 lanes a group, one
    dtype the MXU takes, and a TPU (or the tests' interpret mode) to run
    them."""
    heads, p = x.shape[2:]
    groups, n = b.shape[2:]
    if chunk != _LANES or p not in (64, 128) or n % _LANES:
        return False
    lanes = heads // groups * p
    if lanes % _LANES or lanes > _GROUP_LANES:
        return False
    if x.dtype not in (jnp.bfloat16, jnp.float32):
        return False
    if b.dtype != x.dtype or c.dtype != x.dtype:
        return False
    return _interpret() or jax.default_backend() == "tpu"


def _dot(u, v, contract):
    return jax.lax.dot_general(
        u, v, dimension_numbers=((contract, ((), ()))),
        preferred_element_type=jnp.float32)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _spread(cols, first: int, p: int):
    """The [chunk, 1] columns ``first ...`` of ``cols`` [chunk, 128] over
    the 128 lanes of the tile their heads fill."""
    if p == _LANES:
        return jnp.broadcast_to(cols[:, first:first + 1], cols.shape)
    lane = jax.lax.broadcasted_iota(jnp.int32, cols.shape, 1)
    return jnp.where(lane < p, cols[:, first:first + 1],
                     cols[:, first + 1:first + 2])


def _whole(exps, grow, at: int, p: int):
    """``exp(cs_last)`` over a tile's lanes, [1, 128]: the growth
    ``grow`` [chunk, 128] at the chunk's last position."""
    if p < _LANES:
        return _spread(exps[-1:], at, p)
    # one head a tile: Mosaic spreads no [1, 1] value both ways at once
    row = jax.lax.broadcasted_iota(jnp.int32, grow.shape, 0)
    return jnp.sum(jnp.where(row == grow.shape[0] - 1, grow, 0.0), axis=0,
                   keepdims=True)


def _own_lanes(value, j: int, p: int, other=0.0):
    """``value`` [chunk, 128] on the lanes of the tile's head ``j``,
    ``other`` elsewhere."""
    if p == _LANES:
        return value
    lane = jax.lax.broadcasted_iota(jnp.int32, value.shape, 1)
    return jnp.where((lane < p) == (j == 0), value, other)


def _factors(rows_ref):
    """A grid step's per-position factors, from the ``[2 r8, chunk]``
    rows ``_factor_rows`` made: as rows ``[3 r8, chunk]`` (``dt``, ``cs``,
    ``cs_last - cs``: ``cs`` of head ``h`` is row ``r8 + h``) and, by ONE
    transpose of a whole [128, chunk] tile, the same as columns ``[chunk,
    128]``: ``dt`` of head ``h`` in lane ``h``, ``cs`` in ``r8 + h``,
    ``cs_last - cs`` in ``2 r8 + h``."""
    rows = rows_ref[0, 0]
    cs = rows[rows.shape[0] // 2:]
    rows = jnp.concatenate([rows, cs[:, -1:] - cs], axis=0)
    blank = jnp.zeros((_LANES - rows.shape[0], rows.shape[1]), jnp.float32)
    return rows, jnp.concatenate([rows, blank], axis=0).T


def _decay(cols, rows, at: int, lower):
    """L of the head whose ``cs`` is column and row ``at``: exp of the
    masked difference."""
    diff = cols[:, at:at + 1] - rows[at:at + 1, :]
    return jnp.exp(jnp.where(lower, diff, -jnp.inf))


def _lower(q: int):
    t = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    return t >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)


def _forward_kernel(x_ref, b_ref, c_ref, rows_ref, d_ref, y_ref, *rest,
                    p: int):
    import jax.experimental.pallas as pl

    state = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[:] = jnp.zeros_like(state)

    f32 = jnp.float32
    x, bm, cm = x_ref[0], b_ref[0], c_ref[0]
    cd = x.dtype
    rows, cols = _factors(rows_ref)
    r8 = rows.shape[0] // 3
    exps = jnp.exp(cols)            # of cs: the past's growth; to the end
    before = state[:]
    if len(rest) == 2:
        rest[0][0, 0, 0] = before
    lower = _lower(x.shape[0])
    cb = _dot(cm, bm, _NT)
    past = _dot(cm, before.astype(cd), _NN)
    per_tile = _LANES // p
    for tile in range(x.shape[1] // _LANES):
        lanes = slice(tile * _LANES, (tile + 1) * _LANES)
        first = tile * per_tile
        xt = x[:, lanes].astype(f32)
        xdt = xt * _spread(cols, first, p)
        xdtc = xdt.astype(cd)
        y = None
        for j in range(per_tile):
            m = _decay(cols, rows, r8 + first + j, lower) * cb
            yh = _dot(m.astype(cd), xdtc, _NN)
            y = yh if y is None else _own_lanes(y, 0, p, yh)
        grow = _spread(exps, r8 + first, p)
        y_ref[0, :, lanes] = (y + past[:, lanes] * grow
                              + xt * d_ref[0, :, lanes])
        own = _dot(bm, (xdt * _spread(exps, 2 * r8 + first, p)).astype(cd),
                   _TN)
        # a chunk's whole decay is its growth at the last position
        whole = _whole(exps, grow, r8 + first, p)
        state[:, lanes] = whole * before[:, lanes] + own


def _backward_kernel(x_ref, b_ref, c_ref, rows_ref, d_ref, dy_ref, before_ref,
                     dx_ref, db_ref, dc_ref, drows_ref, dyx_ref, carried,
                     *, p: int):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    @pl.when(pl.program_id(2) == 0)
    def _():
        carried[:] = jnp.zeros_like(carried)

    f32 = jnp.float32
    x, bm, cm = x_ref[0], b_ref[0], c_ref[0]
    cd = x.dtype
    q = x.shape[0]
    rows, cols = _factors(rows_ref)
    r8 = rows.shape[0] // 3
    exps = jnp.exp(cols)
    before = before_ref[0, 0, 0]
    ds = carried[:]
    lower = _lower(q)
    cb = _dot(cm, bm, _NT)
    past = _dot(cm, before.astype(cd), _NN)
    ahead = _dot(bm, ds.astype(cd), _NN)
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, _LANES), 1)
    last_row = jax.lax.broadcasted_iota(jnp.int32, (q, _LANES), 0) == q - 1
    sublane = jax.lax.broadcasted_iota(jnp.int32, (r8, q), 0)
    # what cs is given, by the row and by the column of L, and dt by x:
    # columns collect in the lanes the factors came in
    dcols = jnp.zeros((q, _LANES), f32)
    dcs_rows = jnp.zeros((r8, q), f32)
    dcb = jnp.zeros((q, q), f32)
    dc_past = jnp.zeros(cm.shape, f32)
    db_ahead = jnp.zeros(bm.shape, f32)
    per_tile = _LANES // p
    for tile in range(x.shape[1] // _LANES):
        lanes = slice(tile * _LANES, (tile + 1) * _LANES)
        first = tile * per_tile
        xt = x[:, lanes].astype(f32)
        dy = dy_ref[0, :, lanes]
        dyc = dy.astype(cd)
        dt_s = _spread(cols, first, p)
        xdt = xt * dt_s
        xdtc = xdt.astype(cd)
        to_end = _spread(exps, 2 * r8 + first, p)
        ahead_t = ahead[:, lanes] * to_end
        grow = _spread(exps, r8 + first, p)
        grow_dy = dy * grow
        whole = _whole(exps, grow, r8 + first, p)
        # what the exponents of the growth and of to_end are given, by
        # lane; a chunk's last position is also given what exp(cs_last)
        # is, whole <S_before, dS>, and the sum of what to_end's exponents
        # were (the SAME products: their difference is what a decay that
        # underflowed leaves)
        to_ends = xdt * ahead_t
        at_last = (whole * jnp.sum(before[:, lanes] * ds[:, lanes], axis=0,
                                   keepdims=True)
                   + jnp.sum(to_ends, axis=0, keepdims=True))
        by_lane = (grow_dy * past[:, lanes] - to_ends
                   + jnp.where(last_row, at_last, 0.0))
        dxdt = None
        for j in range(per_tile):
            h = first + j
            decay = _decay(cols, rows, r8 + h, lower)
            m = decay * cb
            dm = _dot(_own_lanes(dyc, j, p), xdtc, _NT)
            dcb = dcb + decay * dm
            given = dm * m
            dcols = jnp.where(
                lane == h,
                jnp.sum(given + _own_lanes(by_lane, j, p), axis=1,
                        keepdims=True), dcols)
            dcs_rows = jnp.where(
                sublane == h, -jnp.sum(given, axis=0, keepdims=True),
                dcs_rows)
            back = _dot(m.astype(cd), dyc, _TN)
            dxdt = back if dxdt is None else _own_lanes(dxdt, 0, p, back)
        dxdt = dxdt + ahead_t
        for j in range(per_tile):
            dcols = jnp.where(
                lane == r8 + first + j,
                jnp.sum(_own_lanes(dxdt * xt, j, p), axis=1, keepdims=True),
                dcols)
        dx_ref[0, :, lanes] = (dxdt * dt_s + dy * d_ref[0, :, lanes]
                               ).astype(dx_ref.dtype)
        dyx_ref[0, 0, 0, :, lanes] = jnp.sum(dy * xt, axis=0, keepdims=True)
        grow_dyc = grow_dy.astype(cd)
        dc_past = dc_past + _dot(grow_dyc, before[:, lanes].astype(cd), _NT)
        db_ahead = db_ahead + _dot((xdt * to_end).astype(cd),
                                   ds[:, lanes].astype(cd), _NT)
        carried[:, lanes] = whole * ds[:, lanes] + _dot(cm, grow_dyc, _TN)
    dcbc = dcb.astype(cd)
    dc_ref[0] = (_dot(dcbc, bm, _NN) + dc_past).astype(dc_ref.dtype)
    db_ref[0] = (_dot(dcbc, cm, _TN) + db_ahead).astype(db_ref.dtype)
    given_rows = dcols.T
    # back through the running sum: what dt A is given at s is the sum of
    # what cs is given from s to the chunk's end
    da = given_rows[0:r8] + dcs_rows
    position = jax.lax.broadcasted_iota(jnp.int32, (r8, q), 1)
    step = 1
    while step < q:
        da = da + jnp.where(position + step < q,
                            pltpu.roll(da, q - step, axis=1), 0.0)
        step *= 2
    drows_ref[0, 0, 0:r8] = da
    drows_ref[0, 0, r8:2 * r8] = given_rows[r8:2 * r8]


def _factor_rows(dt, a, groups: int, chunk: int):
    """``dt`` and the running sum ``cs`` of ``dt A`` inside a chunk, the
    heads of a group as rows and the positions last: ``[b, G, 2 r8, L]``
    float32 (``r8``: the heads of a group rounded up to whole sublane
    tiles, the rest zeros). 2 MB at the cell's shape, made by XLA where
    a head is a lane."""
    bs, length, heads = dt.shape
    r = heads // groups
    dt = dt.astype(jnp.float32).reshape(bs, -1, chunk, heads)
    rows = jnp.stack([dt, jnp.cumsum(dt * a.astype(jnp.float32), axis=2)])
    rows = jnp.transpose(rows.reshape(2, bs, length, groups, r),
                         (1, 3, 0, 4, 2))
    rows = jnp.pad(rows, ((0, 0), (0, 0), (0, 0), (0, -r % 8), (0, 0)))
    return rows.reshape(bs, groups, -1, length)


def _operands(x, dt, a, b, c, d, chunk: int):
    """What both kernels read, each with the kind of block a grid step
    takes of it: ``x``, ``B``, ``C`` as [b, L, H P] and [b, L, G N] (free
    reshapes), the factor rows, and ``D`` over a head's lanes ``[G, 1, r
    P]``."""
    bs, length, _, p = x.shape
    groups = b.shape[2]
    over_lanes = jnp.repeat(d.astype(jnp.float32), p).reshape(groups, 1, -1)
    flat = [v.reshape(bs, length, -1) for v in (x, b, c)]
    return list(zip(
        flat + [_factor_rows(dt, a, groups, chunk), over_lanes],
        ("rows", "rows", "rows", "lanes", "group")))


def _pallas_scan(kernel, operands, outputs, state, chunk: int,
                 backward: bool, interpret: bool):
    """``pallas_call`` of ``kernel`` over (batch, group, chunk), the chunk
    axis last and in order (``backward``: from the last chunk down), the
    carried ``state`` [N, r P] float32 in VMEM. ``operands`` and
    ``outputs`` are ``(array or shape, kind)``: a kind says which block of
    its array a grid step takes."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bs, length = operands[0][0].shape[:2]
    groups = operands[3][0].shape[1]
    nc = length // chunk

    def at(ci):
        return nc - 1 - ci if backward else ci

    def spec(shape, kind):
        block, index = {
            # [b, L, G w]: a chunk's positions, a group's lanes
            "rows": ((1, chunk, shape[-1] // groups),
                     lambda bi, gi, ci: (bi, at(ci), gi)),
            # [b, G, k, L]: a chunk's positions as lanes
            "lanes": ((1, 1, shape[2], chunk),
                      lambda bi, gi, ci: (bi, gi, 0, at(ci))),
            "chunk": ((1, 1, 1) + tuple(shape[3:]),
                      lambda bi, gi, ci: (bi, gi, at(ci), 0, 0)),
            "group": ((1,) + tuple(shape[1:]),
                      lambda bi, gi, ci: (gi, 0, 0)),
        }[kind]
        return pl.BlockSpec(block, index, memory_space=pltpu.VMEM)

    return pl.pallas_call(
        kernel,
        grid=(bs, groups, nc),
        in_specs=[spec(v.shape, kind) for v, kind in operands],
        out_specs=[spec(v.shape, kind) for v, kind in outputs],
        out_shape=[v for v, _ in outputs],
        scratch_shapes=[pltpu.VMEM(state, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*(v for v, _ in operands))


# Both are jitted to be inlined: the mixers and the programs of one process
# that run the same shapes then trace a kernel's body once between them,
# where the unrolled bodies traced at every call site cost the hybrid
# decoder's job 4.5 s of set-up (PERF.md section 6, PR 34).
@functools.partial(jax.jit, static_argnames=("chunk", "save", "interpret"),
                   inline=True)
def _tiled_forward(x, dt, a, b, c, d, *, chunk: int, save: bool,
                   interpret: bool):
    """``y`` [b, L, H, P] float32 and, if ``save``, the state before every
    chunk ``[b, G, chunks, N, r P]`` float32 (67 MB a layer at the cell's
    shape: what the backward reads in place of a forward of its own)."""
    bs, length, heads, p = x.shape
    groups, n = b.shape[2:]
    lanes = heads // groups * p
    f32 = jnp.float32
    outputs = [(jax.ShapeDtypeStruct((bs, length, heads * p), f32), "rows")]
    if save:
        outputs.append((jax.ShapeDtypeStruct(
            (bs, groups, length // chunk, n, lanes), f32), "chunk"))
    y, *before = _pallas_scan(
        functools.partial(_forward_kernel, p=p),
        _operands(x, dt, a, b, c, d, chunk), outputs, (n, lanes), chunk,
        backward=False, interpret=interpret)
    return y.reshape(x.shape), before


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"),
                   inline=True)
def _tiled_backward(x, dt, a, b, c, d, before, dy, *, chunk: int,
                    interpret: bool):
    bs, length, heads, p = x.shape
    groups, n = b.shape[2:]
    r, nc = heads // groups, length // chunk
    lanes = r * p
    f32 = jnp.float32
    operands = _operands(x, dt, a, b, c, d, chunk) + [
        (dy.astype(f32).reshape(bs, length, -1), "rows"), (before, "chunk")]
    r8 = operands[3][0].shape[2] // 2
    shape = jax.ShapeDtypeStruct
    outputs = [(shape((bs, length, heads * p), x.dtype), "rows"),
               (shape((bs, length, groups * n), b.dtype), "rows"),
               (shape((bs, length, groups * n), c.dtype), "rows"),
               (shape((bs, groups, 2 * r8, length), f32), "lanes"),
               (shape((bs, groups, nc, 1, lanes), f32), "chunk")]
    dx, db, dc, drows, dyx = _pallas_scan(
        functools.partial(_backward_kernel, p=p), operands, outputs,
        (n, lanes), chunk, backward=True, interpret=interpret)

    # back where a head is a lane: what dt A is given, and dt through x
    da, dt_by_x = jnp.transpose(
        drows.reshape(bs, groups, 2, r8, length)[:, :, :, :r],
        (2, 0, 4, 1, 3)).reshape(2, bs, length, heads)
    dtf, af = dt.astype(f32), a.astype(f32)
    return (dx.reshape(x.shape), (da * af + dt_by_x).astype(dt.dtype),
            jnp.sum(da * dtf, axis=(0, 1)).astype(a.dtype),
            db.reshape(b.shape), dc.reshape(c.shape),
            dyx.reshape(bs, groups, nc, r, p).sum((0, 2, 4)).reshape(
                heads).astype(d.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _tiled(x, dt, a, b, c, d, chunk):
    return _tiled_forward(x, dt, a, b, c, d, chunk=chunk, save=False,
                          interpret=_interpret())[0]


def _tiled_fwd(x, dt, a, b, c, d, chunk):
    _count_scan("forward", "pallas")
    y, (before,) = _tiled_forward(x, dt, a, b, c, d, chunk=chunk, save=True,
                                  interpret=_interpret())
    return y, (x, dt, a, b, c, d, before)


def _tiled_bwd(chunk, residuals, dy):
    _count_scan("backward", "pallas")
    return _tiled_backward(*residuals, dy, chunk=chunk,
                           interpret=_interpret())


_tiled.defvjp(_tiled_fwd, _tiled_bwd)


@registry.register("ssm_scan", backend="pallas")
def ssm_scan_pallas(x, dt, a, b, c, d, *, chunk: int = CHUNK):
    """The two kernels; delegates to the xla backend for calls
    ``ssm_scan_supported`` refuses."""
    if not ssm_scan_supported(x, dt, a, b, c, d, chunk):
        return ssm_scan_xla(x, dt, a, b, c, d, chunk=chunk)
    return _tiled(x, dt, a, b, c, d, chunk)


def ssm_scan(x, dt, a, b, c, d, *, chunk: int = CHUNK):
    """``y`` float32 [b, L, H, P] of the recurrence in the module
    docstring, differentiable in all six arguments. ``L`` must be a
    whole number of chunks."""
    if x.shape[1] % chunk:
        raise ValueError(
            f"ssm_scan computes the recurrence in chunks of {chunk} "
            f"positions; a sequence of {x.shape[1]} is no whole number of "
            "them (pad the batch, or give the layer another chunk)")
    if x.shape[2] % b.shape[2]:
        raise ValueError(
            f"ssm_scan: {x.shape[2]} heads cannot share {b.shape[2]} "
            "groups evenly")
    return registry.get("ssm_scan")(x, dt, a, b, c, d, chunk=chunk)
