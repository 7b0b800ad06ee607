"""Grouped feed-forward over routed (row, expert) pairs: the matrix
products of an expert layer that holds some of a model's experts
(nn/layers/decoder.py ``RoutedExpertsLayer``).

An expert has one of two forms, told apart by whether the call brings a
gate matrix: ``down(silu(gate x) * up x)``, three matrices and nine
products a training step (the block-diffusion decoder's,
``zoo.sdar_moe``, and the latent-attention decoder's,
``zoo.glm4_moe_lite``); or ``down(max(up x, 0)^2)``, two matrices and six
products (the hybrid decoder's, ``zoo.nemotron_h``). The chunk loop runs
both (``_hidden``, ``_hidden_and_slopes`` are all that differs); the
kernels are written for the gated form alone, and the one configuration
without a gate has the published width 1,856, 14.5 lane tiles, which
they would refuse anyway (ROADMAP Queue 2 item 6 has both steps). A shared
expert that every row takes is no business of this module: it is two
or three plain products in the layer.

The pairs arrive sorted by held expert: pair ``p`` is row ``rows[p]`` of
``x`` weighted by ``coef[p]``, expert ``e`` owns the ``counts[e]`` pairs
after those of the experts before it, and whatever follows
``sum(counts)`` belongs to experts held elsewhere. The layer makes them
with one stable sort of all its (row, choice) pairs by held expert that
carries the pair's index and its weight along (``decoder._sort_pairs``:
``rows`` is the sorted index over ``k``), and ``coef``'s gradient, which
both executors return in this sorted order, goes back to the pairs' own
order by a sort on that index: no gather and no scatter-add of single
scalars on either side of this module. The arrays are as
long as the worst case (every row choosing only experts held here); the
work is not, on either of the two executors below. Nothing is dropped
whatever the counts and nothing is padded into the result. Which one
runs is decided by ``grouped_supported`` from what the call can see.

**Pallas kernels** (a TPU, or the tests' interpret mode; ``d`` and ``f``
multiples of 128; an expert's matrices small enough to stay in VMEM, or
cut into equal slices of the hidden width that are, run one after the
other inside a block: ``_width_slices``).
The sorted pairs are walked in blocks of ``experts * chunk`` pairs (the
share's expected load and half as much again: one block a layer unless
the router is biased), ``ceil(sum(counts) / block)`` of them. A block
gathers its rows ONCE, laid out so that every expert starts on a tile of
``TILE`` rows (the tail of an expert's last tile is row 0 weighted 0; no
tile holds two experts, so no product is masked), and three kernels walk
the tiles with the tile -> expert table as a scalar-prefetch operand:

- ``_ffn_kernel`` (forward): ``coef * down(silu(gate) * up)`` a tile,
  added to the result's rows by the kernel itself;
- ``_bwd_rows_kernel``: gate and up again, then ``dcoef``, ``dgate``,
  ``dup``, ``h * coef`` and the rows' gradient ``dgate Wg^T + dup Wu^T``,
  added to ``dx``'s rows likewise;
- ``_bwd_weights_kernel``: ``xs^T dgate``, ``xs^T dup``, ``(h coef)^T
  dys`` summed in float32 VMEM blocks of the tile's expert across that
  expert's tiles and written once, in the weights' dtype.

Consecutive tiles of one expert give the same weight block index, so an
expert's three matrices are fetched once a kernel; a tile past the last
live one costs a grid step, not a product. The result and ``dx`` are
summed where they lie, in HBM, a row at a time by DMA (``_add_rows``:
the XLA scatter-add this replaces took 95 ns a row of the layout, live
or not, and twice the kernels' own time). A row may be held once an
expert (what top-k routing gives): the rows of one tile are then all
different. One loop walks the blocks with every kernel inside it, once
in a program (a first block outside the loop doubled the kernels, and
loading them cost the decoder job 2 s of set-up, past its bound): a
later block of a biased router goes on from the weight gradients the
blocks before it left, which were rounded to the weights' dtype.

**The chunk loop** (the CPU, widths that are no multiple of 128, an
expert without a gate): the
sorted pairs are walked in chunks of ``chunk`` pairs of one expert by a
loop whose trip count is the number of chunks that hold a pair,
``sum(ceil(counts / chunk))``: a chunk gathers its rows, runs the three
products against its expert's weights, and scatter-adds the weighted
result, at most ``chunk - 1`` idle rows an expert. ``chunk`` is the
loop's grain; the kernels read it only for the size of a block.

Both backwards are written by hand over the same residuals (the
arguments, nothing a chunk or a tile made): a loop whose length is data
cannot be differentiated in reverse, and a scan over the worst case
would save residuals for the worst case. Both recompute gate and up from
the gathered rows (two of eight products).

The gathers, and the loop's scatter-adds, are under the named scope
``route``, the rest under the caller's (``experts`` in the decoder
block), so a device trace tells moving rows from multiplying them; the
kernels' own adding of rows is inside their calls, under ``experts``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.ops import registry

CHUNK = 128
_interpret = registry.pallas_interpret


def _count_call(backend: str) -> None:
    from deeplearning4j_tpu.observability.metrics import get_registry

    get_registry().counter(
        "dl4j_moe_grouped_matmul_calls_total",
        "Grouped expert feed-forward calls traced, by backend",
        ("backend",)).labels(backend=backend).inc()


def _chunk_table(counts, n_pairs: int, chunk: int):
    """For every chunk that may run: its expert, where its pairs start
    in the sorted arrays and how many of them are its expert's; and the
    number of chunks that hold a pair."""
    n_experts = counts.shape[0]
    per_expert = (counts + chunk - 1) // chunk
    ends = jnp.cumsum(per_expert)
    c = jnp.arange(n_pairs // chunk + n_experts, dtype=jnp.int32)
    expert = jnp.minimum(jnp.sum(c[:, None] >= ends[None, :], axis=1),
                         n_experts - 1).astype(jnp.int32)
    first_pair = jnp.cumsum(counts) - counts
    start = first_pair[expert] + (c - (ends - per_expert)[expert]) * chunk
    valid = jnp.clip(first_pair[expert] + counts[expert] - start, 0, chunk)
    return (expert, start.astype(jnp.int32), valid.astype(jnp.int32),
            ends[-1])


def _chunk_inputs(c, table, rows, coef, chunk):
    expert, start, valid, _ = table
    e, s = expert[c], start[c]
    idx = jax.lax.dynamic_slice(rows, (s,), (chunk,))
    live = jnp.arange(chunk, dtype=jnp.int32) < valid[c]
    w = jnp.where(live, jax.lax.dynamic_slice(coef, (s,), (chunk,)), 0.0)
    return e, s, jnp.where(live, idx, 0), w, live


def _pad(rows, coef, chunk):
    """A chunk's slice may run past the last pair."""
    return (jnp.pad(rows, (0, chunk)), jnp.pad(coef, (0, chunk)))


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, ((contract, ((), ()))),
                               preferred_element_type=jnp.float32)


# The two forms of an expert, told apart by what the call brings: with a
# gate matrix ``down(silu(gate x) * up x)``, without one ``down(max(up
# x, 0)^2)``. ``pre`` holds the float32 products of a chunk's rows with
# the matrices that lead into the hidden width: (gate, up) or (up,).
def _hidden(pre):
    if len(pre) == 2:
        gate, up = pre
        return jax.nn.silu(gate) * up
    return jnp.square(jnp.maximum(pre[0], 0.0))


def _hidden_and_slopes(pre):
    """The hidden row again and what a unit of its gradient is worth to
    each of ``pre``."""
    if len(pre) == 2:
        gate, up = pre
        sig = jax.nn.sigmoid(gate)
        act = gate * sig
        return act * up, (up * (sig + act * (1.0 - sig)), act)
    held = jnp.maximum(pre[0], 0.0)
    return held * held, (2.0 * held,)


def _forward(x, rows, coef, counts, wg, wu, wd, chunk):
    table = _chunk_table(counts, rows.shape[0], chunk)
    rows, coef = _pad(rows, coef, chunk)
    cd = x.dtype
    inward = [w for w in (wg, wu) if w is not None]

    def body(c, y):
        with jax.named_scope("route"):
            e, _, idx, w, _ = _chunk_inputs(c, table, rows, coef, chunk)
            xs = jnp.take(x, idx, axis=0)
        pre = [_dot(xs, w_in[e], ((1,), (0,))) for w_in in inward]
        h = _hidden(pre).astype(cd)
        out = _dot(h, wd[e], ((1,), (0,)))
        with jax.named_scope("route"):
            return y.at[idx].add(out * w[:, None])

    return jax.lax.fori_loop(0, table[3], body,
                             jnp.zeros(x.shape, jnp.float32))


def _backward(x, rows, coef, counts, wg, wu, wd, dy, chunk):
    n_pairs = rows.shape[0]
    table = _chunk_table(counts, n_pairs, chunk)
    rows, coef = _pad(rows, coef, chunk)
    cd = x.dtype
    dy = dy.astype(cd)
    inward = [w for w in (wg, wu) if w is not None]

    def body(c, carry):
        dx, dcoef, dw_in, dwd = carry
        with jax.named_scope("route"):
            e, s, idx, w, live = _chunk_inputs(c, table, rows, coef, chunk)
            xs = jnp.take(x, idx, axis=0)
            dys = jnp.take(dy, idx, axis=0)
        pre = [_dot(xs, w_in[e], ((1,), (0,))) for w_in in inward]
        h, slopes = _hidden_and_slopes(pre)
        dh_pair = _dot(dys, wd[e], ((1,), (1,)))        # of one unit of coef
        dcoef = jax.lax.dynamic_update_slice(
            dcoef, jnp.where(live, jnp.sum(dh_pair * h, axis=1), 0.0), (s,))
        dh = dh_pair * w[:, None]
        dpre = [(dh * slope).astype(cd) for slope in slopes]
        dwd = dwd.at[e].add(_dot((h * w[:, None]).astype(cd), dys,
                                 ((0,), (0,))))
        dw_in = [dw.at[e].add(_dot(xs, d, ((0,), (0,))))
                 for dw, d in zip(dw_in, dpre)]
        dxs = _dot(dpre[0], inward[0][e], ((1,), (1,)))
        for d, w_in in zip(dpre[1:], inward[1:]):
            dxs = dxs + _dot(d, w_in[e], ((1,), (1,)))
        with jax.named_scope("route"):
            return dx.at[idx].add(dxs), dcoef, dw_in, dwd

    def zeros(a):
        return jnp.zeros(a.shape, jnp.float32)

    dx, dcoef, dw_in, dwd = jax.lax.fori_loop(
        0, table[3], body,
        (zeros(x), jnp.zeros((n_pairs + chunk,), jnp.float32),
         [zeros(w) for w in inward], zeros(wd)))
    # one gradient a matrix of the call, None for a gate that is not there
    return dx, dcoef[:n_pairs], *([None] * (wg is None)), *dw_in, dwd


# ------------------------------------------------------- the Pallas kernels
TILE = 128                      # rows of one tile of the sorted pairs
LANES = 128
_VMEM_CAP = 96 * 1024 * 1024


_TABLE_CAP = 1 << 15            # rows of a block's layout, listed in SMEM


def _vmem_request(d: int, f: int, itemsize: int) -> int:
    """What the largest of the three kernels asks of VMEM: for one
    expert's weight gradients the float32 sums, a product before it is
    added and the block written back, twice (the next expert's sums run
    while it leaves); a tile of every row operand, twice; the gated
    width's float32 intermediates."""
    weights = 3 * d * f * (4 + 4 + 2 * itemsize)
    rows = 2 * TILE * (2 * d + 3 * f) * itemsize + 2 * TILE * d * 4
    return weights + rows + 12 * TILE * f * 4


def _width_slices(d: int, f: int, itemsize: int) -> int:
    """In how many equal slices of whole lane tiles the kernels take an
    expert's hidden width ``f``: the fewest whose three matrices stay in
    VMEM, 0 where none does. A gated expert is a sum over slices of its
    hidden width, ``y = sum_s (silu(x Wg[:, s]) * x Wu[:, s]) Wd[s, :]``,
    and so are all its gradients but the slices' own, so a width past
    the cap runs the same kernels a slice and adds."""
    if f % LANES:
        return 0
    tiles = f // LANES
    return next((n for n in range(1, tiles + 1) if tiles % n == 0
                 and _vmem_request(d, f // n, itemsize) <= _VMEM_CAP), 0)


def _sliced(wg, wu, wd):
    """The three matrices of every slice ``_width_slices`` asks for
    (themselves where it asks for one)."""
    _, d, f = wg.shape
    n = _width_slices(d, f, wg.dtype.itemsize)
    if n == 1:
        return [(wg, wu, wd)]
    cuts = [slice(i * f // n, (i + 1) * f // n) for i in range(n)]
    return [(wg[:, :, c], wu[:, :, c], wd[:, c, :]) for c in cuts]


def grouped_supported(x, wg, wu, wd, n_pairs: int, chunk: int) -> bool:
    """Whether the kernels cover this call: widths in whole lane tiles,
    a dtype the MXU takes, one expert's matrices (or an equal slice of
    them, ``_width_slices``) resident in VMEM, a block's rows listed in
    SMEM, and a TPU (or the tests' interpret mode) to run them. They are
    written for the gated form: an expert without a gate (``wg`` None)
    takes the chunk loop."""
    if wg is None:
        return False
    n_experts, d, f = wg.shape
    if d % LANES or f % LANES:
        return False
    if x.dtype not in (jnp.bfloat16, jnp.float32):
        return False
    if any(w.dtype != x.dtype for w in (wg, wu, wd)):
        return False
    if not _width_slices(d, f, x.dtype.itemsize):
        return False
    block = _block_pairs(n_pairs, n_experts, chunk)
    if block + n_experts * TILE > _TABLE_CAP:
        return False
    return _interpret() or jax.default_backend() == "tpu"


def _block_pairs(n_pairs: int, n_experts: int, chunk: int) -> int:
    """Pairs of one block, in whole tiles: what the held experts expect
    and half as much again (``chunk`` an expert), and no more than
    there are."""
    return TILE * -(-min(n_experts * chunk, n_pairs) // TILE)


def _block_layout(b, counts, block: int):
    """Block ``b`` of the sorted pairs laid out in tiles of one expert.

    Every expert is given the tiles its pairs inside the block fill, and
    one where it has none (the weight gradients' kernel zeroes an
    expert's block on its first tile). Returns the scalar-prefetch
    tables, one entry a tile (its expert, its live rows, and the tile a
    row operand is read from: the last live one for the tiles after it,
    so that nothing is fetched for them), and what ``_spread`` and
    ``_gather_back`` move the pairs by."""
    i32 = jnp.int32
    n_experts = counts.shape[0]
    n_tiles = block // TILE + n_experts
    first = jnp.cumsum(counts) - counts
    lo = jnp.clip(first - b * block, 0, block)
    hi = jnp.clip(first + counts - b * block, 0, block)
    held = hi - lo                                   # pairs in the block
    tiles = jnp.maximum((held + TILE - 1) // TILE, 1)
    tile_end = jnp.cumsum(tiles)
    tile_lo = tile_end - tiles
    t = jnp.arange(n_tiles, dtype=i32)
    # one-hot over the experts in place of table look-ups: [tiles, experts]
    mine = (t[:, None] >= tile_lo[None, :]) & (t[:, None] < tile_end[None, :])

    def of_tile(per_expert):
        return jnp.sum(jnp.where(mine, per_expert[None, :], 0), axis=1,
                       dtype=i32)

    valid = jnp.clip(of_tile(held) - (t - of_tile(tile_lo)) * TILE, 0, TILE)
    expert = jnp.minimum(
        jnp.sum(t[:, None] >= tile_end[None, :], axis=1, dtype=i32),
        n_experts - 1)
    tables = (expert, valid, jnp.minimum(t, tile_end[-1] - 1))
    live = (jnp.arange(TILE, dtype=i32)[None, :] < valid[:, None]).reshape(-1)
    return tables, (b * block + lo, tile_lo * TILE, live)


def _move_slices(source, out, starts, ends, size: int):
    """``out`` with ``size`` entries of ``source`` from ``starts[e]`` on
    written at ``ends[e]``, for every expert in rising order (traced
    once and unrolled: sixteen slices a call, at every call site, cost
    the decoder job 7 s of set-up when Python made them one by one)."""
    lead = source.shape[:-1]
    zero = (jnp.int32(0),) * len(lead)

    def move(e, out):
        piece = jax.lax.dynamic_slice(source, zero + (starts[e],),
                                      lead + (size,))
        return jax.lax.dynamic_update_slice(out, piece, zero + (ends[e],))

    return jax.lax.fori_loop(0, starts.shape[0], move, out, unroll=True)


def _spread(sorted_pairs, moves, block: int):
    """``sorted_pairs`` [..., P] of one block in the layout's order, 0
    where a row of the layout holds no pair. An expert's pairs are
    neighbours on both sides, so this is one slice an expert, written in
    rising order: what a slice carries past its expert's pairs lands on
    rows the next expert writes or the mask clears."""
    at, to, live = moves
    lead = sorted_pairs.shape[:-1]
    padded = jnp.pad(sorted_pairs, [(0, 0)] * len(lead) + [(0, block)])
    out = jnp.zeros(lead + (live.shape[0] + block,), sorted_pairs.dtype)
    out = _move_slices(padded, out, at, to, block)
    return jnp.where(live, out[..., :live.shape[0]], 0)


@functools.partial(jax.jit, static_argnames="block", inline=True)
def _gather_back(laid_out, moves, block: int):
    """The inverse of ``_spread`` for one float32 row of the layout:
    [block] in the sorted pairs' order (what follows the block's last
    pair is not meaningful)."""
    at, to, _ = moves
    out = _move_slices(jnp.pad(laid_out, (0, block)),
                       jnp.zeros((2 * block,), laid_out.dtype), to,
                       at - at[0], block)
    return out[:block]


@functools.partial(jax.jit, static_argnames="block", inline=True)
def _layout(b, counts, rows, coef, block: int):
    """Block ``b``'s tables, the row of ``x`` and the weight of every
    row of its layout (one pass over both), and the moves. Jitted to be
    inlined, as the kernels are: a step program calls it at sixteen
    sites with the same shapes, and Python walks its small ops once."""
    tables, moves = _block_layout(b, counts, block)
    both = _spread(jnp.stack([
        rows, jax.lax.bitcast_convert_type(coef, jnp.int32)]), moves, block)
    weight = jax.lax.bitcast_convert_type(both[1], jnp.float32)[:, None]
    return tables, both[0], weight, moves


def _add_rows(n, at, idx_ref, sums_ref, mine, theirs, sems):
    """``sums[idx[at + i]] += mine[i]`` for the tile's ``n`` live rows.

    ``sums`` [R, d / 128, 128] stays in HBM: a row of it is whole
    (8, 128) tiles, which is what lets a DMA move one row (Mosaic
    refuses a one-row slice of an [R, d] array). The rows of one tile
    are one expert's, so no two of them are the same row; the next tile
    may hold a row again, so every write has landed before this
    returns. Returns ``fetch`` and ``add``: the reads are started before
    the tile's products and waited for after them."""
    from jax.experimental.pallas import tpu as pltpu

    def read(i):
        return pltpu.make_async_copy(sums_ref.at[idx_ref[at + i]],
                                     theirs.at[i], sems.at[0])

    def write(i):
        return pltpu.make_async_copy(mine.at[i],
                                     sums_ref.at[idx_ref[at + i]], sems.at[1])

    def each(do):
        jax.lax.fori_loop(0, n, lambda i, carry: (do(i), carry)[1], 0)

    def fetch():
        each(lambda i: read(i).start())

    def add():
        each(lambda i: read(i).wait())
        mine[...] += theirs[...]
        each(lambda i: write(i).start())
        each(lambda i: write(i).wait())

    return fetch, add


def _ffn_kernel(expert_ref, valid_ref, source_ref, idx_ref, xs_ref, w_ref,
                wg_ref, wu_ref, wd_ref, y_in, y_ref, mine, theirs, sems):
    import jax.experimental.pallas as pl

    t = pl.program_id(0)

    @pl.when(valid_ref[t] > 0)
    def _():
        fetch, add = _add_rows(valid_ref[t], t * TILE, idx_ref, y_ref, mine,
                               theirs, sems)
        fetch()
        xs, w = xs_ref[...], w_ref[...]
        gate = _dot(xs, wg_ref[...], ((1,), (0,)))
        up = _dot(xs, wu_ref[...], ((1,), (0,)))
        h = (jax.nn.silu(gate) * up).astype(xs.dtype)
        for c in range(mine.shape[1]):
            lanes = slice(c * LANES, (c + 1) * LANES)
            mine[:, c, :] = _dot(h, wd_ref[:, lanes], ((1,), (0,))) * w
        add()


def _bwd_rows_kernel(expert_ref, valid_ref, source_ref, idx_ref, xs_ref,
                     dys_ref, w_ref, wg_ref, wu_ref, wd_ref, dx_in, dx_ref,
                     dcoef_ref, dgate_ref, dup_ref, hw_ref, mine, theirs,
                     sems):
    import jax.experimental.pallas as pl

    t = pl.program_id(0)

    @pl.when(valid_ref[t] > 0)
    def _():
        fetch, add = _add_rows(valid_ref[t], t * TILE, idx_ref, dx_ref, mine,
                               theirs, sems)
        fetch()
        xs, w = xs_ref[...], w_ref[...]
        cd = xs.dtype
        gate = _dot(xs, wg_ref[...], ((1,), (0,)))
        up = _dot(xs, wu_ref[...], ((1,), (0,)))
        sig = jax.nn.sigmoid(gate)
        act = gate * sig
        h = act * up
        dh_pair = _dot(dys_ref[...], wd_ref[...], ((1,), (1,)))
        dcoef_ref[...] = jnp.sum(dh_pair * h, axis=1, keepdims=True)
        dh = dh_pair * w
        dgate = (dh * up * (sig + act * (1.0 - sig))).astype(cd)
        dup = (dh * act).astype(cd)
        dgate_ref[...] = dgate
        dup_ref[...] = dup
        hw_ref[...] = (h * w).astype(cd)
        for c in range(mine.shape[1]):
            rows = slice(c * LANES, (c + 1) * LANES)
            mine[:, c, :] = (_dot(dgate, wg_ref[rows, :], ((1,), (1,)))
                             + _dot(dup, wu_ref[rows, :], ((1,), (1,))))
        add()

    @pl.when(valid_ref[t] == 0)
    def _():
        # ``dcoef`` is read back tile by tile; the weight gradients'
        # kernel skips this one
        dcoef_ref[...] = jnp.zeros_like(dcoef_ref)


def _bwd_weights_kernel(expert_ref, valid_ref, source_ref, block_ref,
                        xs_ref, dys_ref, dgate_ref, dup_ref, hw_ref, *refs):
    import jax.experimental.pallas as pl

    before, sums, accs = refs[:3], refs[3:6], refs[6:]
    t = pl.program_id(0)
    last = pl.num_programs(0) - 1
    mine = expert_ref[t]
    starts = (t == 0) | (expert_ref[jnp.maximum(t - 1, 0)] != mine)

    @pl.when(starts & (block_ref[0] == 0))
    def _():
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)

    @pl.when(starts & (block_ref[0] > 0))
    def _():
        # a later block of a biased router: on from what the blocks
        # before it left
        for acc, was in zip(accs, before):
            acc[...] = was[...].astype(jnp.float32)

    @pl.when(valid_ref[t] > 0)
    def _():
        xs = xs_ref[...]
        accs[0][...] += _dot(xs, dgate_ref[...], ((0,), (0,)))
        accs[1][...] += _dot(xs, dup_ref[...], ((0,), (0,)))
        accs[2][...] += _dot(hw_ref[...], dys_ref[...], ((0,), (0,)))

    # rounded once, as the sums leave VMEM: the gradient in the weights'
    # own dtype is what the caller's cast would make of them
    @pl.when((t == last) | (expert_ref[jnp.minimum(t + 1, last)] != mine))
    def _():
        for total, acc in zip(sums, accs):
            total[...] = acc[...].astype(total.dtype)


@functools.lru_cache(maxsize=None)
def _kernel_calls(n_tiles: int, n_rows: int, n_experts: int, d: int, f: int,
                  dtype, interpret: bool):
    """The three ``pallas_call``s for ``n_tiles`` tiles over ``n_rows``
    rows of ``x``, jitted to be inlined: the layers of one program trace
    and lower each body once between them (ops/lstm.py ``_blocked_call``
    has the measurement). The first two take the layout's rows of ``x``
    as a fourth table and the running sum [n_rows, d / 128, 128] as
    their last operand, which they return; the third takes the block's
    number as its fourth table and the three gradients so far as its
    last operands, which it returns (and does not read in block 0)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    cd = jnp.dtype(dtype)
    laid_out = n_tiles * TILE

    def read(width):            # a row operand: nothing new past the end
        return pl.BlockSpec((TILE, width), lambda t, e, v, s, *_: (s[t], 0))

    def write(width):
        return pl.BlockSpec((TILE, width), lambda t, *_: (t, 0))

    def weights(rows, cols):
        return pl.BlockSpec((None, rows, cols),
                            lambda t, e, *_: (e[t], 0, 0))

    def rows_of(width, dtype):
        return jax.ShapeDtypeStruct((laid_out, width), dtype)

    def call(kernel, tables, in_specs, out_specs, out_shape, scratch,
             **more):
        return jax.jit(pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=tables, grid=(n_tiles,),
                in_specs=in_specs, out_specs=out_specs,
                scratch_shapes=scratch),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_VMEM_CAP),
            interpret=interpret, **more), inline=True)

    expert_weights = [weights(d, f), weights(d, f), weights(f, d)]
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    sums = jax.ShapeDtypeStruct((n_rows, d // LANES, LANES), jnp.float32)
    adding = [pltpu.VMEM((TILE, d // LANES, LANES), jnp.float32),
              pltpu.VMEM((TILE, d // LANES, LANES), jnp.float32),
              pltpu.SemaphoreType.DMA((2,))]
    forward = call(
        _ffn_kernel, 4, [read(d), read(1)] + expert_weights + [in_hbm],
        in_hbm, sums, adding, input_output_aliases={9: 0})
    backward_rows = call(
        _bwd_rows_kernel, 4,
        [read(d), read(d), read(1)] + expert_weights + [in_hbm],
        [in_hbm, write(1), write(f), write(f), write(f)],
        [sums, rows_of(1, jnp.float32), rows_of(f, cd), rows_of(f, cd),
         rows_of(f, cd)], adding, input_output_aliases={10: 0})
    shapes = ((d, f), (d, f), (f, d))
    so_far = [pl.BlockSpec(      # block 0 reads none: one block, once
        (None,) + shape, lambda t, e, v, s, b: (
            e[t] * jnp.minimum(b[0], 1), 0, 0)) for shape in shapes]
    backward_weights = call(
        _bwd_weights_kernel, 4,
        [read(d), read(d), read(f), read(f), read(f)] + so_far,
        expert_weights,
        [jax.ShapeDtypeStruct((n_experts,) + shape, cd) for shape in shapes],
        [pltpu.VMEM(shape, jnp.float32) for shape in shapes],
        input_output_aliases={9: 0, 10: 1, 11: 2})
    return forward, backward_rows, backward_weights


def _calls_for(x, wg, block: int):
    """The kernels for one slice of the hidden width (``wg``: a slice's
    gate matrices)."""
    return _kernel_calls(block // TILE + wg.shape[0], x.shape[0], *wg.shape,
                         x.dtype.name, _interpret())


def _sums(x):
    """Zeros in the shape the kernels add rows in."""
    return jnp.zeros((x.shape[0], x.shape[1] // LANES, LANES), jnp.float32)


def _n_blocks(counts, block: int):
    return (jnp.sum(counts) + block - 1) // block


def _kernel_forward(x, rows, coef, counts, wg, wu, wd, chunk):
    block = _block_pairs(rows.shape[0], counts.shape[0], chunk)
    slices = _sliced(wg, wu, wd)
    ffn, _, _ = _calls_for(x, slices[0][0], block)

    def add_block(b, y):
        with jax.named_scope("route"):
            tables, idx, w, _ = _layout(b, counts, rows, coef, block=block)
            xs = jnp.take(x, idx, axis=0)
        for ws in slices:
            y = ffn(*tables, idx, xs, w, *ws, y)
        return y

    y = jax.lax.fori_loop(0, _n_blocks(counts, block), add_block, _sums(x))
    return y.reshape(x.shape)


def _kernel_backward(x, rows, coef, counts, wg, wu, wd, dy, chunk):
    n_pairs = rows.shape[0]
    block = _block_pairs(n_pairs, counts.shape[0], chunk)
    slices = _sliced(wg, wu, wd)
    _, backward_rows, backward_weights = _calls_for(x, slices[0][0], block)
    dy = dy.astype(x.dtype)

    def add_block(b, carry):
        dx, dcoef, dws = carry
        with jax.named_scope("route"):
            tables, idx, w, moves = _layout(b, counts, rows, coef,
                                            block=block)
            xs = jnp.take(x, idx, axis=0)
            dys = jnp.take(dy, idx, axis=0)
        dcoef_b = []
        for i, ws in enumerate(slices):
            dx, dcoef_s, dgate, dup, hw = backward_rows(
                *tables, idx, xs, dys, w, *ws, dx)
            dws[i] = list(backward_weights(*tables, b[None], xs, dys, dgate,
                                           dup, hw, *dws[i]))
            dcoef_b.append(dcoef_s[:, 0])
        with jax.named_scope("route"):
            # a pair's weight is worth the sum of what its slices say
            dcoef = jax.lax.dynamic_update_slice(
                dcoef, _gather_back(functools.reduce(jnp.add, dcoef_b),
                                    moves, block=block),
                (b * block,))
        return dx, dcoef, dws

    # the gradients' zeros are the loop's to start from and are not read
    dx, dcoef, dws = jax.lax.fori_loop(
        0, _n_blocks(counts, block), add_block,
        (_sums(x), jnp.zeros((n_pairs + block,), jnp.float32),
         [[jnp.zeros_like(w) for w in ws] for ws in slices]))
    dx = dx.reshape(x.shape)
    live = jnp.arange(n_pairs, dtype=jnp.int32) < jnp.sum(counts)
    if len(dws) > 1:
        dws = [[jnp.concatenate(of_slices, axis=axis) for of_slices, axis
                in zip(zip(*dws), (2, 2, 1))]]
    return (dx, jnp.where(live, dcoef[:n_pairs], 0.0), *dws[0])


# ------------------------------------------------------------ the one entry
def _executor(kernels: bool):
    return ((_kernel_forward, _kernel_backward) if kernels
            else (_forward, _backward))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _expert_ffn(x, rows, coef, counts, wg, wu, wd, chunk, kernels):
    return _executor(kernels)[0](x, rows, coef, counts, wg, wu, wd, chunk)


def _expert_ffn_fwd(x, rows, coef, counts, wg, wu, wd, chunk, kernels):
    return (_executor(kernels)[0](x, rows, coef, counts, wg, wu, wd, chunk),
            (x, rows, coef, counts, wg, wu, wd))


def _expert_ffn_bwd(chunk, kernels, residuals, dy):
    x, rows, coef, counts, *ws = residuals
    dx, dcoef, *dws = _executor(kernels)[1](*residuals, dy, chunk)

    def no_gradient(a):
        return np.zeros(a.shape, jax.dtypes.float0)

    return (dx.astype(x.dtype), no_gradient(rows), dcoef.astype(coef.dtype),
            no_gradient(counts),
            *(None if w is None else dw.astype(w.dtype)
              for dw, w in zip(dws, ws)))


_expert_ffn.defvjp(_expert_ffn_fwd, _expert_ffn_bwd)


def expert_ffn(x, rows, coef, counts, wg, wu, wd, *, chunk: int = CHUNK):
    """``y[r] = sum over held pairs (r, e) of coef * down_e(h_e(x[r]))``
    as float32 ``[R, d]``, where ``h_e(x) = silu(gate_e x) * up_e x``, or
    ``max(up_e x, 0)^2`` where ``wg`` is None.

    ``x`` [R, d] and the weights ``wg``, ``wu`` [E, d, f], ``wd``
    [E, f, d] in the compute dtype; ``rows`` int32 [P] and ``coef``
    float32 [P] the pairs sorted by held expert; ``counts`` int32 [E]
    the pairs of each held expert. Differentiable in ``x``, ``coef`` and
    the weights. ``chunk``: the pairs an expert expects and some to
    spare, the grain of the chunk loop and, times the experts, of the
    kernels' blocks (the module docstring has both)."""
    kernels = grouped_supported(x, wg, wu, wd, rows.shape[0], chunk)
    _count_call("pallas" if kernels else "xla_chunks")
    return _expert_ffn(x, rows.astype(jnp.int32), coef,
                       counts.astype(jnp.int32), wg, wu, wd, chunk, kernels)
