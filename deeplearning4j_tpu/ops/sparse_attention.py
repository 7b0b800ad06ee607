"""Attention whose keys are chosen per query by a learned indexer
(DeepSeek Sparse Attention, DeepSeek-V3.2-Exp), for training: a causal
grouped-query attention in which row ``t`` attends only to the ``topk``
keys ``s <= t`` that the indexer scores highest.

The indexer, for ``nI`` heads of ``dI`` (``qI`` [b, L, nI, dI] and
``kI`` [b, L, dI], rotated; ``w`` [b, L, nI] float32, scaled):

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])      s <= t, float32

Three ops, each with an XLA form (the CPU, the tests, shapes the
kernels do not cover) and Pallas kernels (a TPU, or the tests' interpret
mode), chosen by the ``*_supported`` guards as ``ops/attention.py``'s:

- ``dsa_select(qI, kI, w, topk)``: the selection ``S_t``, the ``topk``
  largest ``I[t, s]`` over ``s <= t`` (every visible key where there are
  no more than ``topk``), ties going to the lower ``s``, as packed bits,
  and the log-sum-exp of ``I[t]`` over ``S_t``. The kernel holds a query
  tile's scores against every visible key in VMEM as the order-keeping
  int32 image of the float and finds each row's ``topk``-th largest by a
  bisection over its 32 bits, then, only in a tile where some row has
  more keys at that score than places left, the index bound that keeps
  the lowest of them.
- ``sparse_attention(q, k, v, sel)``: softmax attention over ``S_t``,
  forward and backward on the tiled kernel bodies of ``ops/attention.py``
  (``fwd_body``, ``bwd_body``) with the selection's bits laid over every
  score entry. They walk the causal live tiles; a tile pair in which no
  row selected a key is skipped, through a scalar-prefetch table made
  from ``sel`` once a call (``_tables``), which also gives the count of
  tile pairs walked and skipped.
- ``dsa_indexer_loss(q, k, lse, qI, kI, w, sel, lseI)``: the indexer's
  loss, ``mean_t sum_{s in S_t} p[t, s] (log p[t, s] - log
  softmax_{S_t}(I[t])_s)`` with ``p`` the attention's probabilities
  averaged over its heads (``q``, ``k`` and the attention's ``lse`` come
  in without a gradient). Its gradient reaches ``qI``, ``kI`` and ``w``
  alone: ``dI = softmax_{S_t}(I[t]) - p[t]`` on the selected entries,
  divided by the rows. The kernel makes the loss and that gradient in
  one pass of its own, in the forward: the loss it reports needs ``p``,
  which needs the attention's final log-sum-exp, so it cannot ride in
  the attention's forward, and having ``dI`` there leaves the backward
  nothing to make again.

No float [L, L] array exists on the kernels' path. The selection is
``int32 [b, L/32, L]``: bit ``j`` of ``sel[b, i, t]`` says whether row
``t`` keeps key ``j * L/32 + i``. A key tile of the kernels is then a
shift of the words of its query tile, with no lane moved.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops import attention as att
from deeplearning4j_tpu.ops import registry

_INT_MIN = -2 ** 31
_SELECT_TILE = 128      # query rows a dsa_select grid step
_KEY_CHUNK = 512        # keys a dsa_select pass reads at a time


def _count_attention(direction: str, backend: str) -> None:
    att._count_calls("dl4j_sparse_attention_calls_total",
                     "Attention over an indexer's selection", direction,
                     backend)


def _count_select(backend: str) -> None:
    from deeplearning4j_tpu.observability.metrics import get_registry

    get_registry().counter(
        "dl4j_dsa_select_calls_total",
        "Indexer selections (scores and top-k) traced, by backend",
        ("backend",)).labels(backend=backend).inc()


# ------------------------------------------------------------- the bits
def pack(keep):
    """bool [b, L, L] (row, key) -> the selection's words int32
    [b, L/32, L]."""
    b, rows, keys = keep.shape
    bits = keep.reshape(b, rows, 32, keys // 32).astype(jnp.int32)
    words = jnp.sum(jnp.left_shift(
        bits, jnp.arange(32, dtype=jnp.int32)[None, None, :, None]), axis=2,
        dtype=jnp.int32)
    return jnp.swapaxes(words, 1, 2)


def unpack(sel):
    """The inverse of ``pack``: bool [b, L, L]."""
    b, n, rows = sel.shape
    words = jnp.swapaxes(sel, 1, 2)[:, :, None, :]
    bits = jnp.right_shift(
        words, jnp.arange(32, dtype=jnp.int32)[None, None, :, None]) & 1
    return bits.reshape(b, rows, 32 * n) != 0


def selected_pairs(sel):
    """The (row, key) pairs ``sel`` keeps, int32."""
    return jnp.sum(jax.lax.population_count(sel), dtype=jnp.int32)


def _causal(length):
    rows = jnp.arange(length, dtype=jnp.int32)
    return rows[:, None] >= rows[None, :]


# ------------------------------------------------------------ XLA forms
def index_scores(qI, kI, w):
    """I [b, L, L] float32 (row, key), every entry, a zero as +0."""
    logits = jnp.einsum("btjd,bsd->btjs", qI, kI,
                        preferred_element_type=jnp.float32)
    scores = jnp.einsum("btj,btjs->bts", w.astype(jnp.float32),
                        jnp.maximum(logits, 0.0))
    return jnp.where(scores == 0, 0.0, scores)


@registry.register("dsa_select", backend="xla")
def dsa_select_xla(qI, kI, w, *, topk: int):
    """``lax.top_k`` over the causal rows (it puts the lower index first
    among equals)."""
    _count_select("xla")
    b, length = qI.shape[:2]
    scores = jnp.where(_causal(length), index_scores(qI, kI, w), -jnp.inf)
    _, chosen = jax.lax.top_k(scores, min(topk, length))
    keep = jnp.zeros(scores.shape, bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(length)[None, :, None],
        chosen].set(True) & _causal(length)
    lse = jax.nn.logsumexp(jnp.where(keep, scores, -jnp.inf), axis=-1)
    return pack(keep), jax.lax.stop_gradient(lse)


def _grouped(q, k):
    """q [b, L, Hq, dh] -> [b, L, Hkv, G, dh] with head h reading key
    head h // G."""
    b, t, hq, dh = q.shape
    return q.reshape(b, t, k.shape[2], hq // k.shape[2], dh)


def _masked_scores(q, k, keep):
    """[b, Hkv, G, L, L] float32, -inf where ``keep`` [b, L, L] is
    False."""
    s = jnp.einsum("bikgd,bjkd->bkgij", _grouped(q, k), k,
                   preferred_element_type=jnp.float32) / math.sqrt(q.shape[3])
    return jnp.where(keep[:, None, None], s, -jnp.inf)


def _one_tile(sel):
    """(walked, skipped) of the XLA form, int32: one tile a sequence,
    skipped where it keeps no key."""
    walked = jnp.sum(jnp.any(sel != 0, axis=(1, 2)), dtype=jnp.int32)
    return jnp.stack([walked, sel.shape[0] - walked])


@registry.register("sparse_attention", backend="xla")
def sparse_attention_xla(q, k, v, sel):
    """q [b, L, Hq, dh], k and v [b, L, Hkv, dh], ``sel`` the words ->
    (o [b, L, Hq, dh], lse float32 [b, Hq, L], the tiles) over the dense
    scores; autodiff gives the backward."""
    _count_attention("forward", "xla")
    b, t, hq, dh = q.shape
    s = _masked_scores(q, k, unpack(sel))
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    o = jnp.einsum("bkgij,bjkd->bikgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return (o.reshape(b, t, hq, dh).astype(q.dtype),
            jax.lax.stop_gradient(lse.reshape(b, hq, t)), _one_tile(sel))


def _kl_rows(p_bar, scores, keep):
    """Per row: sum over the kept keys of p (log p - log softmax(I))."""
    log_soft = scores - jax.nn.logsumexp(
        jnp.where(keep, scores, -jnp.inf), axis=-1, keepdims=True)
    on = keep & (p_bar > 0)
    return jnp.sum(jnp.where(on, p_bar * (
        jnp.log(jnp.where(on, p_bar, 1.0)) - log_soft), 0.0), axis=-1)


@registry.register("dsa_indexer_loss", backend="xla")
def dsa_indexer_loss_xla(q, k, lse, qI, kI, w, sel, lseI):
    """The loss over the dense scores; autodiff gives its gradient (the
    log-sum-exp of ``I`` is made again here, so that it has one)."""
    keep = unpack(sel)
    b, t, hq, _ = q.shape
    s = _masked_scores(q, k, keep)
    p = jnp.exp(s - lse.reshape(s.shape[:-1])[..., None])
    p_bar = jax.lax.stop_gradient(jnp.mean(p.reshape(b, hq, t, t), axis=1))
    return jnp.mean(_kl_rows(p_bar, index_scores(qI, kI, w), keep))


# ------------------------------------------------------------ the tables
def _tables(sel, bq, bk):
    """The causal live tile pairs (query-major, ``ops/attention.py``'s
    ``_causal_live_tiles``) that hold a selected entry, per batch row:
    ``qi``, ``ki``, ``first``, ``last`` (int32 [b * P], P the causal
    pairs; a row's steps past its live pairs repeat its last pair, with
    ``first`` and ``last`` 0) and the live count ``n`` [b]."""
    b, n_words, length = sel.shape
    pairs = att._causal_live_tiles(length, bq, bk)
    n_pairs = len(pairs)
    m = bk // n_words                       # bits a key tile spans
    band = -1 if m == 32 else (1 << m) - 1
    # bit j: some row of the query tile keeps a key of [j L/32, (j+1) L/32)
    ored = jax.lax.reduce(sel.reshape(b, n_words, length // bq, bq),
                          jnp.int32(0), jax.lax.bitwise_or, (1, 3))
    pq = jnp.asarray(pairs[:, 0])
    pk = jnp.asarray(pairs[:, 1])
    live = (jnp.right_shift(ored[:, pq], pk * m) & band) != 0   # [b, P]
    slot = jnp.where(live, jnp.cumsum(live, axis=1, dtype=jnp.int32) - 1,
                     n_pairs)
    n = jnp.sum(live, axis=1, dtype=jnp.int32)
    rows = jnp.arange(b)[:, None]
    on = jnp.arange(n_pairs, dtype=jnp.int32)[None] < n[:, None]

    def compact(values):
        out = jnp.zeros((b, n_pairs + 1), jnp.int32).at[rows, slot].set(
            jnp.broadcast_to(values, (b, n_pairs)))[:, :n_pairs]
        tail = jnp.take_along_axis(out, (n - 1)[:, None], axis=1)
        return jnp.where(on, out, tail)

    qi, ki = compact(pq), compact(pk)
    edge = jnp.full((b, 1), -1, jnp.int32)
    first = on & (qi != jnp.concatenate([edge, qi[:, :-1]], axis=1))
    last = on & ((qi != jnp.concatenate([qi[:, 1:], edge], axis=1))
                 | ~jnp.concatenate([on[:, 1:], edge == 0], axis=1))
    flat = [a.reshape(-1).astype(jnp.int32) for a in (qi, ki, first, last)]
    return (*flat, n), n_pairs


def _tiled_length(length: int) -> bool:
    """A selection word row of whole sublane tiles that a key tile holds
    whole: ``L`` a multiple of 256, at most 32 key tiles' worth (16,384
    rows at key tiles of 512)."""
    return (length % 256 == 0
            and att._bd_key_tile(length) % (length // 32) == 0)


def _key_bits(words, ki, bk):
    """The bits of key tile ``ki`` from the query tile's words [L/32, bq]:
    bit ``ki * m + p`` of every word, p < m, stacked down the sublanes
    (int32 [bk, bq], 1 where the row keeps the key)."""
    n_words = words.shape[0]
    m = bk // n_words
    parts = [jnp.right_shift(words, ki * m + p) & 1 for p in range(m)]
    return parts[0] if m == 1 else jnp.concatenate(parts, axis=0)


def _visible(sel_ref, ki, bk):
    """``visible`` of ``ops/attention.py``'s kernel bodies under the
    selection: key tile ``ki``'s bits of the query tile's words made
    into a [bk, bq] mask once a step, and ``mask(lo, hi)`` laying it on
    score lanes ``lo:hi``, one copy a head (``att._on_heads``)."""
    def visible():
        return functools.partial(att._on_heads,
                                 _key_bits(sel_ref[0], ki, bk) != 0)
    return visible


# ---------------------------------------------------- sparse attention
def sparse_attention_supported(q, k, v, sel) -> bool:
    """Whether the kernels cover this call: ``causal_attention_supported``'s
    shapes, a length ``_tiled_length`` takes, and dK, dV resident (the
    one-kernel backward alone)."""
    length, dh = q.shape[1], q.shape[3]
    if not _tiled_length(length):
        return False
    return (att.causal_attention_supported(q, k, v)
            and att._bd_fused_fits(length, dh, k.dtype))


def _specs(g, bq, bk, dh, n_words, steps, hkv):
    """Block specs by what a block follows, the tables indexed by the
    batch row's offset: the query tile, its words, the key tile."""
    import jax.experimental.pallas as pl

    def at(bh, s):
        return (bh // hkv) * steps + s

    def rows(bh, s, qi, ki, *_):
        return bh, 0, qi[at(bh, s)], 0

    def stats(bh, s, qi, ki, *_):
        return bh, 0, qi[at(bh, s)]

    def keys(bh, s, qi, ki, *_):
        return bh, ki[at(bh, s)], 0

    def words(bh, s, qi, ki, *_):
        return bh // hkv, 0, qi[at(bh, s)]

    return (pl.BlockSpec((1, g, bq, dh), rows),
            pl.BlockSpec((1, g, bq), stats),
            pl.BlockSpec((1, bk, dh), keys),
            pl.BlockSpec((1, n_words, bq), words))


def _sel_fwd_kernel(qi_ref, ki_ref, first_ref, last_ref, n_ref,
                    q_ref, k_ref, v_ref, sel_ref, o_ref, lse_ref,
                    m_scr, l_scr, acc_scr, *, scale, steps, hkv):
    import jax.experimental.pallas as pl

    bh, s_id = pl.program_id(0), pl.program_id(1)
    t = (bh // hkv) * steps + s_id
    bk = k_ref.shape[1]

    @pl.when(s_id < n_ref[bh // hkv])
    def _():
        att.fwd_body(lambda: first_ref[t] == 1, lambda: last_ref[t] == 1,
                     _visible(sel_ref, ki_ref[t], bk),
                     q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                     acc_scr, scale=scale)


def _sel_bwd_kernel(qi_ref, ki_ref, first_ref, last_ref, n_ref,
                    q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, sel_ref,
                    dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr,
                    *, scale, steps, hkv):
    import jax.experimental.pallas as pl

    bh, s_id = pl.program_id(0), pl.program_id(1)
    t = (bh // hkv) * steps + s_id
    bk = k_ref.shape[1]
    ki = ki_ref[t]
    att.bwd_body(s_id, lambda: first_ref[t] == 1, lambda: last_ref[t] == 1,
                 ki,
                 _visible(sel_ref, ki, bk),
                 q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                 dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr,
                 scale=scale, live=s_id < n_ref[bh // hkv])


def _sel_geometry(qg, sel):
    bh, g, length, dh = qg.shape
    b = sel.shape[0]
    return (bh, g, length, dh, b, bh // b, att._bd_query_tile(g, length),
            att._bd_key_tile(length))


def _sel_forward(qg, kg, vg, sel, tables, steps):
    from jax.experimental.pallas import tpu as pltpu

    bh, g, length, dh, b, hkv, bq, bk = _sel_geometry(qg, sel)
    att._count_forward_chunks(g, bq)
    rows, stats, keys, words = _specs(g, bq, bk, dh, sel.shape[1], steps,
                                      hkv)
    return att._bd_call(
        _sel_fwd_kernel, tables, steps, bh,
        in_specs=[rows, keys, keys, words], out_specs=[rows, stats],
        out_shape=[jax.ShapeDtypeStruct(qg.shape, qg.dtype),
                   jax.ShapeDtypeStruct((bh, g, length), jnp.float32)],
        scratch=[pltpu.VMEM((1, g * bq), jnp.float32),
                 pltpu.VMEM((1, g * bq), jnp.float32),
                 pltpu.VMEM((dh, g * bq), jnp.float32)],
        scale=1.0 / math.sqrt(dh), steps=steps, hkv=hkv)(
            *tables, qg, kg, vg, sel)


def _sel_backward(steps, qg, kg, vg, og, lse, sel, tables, dog):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, g, length, dh, b, hkv, bq, bk = _sel_geometry(qg, sel)
    rows, stats, keys, words = _specs(g, bq, bk, dh, sel.shape[1], steps,
                                      hkv)
    di = jnp.sum(og.astype(jnp.float32) * dog.astype(jnp.float32), axis=-1)
    head = pl.BlockSpec((1, length, dh), lambda i, *_: (i, 0, 0))
    return tuple(att._bd_call(
        _sel_bwd_kernel, tables, steps, bh,
        in_specs=[rows, keys, keys, rows, stats, stats, words],
        out_specs=[rows, head, head],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype)
                   for a in (qg, kg, vg)],
        scratch=[pltpu.VMEM((dh, g * bq), jnp.float32),
                 pltpu.VMEM((length // bk, bk, dh), jnp.float32),
                 pltpu.VMEM((length // bk, bk, dh), jnp.float32)],
        scale=1.0 / math.sqrt(dh), steps=steps, hkv=hkv)(
            *tables, qg, kg, vg, dog, lse, di, sel))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _sel_attention(qg, kg, vg, sel, tables, steps):
    return _sel_forward(qg, kg, vg, sel, tables, steps)


def _sel_attention_fwd(qg, kg, vg, sel, tables, steps):
    _count_attention("forward", "pallas")
    og, lse = _sel_forward(qg, kg, vg, sel, tables, steps)
    return (og, lse), (qg, kg, vg, og, lse, sel, tables)


def _sel_attention_bwd(steps, residuals, cotangents):
    # the log-sum-exp leaves without a gradient (``sparse_attention``)
    _count_attention("backward", "pallas")
    return (*_sel_backward(steps, *residuals, cotangents[0]), None, None)


_sel_attention.defvjp(_sel_attention_fwd, _sel_attention_bwd)


@registry.register("sparse_attention", backend="pallas")
def sparse_attention_pallas(q, k, v, sel):
    """The tiled forward and one-kernel backward under the selection;
    delegates to the xla backend for calls
    ``sparse_attention_supported`` refuses."""
    if not sparse_attention_supported(q, k, v, sel):
        return sparse_attention_xla(q, k, v, sel)
    b, t, hq, _ = q.shape
    tables, steps = _tables(sel, att._bd_query_tile(hq // k.shape[2], t),
                            att._bd_key_tile(t))
    og, lse = _sel_attention(*att._bd_split(q, k, v), sel, tables, steps)
    walked = jnp.sum(tables[-1])
    return (att._bd_join(og, b),
            jax.lax.stop_gradient(lse.reshape(b, hq, t)),
            jnp.stack([walked, b * steps - walked]))


def sparse_attention(q, k, v, sel):
    """Grouped-query attention of row ``t`` over the keys ``sel`` keeps
    for it: (o [b, L, Hq, dh], the log-sum-exp of every head's scores
    over them, float32 [b, Hq, L], without a gradient, and the causal
    tile pairs the kernels walked and skipped, int32 [2]; where the XLA
    form ran, one tile a sequence)."""
    return registry.get("sparse_attention")(q, k, v, sel)


# ---------------------------------------------------------- dsa_select
def _to_key(f):
    """float32 -> int32 in the same order (a zero as +0)."""
    i = jax.lax.bitcast_convert_type(jnp.where(f == 0, 0.0, f), jnp.int32)
    return i ^ (jnp.right_shift(i, 31) & 0x7FFFFFFF)


def _from_key(key):
    return jax.lax.bitcast_convert_type(
        key ^ (jnp.right_shift(key, 31) & 0x7FFFFFFF), jnp.float32)


def _tile_scores(k, qt, wl, n_heads, rows):
    """I of a key chunk ``k`` [c, dI] against the query tile ``qt``
    [n_heads * tq, dI] with its weights ``wl`` [1, n_heads * tq]:
    (I [c, tq] float32, the logits [c, n_heads * tq])."""
    logits = jax.lax.dot_general(
        k, qt, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    weighted = wl * jnp.maximum(logits, 0.0)
    scores = weighted[:, :rows]
    for j in range(1, n_heads):
        scores = scores + weighted[:, j * rows:(j + 1) * rows]
    return scores, logits


def _select_kernel(qI_ref, kI_ref, w_ref, sel_ref, lse_ref, keys_scr,
                   *, topk):
    """One query tile of ``tq`` rows (along the lanes) against every key
    (down the sublanes): its scores as int32 keys in VMEM, every row's
    ``topk``-th largest by bisection over the 32 bits, the lowest-index
    rule where a row has more keys at that score than places left, then
    the words and the log-sum-exp in one pass over 32 slices."""
    import jax.experimental.pallas as pl

    i = pl.program_id(1)
    n_heads, tq, d_index = qI_ref.shape[1:]
    length = kI_ref.shape[1]
    n_words = length // 32
    ck = min(_KEY_CHUNK, length)
    qt = qI_ref[0].reshape(n_heads * tq, d_index)
    wl = att._rows_to_lanes(w_ref[0], n_heads)
    rows = i * tq + jax.lax.broadcasted_iota(jnp.int32, (1, tq), 1)
    n_vis = (i * tq + tq - 1) // ck + 1      # key chunks up to the diagonal

    def score_chunk(c, carry):
        start = pl.multiple_of(c * ck, ck)
        scores, _ = _tile_scores(kI_ref[0, pl.ds(start, ck), :], qt, wl,
                                 n_heads, tq)
        cols = start + jax.lax.broadcasted_iota(jnp.int32, (ck, 1), 0)
        keys_scr[pl.ds(start, ck), :] = jnp.where(cols <= rows,
                                                  _to_key(scores), _INT_MIN)
        return carry

    def blank_chunk(c, carry):
        keys_scr[pl.ds(pl.multiple_of(c * ck, ck), ck), :] = jnp.full(
            (ck, tq), _INT_MIN, jnp.int32)
        return carry

    jax.lax.fori_loop(0, n_vis, score_chunk, 0)
    jax.lax.fori_loop(n_vis, length // ck, blank_chunk, 0)

    def count(test):
        def one(c, acc):
            start = pl.multiple_of(c * ck, ck)
            hit = test(keys_scr[pl.ds(start, ck), :], start)
            return acc + jnp.sum(hit.astype(jnp.int32), axis=0, keepdims=True,
                                 dtype=jnp.int32)
        return jax.lax.fori_loop(0, n_vis, one, jnp.zeros((1, tq), jnp.int32))

    choose = rows >= topk                    # rows with more keys than places
    tau = jnp.full((1, tq), _INT_MIN, jnp.int32)
    bound = jnp.full((1, tq), length, jnp.int32)

    def bisect():
        def bit(b, tau):
            cand = tau + jnp.left_shift(jnp.int32(1),
                                        jnp.int32(31) - b.astype(jnp.int32))
            return jnp.where(count(lambda kk, _: kk >= cand) >= topk,
                             cand, tau)

        tau = jax.lax.fori_loop(0, 32, bit, jnp.full((1, tq), _INT_MIN,
                                                     jnp.int32))
        need = topk - count(lambda kk, _: kk > tau)
        surplus = jnp.where(choose, count(lambda kk, _: kk == tau) - need, 0)

        def lowest(_, lo_hi):
            lo, hi = lo_hi
            mid = jnp.right_shift(lo + hi, 1)
            got = count(lambda kk, start: (kk == tau) & (
                start + jax.lax.broadcasted_iota(jnp.int32, kk.shape, 0)
                < mid))
            return (jnp.where(got >= need, lo, mid),
                    jnp.where(got >= need, mid, hi))

        def tied():
            return jax.lax.fori_loop(
                0, max(length - 1, 1).bit_length(), lowest,
                (jnp.zeros((1, tq), jnp.int32), bound))[1]

        bound_ = jax.lax.cond(jnp.max(surplus) > 0, tied, lambda: bound)
        return tau, bound_

    tau, bound = jax.lax.cond(i * tq + tq - 1 >= topk, bisect,
                              lambda: (tau, bound))

    def most(c, acc):
        return jnp.maximum(acc, jnp.max(
            keys_scr[pl.ds(pl.multiple_of(c * ck, ck), ck), :], axis=0,
            keepdims=True))

    top = _from_key(jax.lax.fori_loop(0, n_vis, most,
                                      jnp.full((1, tq), _INT_MIN, jnp.int32)))
    words = jnp.zeros((n_words, tq), jnp.int32)
    total = jnp.zeros((1, tq), jnp.float32)
    for j in range(32):
        kk = keys_scr[j * n_words:(j + 1) * n_words, :]
        cols = j * n_words + jax.lax.broadcasted_iota(jnp.int32,
                                                      (n_words, 1), 0)
        keep = ((choose & ((kk > tau) | ((kk == tau) & (cols < bound))))
                | (~choose & (kk != _INT_MIN)))
        words = words | jnp.left_shift(keep.astype(jnp.int32), j)
        total = total + jnp.sum(jnp.where(keep, jnp.exp(_from_key(kk) - top),
                                          0.0), axis=0, keepdims=True)
    sel_ref[0] = words
    lse_ref[0] = top + jnp.log(total)


def dsa_select_supported(qI, kI, w, topk: int) -> bool:
    """Whether the kernel covers this call: rows in whole tiles of 128
    and whole chunks of keys, words of whole sublane tiles (``L`` a
    multiple of 256), and the int32 keys of a tile within a quarter of
    the VMEM limit (32,768 rows)."""
    length = qI.shape[1]
    if length % 256 or length % min(_KEY_CHUNK, length):
        return False
    if 4 * length * _SELECT_TILE > att._BD_VMEM_LIMIT // 4:
        return False
    if qI.dtype not in (jnp.bfloat16, jnp.float32) or kI.dtype != qI.dtype:
        return False
    return att._interpret() or jax.default_backend() == "tpu"


@registry.register("dsa_select", backend="pallas")
def dsa_select_pallas(qI, kI, w, *, topk: int):
    """The kernel; delegates to the xla backend for calls
    ``dsa_select_supported`` refuses."""
    if not dsa_select_supported(qI, kI, w, topk):
        return dsa_select_xla(qI, kI, w, topk=topk)
    _count_select("pallas")
    return _select_tiled(qI, kI, w, topk)


def _select_tiled(qI, kI, w, topk):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, length, n_heads, d_index = qI.shape
    tq = _SELECT_TILE
    sel, lse = pl.pallas_call(
        functools.partial(_select_kernel, topk=topk),
        grid=(b, length // tq),
        in_specs=[
            pl.BlockSpec((1, n_heads, tq, d_index),
                         lambda b_, i: (b_, 0, i, 0)),
            pl.BlockSpec((1, length, d_index), lambda b_, i: (b_, 0, 0)),
            pl.BlockSpec((1, n_heads, tq), lambda b_, i: (b_, 0, i))],
        out_specs=[pl.BlockSpec((1, length // 32, tq),
                                lambda b_, i: (b_, 0, i)),
                   pl.BlockSpec((1, 1, tq), lambda b_, i: (b_, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((b, length // 32, length), jnp.int32),
                   jax.ShapeDtypeStruct((b, 1, length), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((length, tq), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=att._BD_VMEM_LIMIT),
        interpret=att._interpret(),
    )(jnp.swapaxes(qI, 1, 2), kI,
      jnp.swapaxes(w.astype(jnp.float32), 1, 2))
    return sel, lse[:, 0]


def dsa_select(qI, kI, w, *, topk: int):
    """(the selection's words int32 [b, L/32, L], the log-sum-exp of
    ``I`` over it, float32 [b, L]); neither has a gradient."""
    return registry.get("dsa_select")(
        *(jax.lax.stop_gradient(a) for a in (qI, kI, w)), topk=topk)


# --------------------------------------------------- the indexer's loss
def _kl_kernel(qi_ref, ki_ref, first_ref, last_ref, n_ref,
               q_ref, k_ref, lse_ref, qI_ref, kI_ref, w_ref, lseI_ref,
               sel_ref, kl_ref, dqI_ref, dkI_ref, dw_ref,
               kl_scr, dqI_scr, dw_scr, dkI_scr, *, scale, steps):
    """One live pair a step, query-major: ``p`` of the pair summed over
    the attention's heads (its key/value heads one after another), ``I``
    and its logits, then the pair's part of the rows' loss and of the
    gradient of ``I``'s three inputs: ``dqI`` [dI, nI * bq] and ``dw``
    gathered over the query tile's run, ``dkI`` resident for the whole
    sequence as the attention backward's dK."""
    import jax.experimental.pallas as pl

    b, s_id = pl.program_id(0), pl.program_id(1)
    t = b * steps + s_id
    hkv, g, bq, dh = q_ref.shape[1:]
    bk = k_ref.shape[2]
    n_heads = qI_ref.shape[1]
    ki = ki_ref[t]

    @pl.when(s_id == 0)
    def _():
        dkI_scr[:] = jnp.zeros_like(dkI_scr)

    @pl.when(first_ref[t] == 1)
    def _():
        kl_scr[:] = jnp.zeros_like(kl_scr)
        dqI_scr[:] = jnp.zeros_like(dqI_scr)
        dw_scr[:] = jnp.zeros_like(dw_scr)

    @pl.when(s_id < n_ref[b])
    def _():
        keep = _key_bits(sel_ref[0], ki, bk) != 0            # [bk, bq]
        keep_heads = att._on_heads(keep, 0, g * bq)          # [bk, g*bq]
        p_sum = jnp.zeros((bk, bq), jnp.float32)
        for h in range(hkv):
            st = jax.lax.dot_general(
                k_ref[0, h], q_ref[0, h].reshape(g * bq, dh),
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            # a key the indexer passed over may score far above the
            # attention's log-sum-exp: masked before the exponential
            pt = jnp.exp(jnp.where(keep_heads, st, att._MASK_VALUE)
                         - att._rows_to_lanes(lse_ref[0, h], g))
            for gi in range(g):
                p_sum = p_sum + pt[:, gi * bq:(gi + 1) * bq]
        p_bar = p_sum * (1.0 / (hkv * g))
        qt = qI_ref[0].reshape(n_heads * bq, -1)
        wl = att._rows_to_lanes(w_ref[0], n_heads)
        scores, logits = _tile_scores(kI_ref[0], qt, wl, n_heads, bq)
        scores = jnp.where(scores == 0, 0.0, scores)
        log_soft = scores - lseI_ref[0]
        on = keep & (p_bar > 0)
        kl_scr[:] += jnp.sum(jnp.where(on, p_bar * (
            jnp.log(jnp.where(on, p_bar, 1.0)) - log_soft), 0.0),
            axis=0, keepdims=True)
        d_scores = jnp.where(keep, jnp.exp(log_soft) - p_bar, 0.0)
        d_wide = jnp.concatenate([d_scores] * n_heads, axis=1)
        relu = jnp.maximum(logits, 0.0)
        dw_scr[:] += jnp.sum(d_wide * relu, axis=0, keepdims=True)
        d_logits = jnp.where(logits > 0, d_wide * wl, 0.0).astype(qt.dtype)
        dqI_scr[:] += jax.lax.dot_general(
            kI_ref[0], d_logits, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [dI, nI*bq]
        dkI_scr[ki] += jax.lax.dot_general(
            d_logits, qt, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bk, dI]

    @pl.when(last_ref[t] == 1)
    def _():
        kl_ref[0] = kl_scr[:]
        dq = dqI_scr[:]
        dw = dw_scr[:]
        for j in range(n_heads):
            dqI_ref[0, j] = dq[:, j * bq:(j + 1) * bq].T
            dw_ref[0, j:j + 1, :] = dw[:, j * bq:(j + 1) * bq]

    @pl.when(s_id == pl.num_programs(1) - 1)
    def _():
        dkI_ref[0] = dkI_scr[:].reshape(dkI_ref.shape[1:])


def dsa_kl_supported(q, k, qI, sel) -> bool:
    """``sparse_attention_supported``'s shapes, and the indexer's keys
    of a sequence resident as float32 (at most the attention's dK)."""
    return (sparse_attention_supported(q, k, k, sel)
            and qI.shape[3] <= q.shape[3] and qI.dtype == q.dtype)


def _kl_call(q, k, lse, qI, kI, w, sel, lseI):
    """(rows' loss [b, L], dqI [b, L, nI, dI], dkI [b, L, dI], dw
    [b, L, nI]), the gradient that of the loss summed over the rows."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, length, hq, dh = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    n_heads, d_index = qI.shape[2:]
    bq, bk = att._bd_query_tile(g, length), att._bd_key_tile(length)
    tables, steps = _tables(sel, bq, bk)

    def at(b_, s):
        return b_ * steps + s

    qg, kg, _ = att._bd_split(q, k, k)
    spec = pl.BlockSpec
    kl, dqI, dkI, dw = att._bd_call(
        _kl_kernel, tables, steps, b,
        in_specs=[
            spec((1, hkv, g, bq, dh), lambda b_, s, qi, *_:
                 (b_, 0, 0, qi[at(b_, s)], 0)),
            spec((1, hkv, bk, dh), lambda b_, s, qi, ki, *_:
                 (b_, 0, ki[at(b_, s)], 0)),
            spec((1, hkv, g, bq), lambda b_, s, qi, *_:
                 (b_, 0, 0, qi[at(b_, s)])),
            spec((1, n_heads, bq, d_index), lambda b_, s, qi, *_:
                 (b_, 0, qi[at(b_, s)], 0)),
            spec((1, bk, d_index), lambda b_, s, qi, ki, *_:
                 (b_, ki[at(b_, s)], 0)),
            spec((1, n_heads, bq), lambda b_, s, qi, *_:
                 (b_, 0, qi[at(b_, s)])),
            spec((1, 1, bq), lambda b_, s, qi, *_: (b_, 0, qi[at(b_, s)])),
            spec((1, sel.shape[1], bq), lambda b_, s, qi, *_:
                 (b_, 0, qi[at(b_, s)]))],
        out_specs=[
            spec((1, 1, bq), lambda b_, s, qi, *_: (b_, 0, qi[at(b_, s)])),
            spec((1, n_heads, bq, d_index), lambda b_, s, qi, *_:
                 (b_, 0, qi[at(b_, s)], 0)),
            spec((1, length, d_index), lambda b_, *_: (b_, 0, 0)),
            spec((1, n_heads, bq), lambda b_, s, qi, *_:
                 (b_, 0, qi[at(b_, s)]))],
        out_shape=[jax.ShapeDtypeStruct((b, 1, length), jnp.float32),
                   jax.ShapeDtypeStruct((b, n_heads, length, d_index),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((b, length, d_index), jnp.float32),
                   jax.ShapeDtypeStruct((b, n_heads, length), jnp.float32)],
        scratch=[pltpu.VMEM((1, bq), jnp.float32),
                 pltpu.VMEM((d_index, n_heads * bq), jnp.float32),
                 pltpu.VMEM((1, n_heads * bq), jnp.float32),
                 pltpu.VMEM((length // bk, bk, d_index), jnp.float32)],
        scale=1.0 / math.sqrt(dh), steps=steps)(
            *tables, qg.reshape(b, hkv, g, length, dh),
            kg.reshape(b, hkv, length, dh), lse.reshape(b, hkv, g, length),
            jnp.swapaxes(qI, 1, 2), kI,
            jnp.swapaxes(w.astype(jnp.float32), 1, 2), lseI[:, None], sel)
    return (kl[:, 0], jnp.swapaxes(dqI, 1, 2), dkI, jnp.swapaxes(dw, 1, 2))


@jax.custom_vjp
def _kl_tiled(q, k, lse, qI, kI, w, sel, lseI):
    return jnp.mean(_kl_call(q, k, lse, qI, kI, w, sel, lseI)[0])


def _kl_tiled_fwd(q, k, lse, qI, kI, w, sel, lseI):
    kl, dqI, dkI, dw = _kl_call(q, k, lse, qI, kI, w, sel, lseI)
    return jnp.mean(kl), (dqI, dkI, dw, q, k, lse, qI, kI, w, sel, lseI)


def _kl_tiled_bwd(residuals, g):
    dqI, dkI, dw, *operands = residuals
    scale = g / dqI.shape[0] / dqI.shape[1]         # the mean over rows
    q, k, lse, qI, kI, w, sel, lseI = operands
    return (jnp.zeros_like(q), jnp.zeros_like(k), jnp.zeros_like(lse),
            (dqI * scale).astype(qI.dtype), (dkI * scale).astype(kI.dtype),
            (dw * scale).astype(w.dtype), None, jnp.zeros_like(lseI))


_kl_tiled.defvjp(_kl_tiled_fwd, _kl_tiled_bwd)


@registry.register("dsa_indexer_loss", backend="pallas")
def dsa_indexer_loss_pallas(q, k, lse, qI, kI, w, sel, lseI):
    """The one-pass kernel; delegates to the xla backend for calls
    ``dsa_kl_supported`` refuses."""
    if not dsa_kl_supported(q, k, qI, sel):
        return dsa_indexer_loss_xla(q, k, lse, qI, kI, w, sel, lseI)
    return _kl_tiled(q, k, lse, qI, kI, w, sel, lseI)


def dsa_indexer_loss(q, k, lse, qI, kI, w, sel, lseI):
    """The indexer's loss, a float32 scalar whose gradient reaches
    ``qI``, ``kI`` and ``w`` alone (the module docstring)."""
    return registry.get("dsa_indexer_loss")(
        jax.lax.stop_gradient(q), jax.lax.stop_gradient(k),
        jax.lax.stop_gradient(lse), qI, kI, w, sel,
        jax.lax.stop_gradient(lseI))
