"""The language model of Keye-VL-2.0-30B-A3B (``model_type`` KeyeVL2),
one chip's share, in plain float32 ``jax.numpy``: none of the program's
layer or op code, reading the net's parameter tree and state. Source:
https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json;
the cut and what is assumed: ``benchmark/configs/keye_vl2_30b_a3b.json``.

Every layer, for the rows ``x`` [L, d] of one sequence, row ``t`` at
position ``t``, ``h = RMSNorm(x)`` (statistics in float32, eps 1e-6),
``rot`` rotate-half over the whole head at theta 1e7:

    q_h = rot(RMSNorm_q(W_q,h h))  [32 x 128]     k_g = rot(RMSNorm_k(W_k,g h)),  v_g = W_v,g h  [4 x 128]
    qI_t,j = rot((W_IQ h_t)_j)     j = 1..16, 64 each
    kI_s   = rot(LayerNorm(W_IK h_s))            64, one key head
    w_t,j  = (W_w h_t)_j / sqrt(16 * 64)
    I_t,s  = sum_j w_t,j relu(qI_t,j . kI_s)     s <= t
    S_t    = the 2,048 largest I_t,s (all t + 1 keys where t < 2,048), ties to the lower s
    P_h,t  = softmax_{s in S_t}(q_h,t . k_g(h),s / sqrt(128))      g(h) = h div 8
    a      = x + W_o concat_h(sum_{s in S_t} P_h,t,s v_g(h),s)
    u = RMSNorm(a);  r = softmax(W_r u) over all 128 experts;  E = the 8 largest
    y      = a + sum_{e in E, e held here} (r_e / sum_{E} r) W_d,e(silu(W_g,e u) * W_u,e u)

the indexer reading ``h`` without its gradient. The model: ``h =
layers(Emb[ids])``, ``logits = W RMSNorm_f(h)`` (an untied head), ``L =
mean_t nll(logits_t, ids_{t+1}) + sum_layers L_I`` with

    L_I = mean_t sum_{s in S_t} p_t,s (log p_t,s - log softmax_{S_t}(I_t)_s),
    p_t,s = mean_h P_h,t,s without its gradient,

DeepSeek-V3.2-Exp's indexer loss of its sparse training stage, weight 1,
summed over the layers. So the indexer's leaves get the gradient of the
``L_I`` alone and every other leaf that of the cross-entropy alone; the
selection has none. What experts held elsewhere would add is left out,
as in the program.

Departures: none from the equations above. Attention and the indexer
are computed for ``QUERY_ROWS`` query rows at a time (a block of ``I``
is 16 MB at the timed size; no [L, L] array exists) and the experts one
after another, so that loss and gradients fit beside the net.

The selection. The program makes it from bf16 operands, and rounding
moves a score by more than the gap between neighbouring order
statistics at rank 2,048, so near-tie swaps are expected in most rows.
``correct`` therefore holds the program's selection to this module's
scores (``check_selection``): every row keeps exactly ``min(t + 1,
2048)`` keys; every kept key scores at least ``tau_t - delta_t`` and
every passed-over visible key at most ``tau_t + delta_t``, ``tau_t`` this
module's 2,048-th largest score of row ``t`` and ``delta_t`` written at
``SELECT_UNITS``. A selection at random, or by position, fails it.
Downstream of the selection this module follows the program's (the
layer state's ``selection``, which the runner fills with what each layer
chose in the net's own train-mode forward), so the tight limits below
compare the same sums. Without it the module makes its own
(``select``).

What ``correct`` compares on the chip
(``runners/train_fit_sparse_tokens.py``), all of it on the seeded
initial parameters at the timed size: the first loss (the cross-entropy
and the indexers' losses, apart too), every layer's train-mode output on
the program's own input to it, the logits of ``net.output``, Adam's
first moment and the first change of every leaf (the indexer's among
them), and the selection as above.

Tolerances (readings on the v5e in PERF.md, Findings PR 43):
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.glm4_moe_lite import rope
from benchmark.reference.sdar_moe import (_lowered, _mm, adam, experts,
                                          rms_norm)

__all__ = ["KINDS", "adam", "at_random", "attention", "block", "by_position",
           "check_selection", "indexer", "kind_of", "logits", "loss",
           "select", "selection_of"]

TOP_K = 8
EPS = 1e-6
HEAD_DIM = 128
ROPE_THETA = 1e7
INDEX_HEADS = 16
INDEX_HEAD_DIM = 64
INDEX_TOPK = 2048
QUERY_ROWS = 512

KINDS = ("sparse_experts",)

# The router and its ties are SDAR's (the same widths, 128 experts, 8 a
# token, softmax): ``reference/sdar_moe.py`` has TIE_GAP's reason. Every
# limit below lies between two readings on the v5e (PERF.md, Findings PR
# 43; four seeds, the program in bf16 / this module against itself with
# every forward product's operands rounded to float8_e4m3fn, the nearest
# precision below bf16, which has to fail).
# A layer's output against ``block`` on the program's own input to it,
# entry by entry, beyond one unit of the stream's rounding
# (``train_fit_causal_tokens._beyond_rounding``), relative to the largest
# entry the layer adds, the rows not near a tie of the router: 0.0024 to
# 0.0083 / 0.027 to 0.040. The rows near one (17 to 19 in a hundred)
# read 0.024 to 0.036 against twice the largest entry one pair could
# add, 0.35 to 0.53.
BLOCK_RTOL = 0.015
# the logits of net.output, relative to the largest |reference logit|:
# the rows near a tie of the router in no layer 0.0082 to 0.0120 / 0.101
# to 0.119; all rows 0.030 to 0.037, held below what fp8 moves the rows
# without a tie.
LOGITS_RTOL = 0.04
LOGITS_RTOL_ALL_ROWS = 0.08
# the first step's loss against ``loss``, relative, and each of its two
# parts, the cross-entropy and the indexers' losses summed: the total
# 1.9e-6 to 4.6e-6, the cross-entropy 1.1e-6 to 6.1e-6, the indexers'
# 3.9e-5 to 1.1e-4 (the accepted cells' limit, four times the largest).
# It cannot tell precisions apart (the logits do); it catches a wrong
# label, weight, divisor or a loss term left out.
LOSS_RTOL = 5e-4
# Adam's first moment after the first dispatch, a leaf at a time,
# |system - reference|_2 / |reference|_2, worst leaf. The first dispatch
# is 8 steps, and from the second on the program chooses its keys on
# moved parameters: by the dispatch's end 1.3 / 3.8 / 5.8 / 7.7 keys a
# row have changed, layer by layer (the runner's
# ``moved_by_first_dispatch``; seven seeds on the v5e, PR 43), which
# this module, computing every step's gradient on the seeded parameters
# and the first selection, cannot follow. So the runner also reads, for
# every leaf, how far this module's own gradient moves when it follows
# the selection the dispatch ends on (``grad_selection_moved``), and a
# leaf outside the experts, the router and the indexer is held to
# GRAD_RTOL, SDAR's, BEYOND that move: it reads 0.0009 to 0.0015 there
# (the moment itself 0.020 to 0.065, the move 0.009 to 0.070, leaf by
# leaf alike: W_q 0.020-0.056 / 0.024-0.067, W_o 0.010-0.018 /
# 0.009-0.020) / fp8 0.45 to 0.46. The routed matrices by their median
# expert's slice (``reference/sdar_moe.py`` says why): 0.062 to 0.071
# against GRAD_RTOL_EXPERTS, fp8 0.162 to 0.167 on the leaf it moves
# least. The indexer's five leaves, whose gradient is that of the
# indexers' losses alone: 0.049 to 0.056 / 0.42 to 0.54; it is the
# difference of two distributions that nearly agree at the seeded init
# (softmax_S(I) and the heads' mean attention, a KL of 0.09 a layer), so
# the bf16 rounding of the heads' scores is a larger share of it than of
# the attention's own gradient: on the CPU, following the step's own
# selection, the indexer's leaves read 0.040 to 0.065 where W_q reads
# 0.009 to 0.011. The router's own leaves (ROUTER_LEAVES) read 0.070 to
# 0.081, fp8 0.17: read, not held, as in the SDAR cell.
GRAD_RTOL = 0.05
GRAD_RTOL_EXPERTS = 0.12
GRAD_RTOL_INDEXER = 0.15
EXPERT_LEAVES = ("Wg", "Wu", "Wd")
ROUTER_LEAVES = ("ln_g", "Wr")
INDEXER_LEAVES = ("W_IQ", "W_IK", "kI_ln_g", "kI_ln_b", "W_w")
# the parameters' change over the first dispatch, likewise; 1 is what a
# state left unchanged reads. Adam divides every entry by its own
# gradient's size, so each moves by about the rate whatever its size,
# and the change weighs the small entries, whose gradient's rounding is
# the largest share of them, as much as the large ones, which decide the
# moment's norm: a matrix's change reads 1.5 to 4 times its moment, leaf
# by leaf (W_IK 0.073 to 0.110 for 0.019 to 0.054, W_q 0.032 to 0.096 for
# 0.020 to 0.055, W_IQ 0.037 to 0.054 for 0.019 to 0.027, the experts'
# median slice 0.063 to 0.108 for 0.039 to 0.070; PR 43's first chip
# runs). A norm's weight of 1 moves by 1e-7 a step where its float32
# neighbours lie 1.2e-7 above and 6e-8 below, so an entry moves 0, 1 or
# 2 units a step as the rounding falls: the head norms read 0.04 to 0.28.
# kI_ln_g reads 0 (its moment 0.0003 to 0.0007: all 64 entries take the
# same rounding on both sides).
UPDATE_RTOL = 0.5
# delta_t of the selection check, in units of 2^-8 (bf16's unit
# roundoff) of the row's largest sum of the absolute values of the
# products a score is made of, max_s sum_j |w_t,j| sum_d |qI_t,j,d|
# |kI_s,d|. The worst kept or passed-over key of a layer, in those units,
# on the v5e at the timed size (PR 43): the program 0.21 to 0.63 (3.2 to
# 5.5 keys a row swapped near the 2,048-th place; the bf16 bits of the
# indexer's operands, and so the near-ties, follow the program that makes
# them: 0.21-0.26 in a program of the selection alone, 0.35-0.63 in the
# check's forward), this module's own selection with every product's
# operands rounded to float8_e4m3fn 1.93 to 2.89, a selection at random
# 53 to 85, by position 50 to 85. The selection is held to the scores of
# the input it was made from: held to another program's activations of
# the same layer it read up to 1.64 in layer 4.
SELECT_UNITS = 1.1


def kind_of(p):
    """One of ``KINDS`` from a layer's parameter names, None for a layer
    that is no block (the embedding, the final norm, the head)."""
    return "sparse_experts" if "W_IQ" in p else None


def layer_norm(x, g, b, eps=EPS):
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


def indexer(p, h, *, index_heads=INDEX_HEADS, index_head_dim=INDEX_HEAD_DIM,
            theta=ROPE_THETA, eps=EPS, dtype=None):
    """qI [L, nI, dI], kI [L, dI] and w [L, nI] of the normed rows ``h``
    [L, d], without their gradient."""
    h = jax.lax.stop_gradient(h)
    length = h.shape[0]
    q_index = rope(_mm(h, p["W_IQ"], dtype).reshape(
        length, index_heads, index_head_dim), theta)
    k_index = rope(layer_norm(_mm(h, p["W_IK"], dtype), p["kI_ln_g"],
                              p["kI_ln_b"], eps)[:, None, :], theta)[:, 0]
    w = _mm(h, p["W_w"], dtype) / math.sqrt(index_heads * index_head_dim)
    return q_index, k_index, w


def _scores(q_index, k_index, w, dtype=None):
    """I of some rows [R, L] and, beside it, the sum of the absolute
    values of every product it is made of, sum_j |w_j| sum_d |qI_j,d|
    |kI_d| [R, L]."""
    logits = jnp.einsum("rjd,sd->rjs", _lowered(q_index, dtype),
                        _lowered(k_index, dtype))
    w = _lowered(w, dtype)
    scores = jnp.einsum("rj,rjs->rs", w, jnp.maximum(logits, 0.0))
    return (jnp.where(scores == 0, 0.0, scores),
            jnp.einsum("rj,rjd,sd->rs", jnp.abs(w), jnp.abs(q_index),
                       jnp.abs(k_index)))


def _steps(length):
    step = math.gcd(length, QUERY_ROWS)
    return step, jnp.arange(0, length, step)


def _rows_kept(words, rows):
    """bool [R, L] of the words [L/32, L]: bit j of word i of row t keeps
    key j * L/32 + i."""
    cols = words[:, rows].T                                  # [R, L/32]
    bits = jnp.right_shift(cols[:, None, :],
                           jnp.arange(32, dtype=jnp.int32)[None, :, None]) & 1
    return bits.reshape(rows.shape[0], -1) != 0


def _pack_rows(keep):
    """The words [L/32, R] of some rows' keep [R, L]."""
    r, length = keep.shape
    bits = keep.reshape(r, 32, length // 32).astype(jnp.int32)
    return jnp.sum(jnp.left_shift(
        bits, jnp.arange(32, dtype=jnp.int32)[None, :, None]), axis=1,
        dtype=jnp.int32).T


def _top(scores, rows, topk):
    """The words [L/32, R] of the ``topk`` largest visible ``scores``
    [R, L] of ``rows`` (every visible key where there are fewer)."""
    visible = jnp.arange(scores.shape[1])[None, :] <= rows[:, None]
    _, chosen = jax.lax.top_k(jnp.where(visible, scores, -jnp.inf),
                              min(topk, scores.shape[1]))
    keep = jnp.zeros(scores.shape, bool).at[
        jnp.arange(rows.shape[0])[:, None], chosen].set(True) & visible
    return _pack_rows(keep)


def _by_rows(length, words_of):
    """The words [L/32, L] of a sequence, ``words_of(rows)`` [L/32, R] a
    block of rows at a time."""
    step, starts = _steps(length)
    return jnp.concatenate(list(jax.lax.map(
        lambda start: words_of(start + jnp.arange(step)), starts)), axis=1)


def select(q_index, k_index, w, topk=INDEX_TOPK, dtype=None):
    """This module's own selection of one sequence, as words [L/32, L];
    ``dtype``: the products' operands rounded to it."""
    def words_of(rows):
        scores, _ = _scores(
            jax.lax.dynamic_slice_in_dim(q_index, rows[0], rows.shape[0]),
            k_index, jax.lax.dynamic_slice_in_dim(w, rows[0], rows.shape[0]),
            dtype)
        return _top(scores, rows, topk)

    return _by_rows(k_index.shape[0], words_of)


def selection_of(p, x, *, topk=INDEX_TOPK, dtype=None, **how):
    """This module's selection of the layer's input ``x`` [L, d], words
    [L/32, L]; ``dtype``: every product's operands rounded to it, the
    indexer's projections among them."""
    h = rms_norm(x, p["attn_ln_g"], how.get("eps", EPS))
    return select(*indexer(p, h, dtype=dtype, **{
        k: how[k] for k in ("index_heads", "index_head_dim", "theta", "eps")
        if k in how}), topk, dtype)


def at_random(key, length, topk=INDEX_TOPK):
    """A selection with the right count of keys in every row, the keys
    drawn at random among the visible: what ``check_selection`` has to
    refuse."""
    def words_of(rows):
        return _top(jax.random.uniform(jax.random.fold_in(key, rows[0]),
                                       (rows.shape[0], length)), rows, topk)

    return _by_rows(length, words_of)


def by_position(length, topk=INDEX_TOPK):
    """The ``topk`` most recent keys of every row: a sliding window, what
    ``check_selection`` has to refuse."""
    def words_of(rows):
        return _top(jnp.broadcast_to(jnp.arange(length, dtype=jnp.float32),
                                     (rows.shape[0], length)), rows, topk)

    return _by_rows(length, words_of)


def check_selection(p, x, words, *, topk=INDEX_TOPK, **how):
    """The program's selection ``words`` [L/32, L] of one sequence held
    to this module's scores on the layer's input ``x`` [L, d]: per row,
    whether it keeps ``min(t + 1, topk)`` keys, and the worst of ``(tau
    - kept score) / delta`` and ``(passed-over score - tau) / delta``
    (at most 1 passes), and how many kept keys score below ``tau`` (the
    near-tie swaps)."""
    eps = how.get("eps", EPS)
    words = jnp.asarray(words)
    q_index, k_index, w = indexer(
        p, rms_norm(x, p["attn_ln_g"], eps),
        **{k: how[k] for k in ("index_heads", "index_head_dim", "theta",
                               "eps") if k in how})
    length = x.shape[0]
    step, starts = _steps(length)
    cols = jnp.arange(length)
    unit = 2.0 ** -8 * SELECT_UNITS

    def some_rows(start):
        rows = start + jnp.arange(step)
        scores, size = _scores(
            jax.lax.dynamic_slice_in_dim(q_index, start, step), k_index,
            jax.lax.dynamic_slice_in_dim(w, start, step))
        visible = cols[None, :] <= rows[:, None]
        keep = _rows_kept(words, rows)
        k = min(topk, length)
        tau = jax.lax.top_k(jnp.where(visible, scores, -jnp.inf), k)[0][:, -1]
        delta = unit * jnp.max(jnp.where(visible, size, 0.0), axis=1)
        below = jnp.max(jnp.where(keep, tau[:, None] - scores, -jnp.inf), 1)
        above = jnp.max(jnp.where(visible & ~keep, scores - tau[:, None],
                                  -jnp.inf), 1)
        # rows with no more keys than places keep every visible one
        all_kept = jnp.all(keep == visible, axis=1)
        worst = jnp.where(rows < k, jnp.where(all_kept, 0.0, jnp.inf),
                          jnp.maximum(below, above) / delta)
        return (jnp.sum(keep, axis=1) == jnp.minimum(rows + 1, k), worst,
                jnp.sum(keep & (scores < tau[:, None]), axis=1))

    count_ok, worst, swaps = (a.reshape(-1) for a in
                              jax.lax.map(some_rows, starts))
    return {"rows_count_ok": count_ok, "worst": worst, "swaps": swaps}


def attention(p, x, words=None, *, head_dim=HEAD_DIM, theta=ROPE_THETA,
              eps=EPS, index_heads=INDEX_HEADS, index_head_dim=INDEX_HEAD_DIM,
              topk=INDEX_TOPK, dtype=None):
    """``x`` [L, d] -> (``x + W_o attention`` [L, d], L_I of the layer).
    ``words`` the selection to follow [L/32, L], None: this module's."""
    t = x.shape[0]
    x = x.astype(jnp.float32)
    h = rms_norm(x, p["attn_ln_g"], eps)
    q = rope(rms_norm(_mm(h, p["Wq"], dtype).reshape(t, -1, head_dim),
                      p["q_norm_g"], eps), theta)
    k = rope(rms_norm(_mm(h, p["Wk"], dtype).reshape(t, -1, head_dim),
                      p["k_norm_g"], eps), theta)
    v = _mm(h, p["Wv"], dtype).reshape(t, -1, head_dim)
    q_index, k_index, w = indexer(p, h, index_heads=index_heads,
                                  index_head_dim=index_head_dim, theta=theta,
                                  eps=eps, dtype=dtype)
    words = (select(q_index, k_index, w, topk) if words is None
             else jnp.asarray(words))
    group = q.shape[1] // k.shape[1]
    q = _lowered(q, dtype)
    k = _lowered(jnp.repeat(k, group, axis=1), dtype)
    v = _lowered(jnp.repeat(v, group, axis=1), dtype)
    step, starts = _steps(t)

    def some_rows(start):
        rows = start + jnp.arange(step)
        keep = _rows_kept(words, rows)
        s = jnp.einsum("ihd,jhd->hij", jax.lax.dynamic_slice_in_dim(
            q, start, step), k) / math.sqrt(head_dim)
        probs = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hij,jhd->ihd", _lowered(probs, dtype), v)
        p_bar = jax.lax.stop_gradient(jnp.mean(probs, axis=0))
        scores, _ = _scores(jax.lax.dynamic_slice_in_dim(q_index, start, step),
                            k_index,
                            jax.lax.dynamic_slice_in_dim(w, start, step),
                            dtype)
        log_soft = scores - jax.nn.logsumexp(
            jnp.where(keep, scores, -jnp.inf), axis=-1, keepdims=True)
        on = keep & (p_bar > 0)
        kl = jnp.sum(jnp.where(on, p_bar * (jnp.log(jnp.where(on, p_bar, 1.0))
                                            - log_soft), 0.0), axis=-1)
        return o, kl

    # a gradient keeps no block's scores: it makes them again
    o, kl = jax.lax.map(jax.checkpoint(some_rows), starts)
    return x + _mm(o.reshape(t, -1), p["Wo"], dtype), jnp.mean(kl)


def block(kind, p, state, x, *, top_k=TOP_K, first_expert=0,
          head_dim=HEAD_DIM, theta=ROPE_THETA, eps=EPS,
          index_heads=INDEX_HEADS, index_head_dim=INDEX_HEAD_DIM,
          topk=INDEX_TOPK, dtype=None):
    """One layer of ``kind`` on ``x`` [L, d] -> (y [L, d], risk [L], the
    largest entry of one pair) as ``sdar_moe.experts`` gives them, the
    selection the state's ``selection`` [L/32, L] where it has one."""
    a, _ = attention(p, x, state.get("selection"), head_dim=head_dim,
                     theta=theta, eps=eps, index_heads=index_heads,
                     index_head_dim=index_head_dim, topk=topk, dtype=dtype)
    return experts(p, a, top_k=top_k, first_expert=first_expert, eps=eps,
                   dtype=dtype)


def _names(params):
    return sorted(params, key=lambda k: int(k.rsplit("_", 1)[1]))


def logits_and_risk(params, state, ids, **how):
    """ids int [b, L] -> (logits [b, L, vocab], whether each row's
    routing was near a tie in any layer, the indexers' losses summed
    over the layers and averaged over the batch). A layer's state may
    hold the program's ``selection`` [b, L/32, L]."""
    names = _names(params)
    eps, dtype = how.get("eps", EPS), how.get("dtype")
    attend = {k: v for k, v in how.items() if k not in ("top_k",
                                                          "first_expert")}
    route = {k: how[k] for k in ("top_k", "first_expert", "eps", "dtype")
             if k in how}
    blocks = [n for n in names if kind_of(params[n])]

    def layer(p, x, words):
        a, kl = attention(p, x, words, **attend)
        y, risk, _ = experts(p, a, **route)
        return y, risk, kl

    layer = jax.checkpoint(layer)

    def one(i, row):
        x = params[names[0]]["W"][row]
        risk = jnp.zeros(row.shape, bool)
        index_loss = jnp.zeros((), jnp.float32)
        for name in blocks:
            words = state.get(name, {}).get("selection")
            x, here, kl = layer(params[name], x,
                                None if words is None else words[i])
            risk |= here
            index_loss = index_loss + kl
        h = rms_norm(x, params[names[-2]]["g"], eps)
        return _mm(h, params[names[-1]]["W"], dtype), risk, index_loss

    z, risk, index_loss = zip(*(one(i, row) for i, row in enumerate(ids)))
    return jnp.stack(z), jnp.stack(risk), jnp.mean(jnp.stack(index_loss))


def logits(params, state, ids, **how):
    return logits_and_risk(params, state, ids, **how)[0]


def loss(params, state, ids, labels, weights=None, with_logits=False,
         **how):
    """The objective; ``with_logits``: (loss, (logits, risk, [the
    cross-entropy, the indexers' losses])), the shape
    ``jax.value_and_grad(..., has_aux=True)`` takes."""
    z, risk, index_loss = logits_and_risk(params, state, ids, **how)
    nll = (jax.nn.logsumexp(z, axis=-1)
           - jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0])
    if weights is not None:
        nll = nll * weights
    ce = jnp.sum(nll) / labels.size
    value = ce + index_loss
    return ((value, (z, risk, jnp.stack([ce, index_loss])))
            if with_logits else value)
