"""SDAR-30B-A3B-Chat (``model_type`` sdar_moe), one chip's share, in
plain float32 ``jax.numpy``: none of the program's layer code, reading
the net's parameter tree. Source: https://huggingface.co/JetLM/
SDAR-30B-A3B-Chat/blob/main/config.json; the cut and what is assumed:
``benchmark/configs/sdar_30b_a3b.json``.

A decoder layer, for a row ``x`` of the residual stream (``u =
RMSNorm(x)``, statistics in float32):

    q = W_q u (32 x 128)   k = W_k u (4 x 128)   v = W_v u (4 x 128)
    q_h <- RMSNorm_128(q_h), k_g <- RMSNorm_128(k_g)   one weight each
    q_h, k_g <- RoPE(., position p, theta = 1e6)        rotate-half
    s_ij = q_h,i . k_g(h),j / sqrt(128) where M(i, j), -inf elsewhere
    a = x + W_o concat_h(sum_j softmax(s)_ij v_g(h),j)     g(h) = h div 8
    w = RMSNorm(a);  r = softmax(W_r w) over all 128 experts
    S = the 8 largest;  c_e = r_e / sum_{e' in S} r_e'
    y = a + sum_{e in S, e held here} c_e W_down,e(silu(W_gate,e w) * W_up,e w)

The model: a token embedding (a gather), the layers, a final RMSNorm, an
untied head. The input is ``2L`` ids, a noised copy ``xt`` then the clean
copy ``x0`` of one sequence, both at positions ``0..L-1``; with
``blk(i) = (i mod L) div B`` and ``n(i)`` = "row i < L" (noised), row i
sees row j (``M(i, j)``) where

    n(i) and n(j):          blk(i) == blk(j)
    n(i) and not n(j):      blk(j) <  blk(i)
    not n(i) and not n(j):  blk(j) <= blk(i)
    not n(i) and n(j):      never

Logits are taken on the L noised rows. ``loss = 1/(b L) sum_i weights_i
(-log softmax(logits_i)[labels_i])``. What experts held elsewhere would
add is left out, as in the program (the guide's cut: the partial result
goes on to the next layer).

Departures: none from the equations above. Attention is computed for
``QUERY_ROWS`` query rows at a time and the experts one after another, so
that the timed size fits beside the net (scores of 32 heads x 512 x
8,192 rows are 0.5 GB).

What ``correct`` compares on the chip (``runners/train_fit_tokens.py``),
all of it on the seeded initial parameters at the timed size:

- the first training step's loss against ``loss``, end to end;
- the logits of ``net.output`` against ``logits``, end to end, entry by
  entry, relative to the largest logit: the rows whose routing was near
  a tie in no layer against ``LOGITS_RTOL``, all rows against
  ``LOGITS_RTOL_ALL_ROWS``;
- every decoder layer's output (the train-mode forward) against
  ``block`` on the program's own input to that layer, entry by entry,
  relative to the largest entry the layer adds to its input. The program
  computes in bf16, so a layer's router sees inputs that differ from this
  module's by the rounding upstream, and a row whose best experts are
  nearly tied takes the other one: no fault, and such a row's output
  moves by as much as one expert adds, as far as fp8 would move it. So a
  layer is compared on the program's input to it, where rounding cannot
  spread from row to row through the layers before; every row is
  compared, the rows whose routing is not within ``TIE_GAP`` of a tie
  (``risk`` false) against ``BLOCK_RTOL``, the others against that and
  twice the largest entry one pair could add, which lets one pair go
  for another;
- from the timed program itself, the state its first dispatch leaves:
  Adam's first moment (the mean of that dispatch's gradients that
  ``adam`` makes of ``jax.grad(loss)`` on the ring's batches at the
  initial parameters) and the parameters' change, leaf by leaf, as the
  norm of the difference over the norm of this module's; how the
  experts' and the router's leaves are read is written at
  ``GRAD_RTOL``.

Tolerances (readings on the v5e in PERF.md, Findings PR 31):
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

TOP_K = 8
ROPE_THETA = 1e6
EPS = 1e-6
BLOCK_LEN = 4
QUERY_ROWS = 512

# A router logit is w . W_r[:, e] over 2,048 entries of about 1 x 0.02.
# The program's differ from this module's by what its bf16 attention
# leaves in w: at most 0.010 in any of 8,192 x 128 logits of a layer on
# the v5e (0.005 at the 99.9th percentile of rows; PERF.md Findings PR
# 31). 0.05 is five of the largest seen.
TIE_GAP = 0.05

# max |system - reference| over a block's entries, relative to the
# largest entry of |reference - input|: of what the layer adds to the
# stream (the stream itself is ten times that, and would hide it).
# BLOCK_RTOL, for the rows not near a tie, lies between two readings on
# the v5e: the program in bf16, 0.0053 to 0.0127 over 13 seeds, and this
# module against itself with every product's operands rounded to
# float8_e4m3fn, the nearest precision below bf16, 0.035 to 0.075, which
# has to fail. A row that loses or gains one (row, expert) pair reads
# 0.03 to 0.046 and fails it too. The rows near a tie (7 to 18 in a
# hundred) are held to BLOCK_RTOL and twice the largest entry one pair
# could add (``experts``), since there a pair may go for another: they
# read 0.054 to 0.069 against 0.13 to 0.16.
BLOCK_RTOL = 0.025
# the logits of net.output, end to end: max |logit - reference| over all
# entries, relative to the largest |reference logit|. LOGITS_RTOL for the
# rows whose routing was near a tie in no layer (47 to 82 in a hundred):
# bf16 reads 0.0048 to 0.0060 on twelve seeds and 0.0112, 0.0115 on two
# (such a row attends to few keys, one of which took another expert),
# float8_e4m3fn 0.13 to 0.16. A row that took another expert somewhere
# carries the difference to its logits: all rows read 0.037 to 0.070,
# and are held to what only a gross fault passes.
LOGITS_RTOL = 0.04
LOGITS_RTOL_ALL_ROWS = 0.2
# the first training step's loss against ``loss`` end to end, relative:
# 1e-7 to 6e-5 over the seeds. It cannot tell precisions apart (the
# logits do that); it catches a wrong weight, label or divisor.
LOSS_RTOL = 5e-4
# Adam's first moment after the first dispatch, a leaf at a time:
# |system - reference|_2 / |reference|_2, worst leaf. GRAD_RTOL, for the
# leaves outside the experts' half: bf16 reads 0.0147 to 0.0215 over 10
# seeds, and this module's own gradient with every forward product's
# operands rounded to float8_e4m3fn 0.37 to 0.49 (0.07 on the leaf it
# moves least). The experts' matrices are sums over routed pairs, and a
# share f of rows taking another expert in bf16 moves such a sum over
# unrelated rows by sqrt(2 f); where the rows of one token, [MASK], sit
# at a tie (a layer in ten) the two experts they are shared between
# read 0.25 as whole leaves. So the runner reads them by their median
# expert's slice, 0.06 to 0.13, against GRAD_RTOL_EXPERTS. The router's
# own leaves (ROUTER_LEAVES) have no expert's slice to take and read 0.04
# to 0.31, most where the rows that carry loss sit at a tie: read, not
# held.
GRAD_RTOL = 0.05
GRAD_RTOL_EXPERTS = 0.3
EXPERT_LEAVES = ("Wg", "Wu", "Wd")
ROUTER_LEAVES = ("ln_g", "Wr")
# the parameters' change over the first dispatch, likewise; 1 is what a
# state left unchanged reads. It reads 0.06 to 0.17: at 1e-7 a step
# moves a norm's weight of 1 by less than the 1.2e-7 between its float32
# neighbours, and Adam divides a gradient by its own size, so an entry
# near 0 takes its sign from the rounding.
UPDATE_RTOL = 0.5


def _lowered(a, dtype):
    """``a`` rounded to ``dtype`` (None: as it is), as float32: what a
    product in that precision would read. The gradient passes through
    the rounding as it stands. ``dtype`` may be a pair (dtype, on): the
    rounding is made where ``on``, which may be traced, so that one
    compiled program gives the reading in both precisions."""
    a = a.astype(jnp.float32)
    if dtype is None:
        return a
    kind, on = dtype if isinstance(dtype, tuple) else (dtype, True)
    return a + jnp.where(on, jax.lax.stop_gradient(
        a.astype(kind).astype(jnp.float32) - a), 0.0)


def _mm(a, b, dtype):
    return _lowered(a, dtype) @ _lowered(b, dtype)


def rms_norm(x, g, eps=EPS):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def rope(x, theta):
    """x [2L, h, dh]; rotate-half over the whole head, positions
    0..L-1, 0..L-1."""
    t, _, dh = x.shape
    pos = jnp.arange(t) % (t // 2)
    freq = theta ** (-jnp.arange(0, dh, 2) / dh)
    angle = (pos[:, None] * freq[None, :]).astype(jnp.float32)
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1)[:, None, :]
    half = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], -1)
    return x * cos + half * sin


def visible(i, j, seq_len, block_len):
    """M(i, j) of the docstring."""
    ni, nj = i < seq_len, j < seq_len
    bi, bj = (i % seq_len) // block_len, (j % seq_len) // block_len
    return ((ni & nj & (bi == bj)) | (ni & ~nj & (bj < bi))
            | (~ni & ~nj & (bj <= bi)))


def attention(q, k, v, block_len, dtype=None):
    """q [2L, hq, dh], k and v [2L, hkv, dh] -> [2L, hq, dh]."""
    t, hq, dh = q.shape
    group = hq // k.shape[1]
    k = _lowered(jnp.repeat(k, group, axis=1), dtype)
    v = _lowered(jnp.repeat(v, group, axis=1), dtype)
    q = _lowered(q, dtype)
    cols = jnp.arange(t)
    step = min(QUERY_ROWS, t)

    def some_rows(start):
        rows = start + jnp.arange(step)
        s = jnp.einsum("ihd,jhd->hij", jax.lax.dynamic_slice_in_dim(
            q, start, step), k) / math.sqrt(dh)
        s = jnp.where(visible(rows[:, None], cols[None, :], t // 2,
                              block_len)[None], s, -jnp.inf)
        p = _lowered(jax.nn.softmax(s, axis=-1), dtype)
        return jnp.einsum("hij,jhd->ihd", p, v)

    starts = jnp.arange(0, t, step)
    if t % step:
        raise ValueError(f"{t} rows are not whole groups of {step}")
    # a gradient keeps no group's scores: it makes them again
    return jax.lax.map(jax.checkpoint(some_rows), starts).reshape(t, hq, dh)


def routing(w, p, top_k, first_expert, with_largest=False):
    """The weight of every held expert for every row, ``c`` [R, held]
    (0 where the expert is not among the row's ``top_k``), and ``risk``
    [R]: an expert held here is within TIE_GAP of changing sides, a
    chosen one of the (k+1)-th logit or a passed-over one of the k-th.
    ``with_largest``: ``c`` comes with the row's largest weight [R]."""
    z = jnp.dot(w, p["Wr"], precision=jax.lax.Precision.HIGHEST)
    r = jax.nn.softmax(z, axis=-1)
    order = jnp.argsort(-z, axis=-1)
    chosen = jnp.zeros(z.shape, bool).at[
        jnp.arange(z.shape[0])[:, None], order[:, :top_k]].set(True)
    c = jnp.where(chosen, r, 0.0)
    c = c / jnp.sum(c, axis=-1, keepdims=True)
    held = p["Wg"].shape[0]
    ranked = jnp.take_along_axis(z, order, axis=-1)
    to_other_side = jnp.where(chosen, z - ranked[:, top_k:top_k + 1],
                              ranked[:, top_k - 1:top_k] - z)
    risk = jnp.any(to_other_side[:, first_expert:first_expert + held]
                   < TIE_GAP, axis=-1)
    here = c[:, first_expert:first_expert + held]
    return ((here, jnp.max(c, axis=-1)) if with_largest else here), risk


def attend(p, x, *, block_len=BLOCK_LEN, theta=ROPE_THETA, eps=EPS,
           dtype=None):
    """The attention half of a layer: ``x`` [2L, d] -> ``a`` [2L, d]."""
    t = x.shape[0]
    dh = p["q_norm_g"].shape[0]
    u = rms_norm(x, p["attn_ln_g"], eps)
    q = rms_norm(_mm(u, p["Wq"], dtype).reshape(t, -1, dh), p["q_norm_g"], eps)
    k = rms_norm(_mm(u, p["Wk"], dtype).reshape(t, -1, dh), p["k_norm_g"], eps)
    v = _mm(u, p["Wv"], dtype).reshape(t, -1, dh)
    o = attention(rope(q, theta), rope(k, theta), v, block_len, dtype)
    return x.astype(jnp.float32) + _mm(o.reshape(t, -1), p["Wo"], dtype)


def experts(p, a, *, top_k=TOP_K, first_expert=0, eps=EPS, dtype=None):
    """The expert half: ``a`` [R, d] -> (y [R, d], risk [R], the largest
    entry that one (row, held expert) pair could add: any held expert's
    output for any row under that row's largest weight). ``p`` holds the
    router over all experts and the weights of those held here,
    ``first_expert`` the first of them."""
    a = a.astype(jnp.float32)
    w = rms_norm(a, p["ln_g"], eps)
    c, risk = routing(w, p, top_k, first_expert, with_largest=True)
    c, largest = c

    def one_expert(carry, e):
        y, most = carry
        wg, wu, wd, ce = e
        h = jax.nn.silu(_mm(w, wg, dtype)) * _mm(w, wu, dtype)
        out = _mm(h, wd, dtype)
        most = jnp.maximum(most, jnp.max(jnp.abs(largest[:, None] * out)))
        return (y + ce[:, None] * out, most), None

    (y, most), _ = jax.lax.scan(one_expert, (a, jnp.zeros((), jnp.float32)),
                                (p["Wg"], p["Wu"], p["Wd"], c.T))
    return y, risk, most


def block(p, x, *, top_k=TOP_K, first_expert=0, block_len=BLOCK_LEN,
          theta=ROPE_THETA, eps=EPS, dtype=None):
    """One decoder layer on ``x`` [2L, d] -> (y [2L, d], risk [2L], the
    largest entry of one pair) as ``experts`` gives them."""
    a = attend(p, x, block_len=block_len, theta=theta, eps=eps, dtype=dtype)
    return experts(p, a, top_k=top_k, first_expert=first_expert, eps=eps,
                   dtype=dtype)


def _layers(params):
    """(embedding, [blocks], final norm, head) of the net's tree, whose
    layers are named ``layer_<i>`` in order."""
    names = sorted(params, key=lambda k: int(k.rsplit("_", 1)[1]))
    return (params[names[0]], [params[n] for n in names[1:-2]],
            params[names[-2]], params[names[-1]])


def head(params, x, *, eps=EPS, dtype=None):
    """Final norm and logits of the noised half of ``x`` [2L, d]."""
    _, _, norm, out = _layers(params)
    h = rms_norm(x[:x.shape[0] // 2], norm["g"], eps)
    return _mm(h, out["W"], dtype)


def logits_and_risk(params, state, ids, **how):
    """ids int [b, 2L] -> logits [b, L, vocab] of the noised rows, and
    for each of them whether its routing was near a tie in any layer."""
    emb, blocks, _, _ = _layers(params)
    eps_dtype = {k: how[k] for k in ("eps", "dtype") if k in how}
    layer = jax.checkpoint(functools.partial(block, **how))

    def one(row):
        x = emb["W"][row]
        risk = jnp.zeros(row.shape, bool)
        for p in blocks:
            x, here, _ = layer(p, x)
            risk |= here
        return head(params, x, **eps_dtype), risk[:row.shape[0] // 2]

    z, risk = zip(*(one(row) for row in ids))
    return jnp.stack(z), jnp.stack(risk)


def logits(params, state, ids, **how):
    return logits_and_risk(params, state, ids, **how)[0]


def loss(params, state, ids, labels, weights, with_logits=False, **how):
    """The objective; ``with_logits``: (loss, (logits, risk)), the shape
    ``jax.value_and_grad(..., has_aux=True)`` takes."""
    z, risk = logits_and_risk(params, state, ids, **how)
    nll = (jax.nn.logsumexp(z, axis=-1)
           - jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0])
    value = jnp.sum(nll * weights) / labels.size
    return (value, (z, risk)) if with_logits else value


def adam(grads, params, steps, *, learning_rate, beta1=0.9, beta2=0.999,
         epsilon=1e-8):
    """``steps`` steps of Adam (Kingma and Ba 2015, section 2's form:
    ``alpha_t = lr sqrt(1 - beta2^t) / (1 - beta1^t)``, ``p -= alpha_t m
    / (sqrt(v) + epsilon)``) from ``params`` and zero moments, step ``i``
    on ``grads[i mod len(grads)]``: what a ring of batches gives while
    the parameters have hardly moved. Returns (first moment, parameters'
    change), in the parameters' dtype."""
    tree = jax.tree_util.tree_map
    m = tree(jnp.zeros_like, params)
    v = tree(jnp.zeros_like, params)
    p = params
    for i in range(steps):
        g = grads[i % len(grads)]
        m = tree(lambda m_, g_: beta1 * m_ + (1 - beta1) * g_, m, g)
        v = tree(lambda v_, g_: beta2 * v_ + (1 - beta2) * g_ * g_, v, g)
        alpha = (learning_rate * math.sqrt(1 - beta2 ** (i + 1))
                 / (1 - beta1 ** (i + 1)))
        p = tree(lambda p_, m_, v_: p_ - alpha * m_ / (jnp.sqrt(v_) + epsilon),
                 p, m, v)
    return m, tree(jnp.subtract, p, params)
