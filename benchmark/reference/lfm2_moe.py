"""LFM2-24B-A2B (``model_type`` lfm2_moe), one chip's share, in plain
float32 ``jax.numpy``: none of the program's layer or op code, reading
the net's parameter tree and state. Source:
https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json; the
cut and what is assumed: ``benchmark/configs/lfm2_24b_a2b.json``.

Every layer is ``a = x + operator(RMSNorm_op(x))`` then ``a +
ffn(RMSNorm_ffn(a))`` (statistics in float32, eps 1e-5, a weight a
column, no bias anywhere). Which operator and which feed-forward a layer
has is read from its parameters' names (``kind_of``).

The short-convolution operator, ``u = RMSNorm_op(x)``, ``K`` positions:

    [B | C | x~] = W_in u                          [3 d], in this order
    g_t = B_t * x~_t                               (elementwise)
    s_t,c = sum_{k < K} w_c,k g_{t-K+1+k, c}        (g before position 0 is zero)
    operator = W_out (C_t * s_t)

The attention operator, ``Hq`` query heads on ``Hkv`` key/value heads of
``dh``, positions ``p = 0..L-1``:

    q_h = R_p RMSNorm_q(W_q,h u)   k_g = R_p RMSNorm_k(W_k,g u)   v_g = W_v,g u
    s_h,ij = q_h,i . k_g(h),j / sqrt(dh) for j <= i, -inf elsewhere
    operator = W_o concat_h(sum_j softmax(s_h)_ij v_g(h),j)

the head norms over the ``dh`` columns, ``R_p`` over the whole head
(rotate-half: column ``i`` with ``i + dh / 2``, by ``p theta^(-2i /
dh)``; ``rope`` is the latent decoder's reference's, given the whole
head).

The dense feed-forward (the leading layers): ``ffn(w) = W_d (silu(W_g w)
* W_u w)`` (``dense``, the latent decoder's reference's). The expert
feed-forward, with the router's correction bias
``b`` (in the layer's state; no gradient reaches it):

    s = sigmoid(W_r w)     S = the top_k largest of s + b
    c_e = scale * s_e / (sum_{e' in S} s_e' + router_eps)
    ffn(w) = sum_{e in S, e held here} c_e W_d,e (silu(W_g,e w) * W_u,e w)

The model: ``h = layers(E[t_0..t_{L-1}])``, ``logits = RMSNorm_f(h)
E^T`` with ``E`` the embedding itself (the head is tied: one array, read
twice), ``loss = mean_i nll(logits_i, t_{i+1})``. What experts held
elsewhere would add is left out, as in the program.

Departures: none from the equations above. Attention is computed for
``QUERY_ROWS`` query rows at a time and the experts one after another,
so that loss and gradients fit at the timed size.

What ``correct`` compares on the chip (``runners/train_fit_decoder_
tokens.py``), all of it on the seeded initial parameters at the timed
size, is what ``reference/nemotron_h.py`` lists for its cell: the first
loss, the logits of ``net.output`` entry by entry, every layer's
train-mode output on the program's own input to it by its kind
(``KINDS``), and the state the first dispatch leaves (Adam's first
moment and the parameters' change, leaf by leaf). The embedding's matrix
holds the sum of two users' gradients, the gather's and the head's
product's, and this module's gradient of it is that sum by construction
(one array, read twice): a program that kept a copy for the head, or
dropped a user, reads the other user's share of the gradient as error,
far past ``GRAD_RTOL`` (``grad_rel_err_tied``).

Tolerances (readings on the v5e in PERF.md, Findings PR 39):
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.glm4_moe_lite import _gated, dense, rope
from benchmark.reference.sdar_moe import _lowered, _mm, adam, rms_norm

__all__ = ["KINDS", "adam", "attention", "block", "dense", "experts",
           "kind_of", "logits", "loss", "rope", "routing", "short_conv",
           "spread"]

TOP_K = 4
SCALE = 1.0
ROUTER_EPS = 1e-6
EPS = 1e-5
HEAD_DIM = 64
ROPE_THETA = 1e6
QUERY_ROWS = 512

# The kinds of layer ``block`` computes: the operator (a short
# convolution or attention) and the feed-forward (dense or experts).
KINDS = ("conv_dense", "attn_dense", "conv_experts", "attn_experts")

# The choice is made among s + b, s = sigmoid(logit): the hybrid
# decoder's gap (``reference/nemotron_h.py``), for the same router over
# rows of about the same size.
TIE_GAP = 0.0125

# A layer's output against ``block`` on the program's own input to it,
# entry by entry, for the rows not near a tie: what ``|system -
# reference|`` exceeds one unit in the last place of the stream's dtype
# by, relative to the largest entry of ``|reference - input|``
# (``train_fit_causal_tokens._beyond_rounding`` says why a unit is
# allowed). Readings on the v5e over 14 seeds (PERF.md Findings PR 39),
# the program in bf16 / this module against itself with every forward
# product's operands rounded to float8_e4m3fn, the nearest precision
# below bf16, left in the stream's dtype, which has to fail: the dense
# operator layer 0.0081 to 0.0091 / 0.069 to 0.080, the attention expert
# layer 0.0033 to 0.0060 / 0.034 to 0.123, an operator expert layer
# 0.0085 to 0.0112 / 0.044 to 0.185. The rows near a tie (14 to 16 in a
# hundred of an expert layer) read 0.14 to 0.25 against this and twice
# the largest entry one pair could add, 0.35 to 0.61.
BLOCK_RTOL = 0.02
# the logits of net.output, end to end, entry by entry, relative to the
# largest |reference logit|. LOGITS_RTOL for the rows that neither were
# near a tie in any layer nor had such a row mixed into them by a later
# operator (``spread``; 7 to 9 in a hundred, 600 to 700 rows of 8,192):
# bf16 reads 0.0123 to 0.0149 over 11 seeds, float8_e4m3fn 0.114 to
# 0.147. Without ``spread`` the rows whose own routing was clear (53 in
# a hundred) read 0.058 to 0.069 over 3 seeds, half of what all rows
# read: a filter of 3 positions hands a neighbour's other expert on at
# full weight, where attention averages it away. All rows read 0.094 to
# 0.125 and are held to what only a gross fault passes.
LOGITS_RTOL = 0.03
LOGITS_RTOL_ALL_ROWS = 0.35
# the first step's loss against ``loss``, relative: 3.3e-6 to 2.5e-5
# over the seeds. It cannot tell precisions apart (the logits do that);
# it catches a wrong label, weight or divisor.
LOSS_RTOL = 5e-4
# Adam's first moment after the first dispatch, a leaf at a time:
# |system - reference|_2 / |reference|_2, worst leaf. GRAD_RTOL, for the
# leaves outside the routed experts' matrices and the router's: bf16
# reads 0.155 to 0.172 over 14 seeds, and the worst leaf is always an
# expert layer's norm weight, which rows that took another expert move
# (here nothing but routed pairs passes through that norm: no shared
# expert dilutes them, so it reads four times the latent decoder's
# 0.045); every matrix outside the experts reads at most 0.041, the
# tied embedding 0.030 to 0.032 (``grad_rel_err_tied``; a dropped user
# would read 0.3 and more). This module's own gradient through an fp8
# forward reads 0.487 to 0.507 on its worst leaf (0.15 on the matrices
# it moves least). The routed matrices by their median expert's slice
# (``reference/sdar_moe.py`` says why): 0.152 to 0.174 against
# GRAD_RTOL_EXPERTS, fp8 0.399 to 0.417 on the leaf it moves least. The
# router's own matrix has no expert's slice to take and reads 0.21 to
# 0.25: read, not held.
GRAD_RTOL = 0.28
GRAD_RTOL_EXPERTS = 0.26
EXPERT_LEAVES = ("Wg", "Wu", "Wd")      # of a layer that has a router
ROUTER_LEAVES = ("Wr",)
# the parameters' change over the first dispatch, likewise; 1 is what a
# state left unchanged reads. It reads 0.301 to 0.346, and the worst
# leaf is always an expert layer's norm weight: its gradient carries
# the 0.16 above, and its values are 1, so eight steps of 1e-7 move an
# entry by 8e-7, under seven of the 1.2e-7 between its float32
# neighbours (``reference/nemotron_h.py``). The matrices of entries near
# 0.02 read at most 0.108, the routed ones 0.232 to 0.255 by their
# median slice, the router's 0.34 to 0.37.
UPDATE_RTOL = 0.6


def kind_of(p):
    """One of ``KINDS`` from a layer's parameter names, None for a layer
    that is no block (the embedding, the final norm)."""
    if "W_in" not in p and "Wq" not in p:
        return None
    return (("conv" if "W_in" in p else "attn") + "_"
            + ("experts" if "Wr" in p else "dense"))


def short_conv(p, x, *, eps=EPS, dtype=None):
    """``x`` [L, d] -> ``x + W_out (C * conv(B * x~))`` [L, d]."""
    length, d = x.shape
    x = x.astype(jnp.float32)
    k = p["conv_w"].shape[1]
    bcx = _mm(rms_norm(x, p["op_ln_g"], eps), p["W_in"], dtype)
    b, c, xt = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    g = jnp.concatenate([jnp.zeros((k - 1, d), jnp.float32), b * xt])
    s = sum(p["conv_w"][:, i] * g[i:i + length] for i in range(k))
    return x + _mm(c * s, p["W_out"], dtype)


def attention(p, x, *, head_dim=HEAD_DIM, theta=ROPE_THETA, eps=EPS,
              dtype=None):
    """``x`` [L, d] -> ``x + W_o attention`` [L, d]."""
    t = x.shape[0]
    x = x.astype(jnp.float32)
    u = rms_norm(x, p["attn_ln_g"], eps)
    q = _mm(u, p["Wq"], dtype).reshape(t, -1, head_dim)
    k = _mm(u, p["Wk"], dtype).reshape(t, -1, head_dim)
    v = _mm(u, p["Wv"], dtype).reshape(t, -1, head_dim)
    q = rope(rms_norm(q, p["q_norm_g"], eps), theta)
    k = rope(rms_norm(k, p["k_norm_g"], eps), theta)
    group = q.shape[1] // k.shape[1]
    q = _lowered(q, dtype)
    k = _lowered(jnp.repeat(k, group, axis=1), dtype)
    v = _lowered(jnp.repeat(v, group, axis=1), dtype)
    cols = jnp.arange(t)
    step = math.gcd(t, QUERY_ROWS)

    def some_rows(start):
        rows = start + jnp.arange(step)
        s = jnp.einsum("ihd,jhd->hij", jax.lax.dynamic_slice_in_dim(
            q, start, step), k) / math.sqrt(head_dim)
        s = jnp.where((cols[None, :] <= rows[:, None])[None], s, -jnp.inf)
        return jnp.einsum("hij,jhd->ihd",
                          _lowered(jax.nn.softmax(s, axis=-1), dtype), v)

    # a gradient keeps no group's scores: it makes them again
    o = jax.lax.map(jax.checkpoint(some_rows), jnp.arange(0, t, step))
    return x + _mm(o.reshape(t, -1), p["Wo"], dtype)


def routing(w, p, bias, top_k, first_expert, scale, router_eps):
    """The weight of every held expert for every row, ``c`` [R, held]
    (0 where the expert is not among the row's ``top_k``), the row's
    largest weight [R], and ``risk`` [R]: an expert held here is within
    TIE_GAP of changing sides, a chosen one of the (k+1)-th of ``s +
    b`` or a passed-over one of the k-th."""
    s = jax.nn.sigmoid(jnp.dot(w, p["Wr"],
                               precision=jax.lax.Precision.HIGHEST))
    sel = s + bias
    order = jnp.argsort(-sel, axis=-1)
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], order[:, :top_k]].set(True)
    c = jnp.where(chosen, s, 0.0)
    c = scale * c / (jnp.sum(c, axis=-1, keepdims=True) + router_eps)
    held = p["Wu"].shape[0]
    ranked = jnp.take_along_axis(sel, order, axis=-1)
    to_other_side = jnp.where(chosen, sel - ranked[:, top_k:top_k + 1],
                              ranked[:, top_k - 1:top_k] - sel)
    risk = jnp.any(to_other_side[:, first_expert:first_expert + held]
                   < TIE_GAP, axis=-1)
    return c[:, first_expert:first_expert + held], jnp.max(c, axis=-1), risk


def experts(p, state, a, *, top_k=TOP_K, first_expert=0, scale=SCALE,
            router_eps=ROUTER_EPS, eps=EPS, dtype=None):
    """The experts on ``a`` [R, d] -> (y [R, d], risk [R], the largest
    entry that one (row, held expert) pair could add)."""
    w = rms_norm(a, p["ln_g"], eps)
    c, largest, risk = routing(w, p, state["router_bias"], top_k,
                               first_expert, scale, router_eps)

    def one_expert(carry, e):
        y, most = carry
        wg, wu, wd, ce = e
        out = _gated(w, wg, wu, wd, dtype)
        most = jnp.maximum(most, jnp.max(jnp.abs(largest[:, None] * out)))
        return (y + ce[:, None] * out, most), None

    (y, most), _ = jax.lax.scan(
        one_expert, (a, jnp.zeros((), jnp.float32)),
        (p["Wg"], p["Wu"], p["Wd"], c.T))
    return y, risk, most


def block(kind, p, state, x, *, top_k=TOP_K, first_expert=0, scale=SCALE,
          router_eps=ROUTER_EPS, head_dim=HEAD_DIM, theta=ROPE_THETA,
          eps=EPS, dtype=None):
    """One layer of ``kind`` (one of ``KINDS``) on ``x`` [L, d] -> (y
    [L, d], risk [L], the largest entry of one pair), the last two as
    ``experts`` gives them and nothing for a dense layer."""
    operator, ffn = kind.split("_")
    a = (short_conv(p, x, eps=eps, dtype=dtype) if operator == "conv"
         else attention(p, x, head_dim=head_dim, theta=theta, eps=eps,
                        dtype=dtype))
    if ffn == "dense":
        return (dense(p, a, eps=eps, dtype=dtype),
                jnp.zeros(x.shape[:1], bool), jnp.zeros((), jnp.float32))
    return experts(p, state, a, top_k=top_k, first_expert=first_expert,
                   scale=scale, router_eps=router_eps, eps=eps, dtype=dtype)


def _names(params):
    """The net's layers that hold parameters, named ``layer_<i>``, in
    order."""
    return sorted(params, key=lambda k: int(k.rsplit("_", 1)[1]))


def spread(p, risk):
    """``risk`` [L] as the operator of the layer ``p`` hands it on: a
    short convolution mixes a row with the ``K - 1`` before it at full
    weight, so a row whose neighbour took another expert in an earlier
    layer carries that difference as its own. Attention is left as the
    other references leave it: it averages over the whole history, and
    what one earlier row's other expert moves of a later row's output is
    held by ``LOGITS_RTOL_ALL_ROWS`` (in this cell's share the one
    attention layer precedes every expert layer and has nothing to
    spread)."""
    if "conv_w" not in p:
        return risk
    for _ in range(p["conv_w"].shape[1] - 1):
        risk = risk | jnp.concatenate([jnp.zeros((1,), bool), risk[:-1]])
    return risk


def logits_and_risk(params, state, ids, **how):
    """ids int [b, L] -> logits [b, L, vocab], and for each row whether
    its routing, or that of a row its operators mixed into it
    (``spread``), was near a tie in any layer. The embedding is the
    first layer, the final norm the last that holds parameters; the head
    is the embedding's transpose."""
    names = _names(params)
    eps, dtype = how.get("eps", EPS), how.get("dtype")
    emb = params[names[0]]["W"]

    def one(row):
        x = emb[row]
        risk = jnp.zeros(row.shape, bool)
        for name in names[1:-1]:
            layer = jax.checkpoint(functools.partial(
                block, kind_of(params[name]), **how))
            x, here, _ = layer(params[name], state.get(name, {}), x)
            risk = spread(params[name], risk) | here
        return _mm(rms_norm(x, params[names[-1]]["g"], eps), emb.T,
                   dtype), risk

    z, risk = zip(*(one(row) for row in ids))
    return jnp.stack(z), jnp.stack(risk)


def logits(params, state, ids, **how):
    return logits_and_risk(params, state, ids, **how)[0]


def loss(params, state, ids, labels, weights=None, with_logits=False,
         **how):
    """The objective; ``with_logits``: (loss, (logits, risk)), the shape
    ``jax.value_and_grad(..., has_aux=True)`` takes."""
    z, risk = logits_and_risk(params, state, ids, **how)
    nll = (jax.nn.logsumexp(z, axis=-1)
           - jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0])
    if weights is not None:
        nll = nll * weights
    value = jnp.sum(nll) / labels.size
    return (value, (z, risk)) if with_logits else value
