"""GLM-4.7-Flash (``model_type`` glm4_moe_lite), one chip's share, in
plain float32 ``jax.numpy``: none of the program's layer code, reading
the net's parameter tree and state. Source:
https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json; the
cut and what is assumed: ``benchmark/configs/glm4_7_flash.json``.

Every layer is ``a = x + attention(RMSNorm(x))`` then ``a +
ffn(RMSNorm(a))`` (statistics in float32, eps 1e-5, no bias anywhere).
Which feed-forward a layer has is read from its parameters' names
(``kind_of``).

Latent attention, ``H`` heads, positions ``p = 0..L-1``, ``u =
RMSNorm(x)``:

    c_q  = RMSNorm(W_dq u)                    q_h = W_uq,h c_q = [q_nope,h | q_rope,h]
    [c_kv | k_rope] = W_dkv u                 c_kv <- RMSNorm(c_kv)
    [k_nope,h | v_h] = W_ukv,h c_kv
    q_h = [q_nope,h | R_p q_rope,h]           k_h = [k_nope,h | R_p k_rope]
    s_h,ij = q_h,i . k_h,j / sqrt(nope + rope) for j <= i, -inf elsewhere
    attention = W_o concat_h(sum_j softmax(s_h)_ij v_h,j)

``R_p`` rotates the rope columns alone, column ``i`` with ``i + rope /
2``, by ``p theta^(-2i / rope)``; one ``k_rope`` serves all heads.

``D``, the leading dense layer: ``ffn(w) = W_d (silu(W_g w) * W_u w)``.

``E``, an expert layer, with the router's correction bias ``b`` (in the
layer's state; no gradient reaches it):

    s = sigmoid(W_r w)     S = the top_k largest of s + b
    c_e = scale * s_e / (sum_{e' in S} s_e' + 1e-20)
    ffn(w) = sum_{e in S, e held here} c_e W_d,e (silu(W_g,e w) * W_u,e w)
           + W_d,s (silu(W_g,s w) * W_u,s w)

The model: ``h = layers(Emb(t_0..t_{L-1}))``, ``logits = W_head
RMSNorm_f(h)``, ``L_main = mean_i nll(logits_i, t_{i+1})``. The
multi-token-prediction module, with the model's own ``Emb`` and
``W_head``:

    h'_i = W_eh [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)]      (h before RMSNorm_f)
    g = one more expert layer on h', positions 0..L-1
    logits' = W_head RMSNorm_s(g)         L_mtp = mean_i nll(logits'_i, t_{i+2})

``loss = L_main + mtp_weight * L_mtp``. What experts held elsewhere
would add is left out, as in the program.

Departures: none from the equations above. Attention is computed for
``QUERY_ROWS`` query rows at a time and the experts one after another,
so that loss and gradients fit at the timed size.

What ``correct`` compares on the chip (``runners/train_fit_mtp_tokens.
py``), all of it on the seeded initial parameters at the timed size, is
what ``reference/nemotron_h.py`` lists for its cell, and besides: both
losses apart, the module as a block of its own (on the program's own
``h``), and the shared leaves. The embedding's matrix and the head's
hold the sum of two users' gradients (the model's and the module's), and
this module's gradient of them is that sum by construction (one array,
read twice): a program that kept a copy for the module, or dropped a
user, reads the other user's share of the gradient as error, 0.3 to 1 of
the leaf's norm, far past ``GRAD_RTOL``.

Tolerances (readings on the v5e in PERF.md, Findings PR 37):
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.nemotron_h import routing
from benchmark.reference.sdar_moe import _lowered, _mm, adam, rms_norm

__all__ = ["adam", "block", "dense", "experts", "kind_of",
           "latent_attention", "logits", "loss", "module", "rope",
           "routing"]

TOP_K = 4
SCALE = 1.8
EPS = 1e-5
HEADS = 20
NOPE = 192
ROPE_THETA = 1e6
MTP_WEIGHT = 0.3
QUERY_ROWS = 512

# A layer's output against ``block`` on the program's own input to it,
# entry by entry, for the rows not near a tie (``routing``'s TIE_GAP, the
# hybrid decoder's: the same router over rows of the same size): what
# ``|system - reference|`` exceeds one unit in the last place of the
# stream's dtype by, relative to the largest entry of ``|reference -
# input|`` (``train_fit_causal_tokens._beyond_rounding`` says why a unit
# is allowed). Readings on the v5e over 7 seeds (PERF.md Findings PR
# 37), the program in bf16 / this module against itself with every
# forward product's operands rounded to float8_e4m3fn, the nearest
# precision below bf16, left in the stream's dtype, which has to fail:
# the dense layer 0.0044 to 0.0056 / 0.042 to 0.049, an expert layer
# 0.0053 to 0.0093 / 0.043 to 0.048, the module (``eh_proj`` and its
# expert layer, on the program's last hidden rows) 0.0072 to 0.0087 /
# 0.10 to 0.28. The rows near a tie (13 to 16 in a hundred of an expert
# layer) read 0.22 to 0.39 against this and twice the largest entry one
# pair could add, 0.73 to 0.98.
BLOCK_RTOL = 0.02
# the logits of net.output and the module's, end to end, entry by entry,
# relative to the largest |reference logit|. LOGITS_RTOL for the rows
# whose routing was near a tie in no layer (52 to 55 in a hundred of the
# model's, 45 to 46 of the module's, which pass one layer more): bf16
# reads 0.0072 to 0.0090 (the model's) and 0.0060 to 0.0104 (the
# module's) over 7 seeds, float8_e4m3fn 0.109 to 0.155 and 0.107 to
# 0.148. A row that took another expert somewhere carries the
# difference to its logits, and in a causal model to the rows after it:
# all rows read 0.120 to 0.167 over 9 seeds (the hybrid decoder's read
# 0.05 to 0.08
# with a shared expert twice the routed width; here a routed pair is a
# larger part of what a layer adds) and are held to what only a gross
# fault passes.
LOGITS_RTOL = 0.03
LOGITS_RTOL_ALL_ROWS = 0.35
# the first step's loss and its two parts against ``loss``, each
# relative: 7e-8 to 5.1e-5 over the seeds. They cannot tell precisions
# apart (the logits do that); they catch a wrong label, a wrong weight
# of the second loss, or a module that reads the token it should
# predict.
LOSS_RTOL = 5e-4
# Adam's first moment after the first dispatch, a leaf at a time:
# |system - reference|_2 / |reference|_2, worst leaf. GRAD_RTOL, for the
# leaves outside the routed experts' matrices and the router's: bf16
# reads 0.041 to 0.045 over 7 seeds (the worst leaf is always an expert
# layer's norm weight, which rows that took another expert move; the
# matrices read at most 0.027), and this module's own gradient through
# an fp8 forward 0.49 to 0.53 (0.041 on the leaf it moves least). The
# embedding's and the head's matrices, the leaves with two users, read
# 0.017 to 0.025 (``grad_rel_err_shared``); a dropped user would read
# 0.3 and more. The routed matrices by their median expert's slice
# (``reference/sdar_moe.py`` says why): 0.111 to 0.126 against
# GRAD_RTOL_EXPERTS (fp8 reads 0.08 to 0.35 on them, leaf by leaf, and
# is held by the other leaves). The router's own matrix has
# no expert's slice to take and reads 0.10 to 0.19: read, not held.
GRAD_RTOL = 0.14
GRAD_RTOL_EXPERTS = 0.22
EXPERT_LEAVES = ("Wg", "Wu", "Wd")
ROUTER_LEAVES = ("Wr",)
# the parameters' change over the first dispatch, likewise; 1 is what a
# state left unchanged reads. It reads 0.127 to 0.145, and the worst
# leaf is always an expert layer's norm weight, whose values are 1:
# eight steps of 1e-7 move such an entry by 8e-7, under seven of the
# 1.2e-7 between its float32 neighbours (``reference/nemotron_h.py``).
# The matrices of entries near 0.02 read 0.025 to 0.060, the routed
# ones 0.18 to 0.20 by their median slice, the router's 0.23 to 0.31.
UPDATE_RTOL = 0.5


def kind_of(p) -> str:
    """``D`` (attention and a dense MLP), ``E`` (attention and experts)
    or ``M`` (the output layer: final norm, head and the module) from a
    layer's parameter names."""
    return "M" if "W_eh" in p else "E" if "Wr" in p else "D"


def rope(x, theta):
    """x [L, h, r]: rotate-half over all ``r`` columns, row ``i`` at
    position ``i``."""
    length, _, r = x.shape
    freq = theta ** (-jnp.arange(0, r, 2) / r)
    angle = (jnp.arange(length)[:, None] * freq[None, :]).astype(jnp.float32)
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1)[:, None, :]
    half = jnp.concatenate([-x[..., r // 2:], x[..., :r // 2]], -1)
    return x * cos + half * sin


def heads_of(p, x, *, heads=HEADS, nope=NOPE, theta=ROPE_THETA, eps=EPS,
             dtype=None):
    """q, k [L, H, nope + rope] and v [L, H, v] of ``x`` [L, d], every
    head's key written out."""
    length = x.shape[0]
    rank = p["kv_ln_g"].shape[0]
    u = rms_norm(x, p["attn_ln_g"], eps)
    c_q = rms_norm(_mm(u, p["W_dq"], dtype), p["q_ln_g"], eps)
    q = _mm(c_q, p["W_uq"], dtype).reshape(length, heads, -1)
    down = _mm(u, p["W_dkv"], dtype)
    c_kv = rms_norm(down[:, :rank], p["kv_ln_g"], eps)
    kv = _mm(c_kv, p["W_ukv"], dtype).reshape(length, heads, -1)
    k_rope = rope(down[:, None, rank:], theta)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_rope, (length, heads, k_rope.shape[-1]))], -1)
    return q, k, kv[..., nope:]


def latent_attention(p, x, *, dtype=None, **how):
    """``x`` [L, d] -> ``x + W_o attention`` [L, d]."""
    t = x.shape[0]
    x = x.astype(jnp.float32)
    q, k, v = (_lowered(a, dtype) for a in heads_of(p, x, dtype=dtype, **how))
    cols = jnp.arange(t)
    step = math.gcd(t, QUERY_ROWS)

    def some_rows(start):
        rows = start + jnp.arange(step)
        s = jnp.einsum("ihd,jhd->hij", jax.lax.dynamic_slice_in_dim(
            q, start, step), k) / math.sqrt(q.shape[-1])
        s = jnp.where((cols[None, :] <= rows[:, None])[None], s, -jnp.inf)
        return jnp.einsum("hij,jhd->ihd",
                          _lowered(jax.nn.softmax(s, axis=-1), dtype), v)

    # a gradient keeps no group's scores: it makes them again
    o = jax.lax.map(jax.checkpoint(some_rows), jnp.arange(0, t, step))
    return x + _mm(o.reshape(t, -1), p["Wo"], dtype)


def _gated(w, wg, wu, wd, dtype):
    return _mm(jax.nn.silu(_mm(w, wg, dtype)) * _mm(w, wu, dtype), wd, dtype)


def dense(p, a, *, eps=EPS, dtype=None):
    """The dense MLP on ``a`` [R, d], residual included."""
    return a + _gated(rms_norm(a, p["ln_g"], eps), p["Wg"], p["Wu"], p["Wd"],
                      dtype)


def experts(p, state, a, *, top_k=TOP_K, first_expert=0, scale=SCALE,
            eps=EPS, dtype=None):
    """The experts on ``a`` [R, d] -> (y [R, d], risk [R], the largest
    entry that one (row, held expert) pair could add)."""
    w = rms_norm(a, p["ln_g"], eps)
    c, largest, risk = routing(w, p, state["router_bias"], top_k,
                               first_expert, scale)

    def one_expert(carry, e):
        y, most = carry
        wg, wu, wd, ce = e
        out = _gated(w, wg, wu, wd, dtype)
        most = jnp.maximum(most, jnp.max(jnp.abs(largest[:, None] * out)))
        return (y + ce[:, None] * out, most), None

    shared = _gated(w, p["Ws_g"], p["Ws_u"], p["Ws_d"], dtype)
    (y, most), _ = jax.lax.scan(
        one_expert, (a + shared, jnp.zeros((), jnp.float32)),
        (p["Wg"], p["Wu"], p["Wd"], c.T))
    return y, risk, most


def block(kind, p, state, x, *, top_k=TOP_K, first_expert=0, scale=SCALE,
          heads=HEADS, nope=NOPE, theta=ROPE_THETA, eps=EPS, dtype=None):
    """One layer of ``kind`` ``D`` or ``E`` (an ``M`` layer's own expert
    layer is an ``E``) on ``x`` [L, d] -> (y [L, d], risk [L], the
    largest entry of one pair), the last two as ``experts`` gives them
    and nothing for a dense layer."""
    a = latent_attention(p, x, heads=heads, nope=nope, theta=theta, eps=eps,
                         dtype=dtype)
    if kind == "D":
        return (dense(p, a, eps=eps, dtype=dtype),
                jnp.zeros(x.shape[:1], bool), jnp.zeros((), jnp.float32))
    return experts(p, state, a, top_k=top_k, first_expert=first_expert,
                   scale=scale, eps=eps, dtype=dtype)


def module(p, state, emb, h, next_ids, **how):
    """The prediction module of the output layer ``p`` on the model's
    last hidden rows ``h`` [L, d] and the ids one ahead [L]: (its expert
    layer's input h', that layer's output g, risk, the largest entry of
    one pair)."""
    eps, dtype = how.get("eps", EPS), how.get("dtype")
    both = jnp.concatenate([rms_norm(emb[next_ids], p["enorm_g"], eps),
                            rms_norm(h, p["hnorm_g"], eps)], axis=-1)
    given = _mm(both, p["W_eh"], dtype)
    return (given, *block("E", p, state, given, **how))


def _names(params):
    """The net's layers, named ``layer_<i>``, in order."""
    return sorted(params, key=lambda k: int(k.rsplit("_", 1)[1]))


def both_logits(params, state, ids, next_ids, **how):
    """ids, next_ids int [b, L] -> the model's logits [b, L, vocab] with
    ``risk`` [b, L] (a row's routing was near a tie in some layer), and
    the module's with theirs (the model's or the module's own)."""
    names = _names(params)
    eps, dtype = how.get("eps", EPS), how.get("dtype")
    emb, out = params[names[0]]["W"], params[names[-1]]
    s_out = state.get(names[-1], {})

    def one(row, ahead):
        x = emb[row]
        risk = jnp.zeros(row.shape, bool)
        for name in names[1:-1]:
            layer = jax.checkpoint(functools.partial(
                block, kind_of(params[name]), **how))
            x, here, _ = layer(params[name], state.get(name, {}), x)
            risk |= here
        z = _mm(rms_norm(x, out["norm_g"], eps), out["W"], dtype)
        _, g, here, _ = jax.checkpoint(functools.partial(module, **how))(
            out, s_out, emb, x, ahead)
        z_ahead = _mm(rms_norm(g, out["mtp_norm_g"], eps), out["W"], dtype)
        return z, risk, z_ahead, risk | here

    return tuple(jnp.stack(a) for a in zip(*(
        one(row, ahead) for row, ahead in zip(ids, next_ids))))


def logits(params, state, ids, **how):
    """What ``net.output`` answers: the model's logits alone."""
    return both_logits(params, state, ids, ids, **how)[0]


def _nll(z, labels, weights):
    nll = (jax.nn.logsumexp(z, axis=-1)
           - jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0])
    if weights is not None:
        nll = nll * weights
    return jnp.sum(nll) / labels.size


def loss(params, state, ids, labels, weights=None, with_logits=False,
         mtp_weight=MTP_WEIGHT, **how):
    """The objective, labels [b, 2, L]; ``with_logits``: (loss, (both
    losses, the model's logits, risk, the module's, risk)), the shape
    ``jax.value_and_grad(..., has_aux=True)`` takes."""
    z, risk, z_ahead, risk_ahead = both_logits(params, state, ids,
                                               labels[:, 0], **how)
    parts = jnp.stack([
        _nll(z, labels[:, 0], None if weights is None else weights[:, 0]),
        _nll(z_ahead, labels[:, 1],
             None if weights is None else weights[:, 1])])
    value = parts[0] + mtp_weight * parts[1]
    return ((value, (parts, z, risk, z_ahead, risk_ahead)) if with_logits
            else value)
