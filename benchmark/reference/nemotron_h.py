"""NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type`` nemotron_h), one
chip's share, in plain float32 ``jax.numpy``: none of the program's
layer code, reading the net's parameter tree and state. Source:
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/
main/config.json; the cut and what is assumed:
``benchmark/configs/nemotron3_nano_30b_a3b.json``.

Every layer is one mixer: ``y = x + mixer(u)``, ``u = RMSNorm(x)``
(statistics in float32, eps 1e-5), no bias in any projection,
``relu2(a) = max(a, 0)^2``. Which mixer a layer has is read from its
parameters' names (``kind_of``).

``M``, Mamba-2, ``H`` heads of ``P``, ``G`` groups, state ``N``,
``g(h) = h div (H / G)``, convolution of ``K`` positions:

    [z | xBC | dt] = W_in u
    xBC_t,c <- silu(b_c + sum_k w_c,k xBC_{t-K+1+k, c})      zeros before 0
    [x | B | C] = xBC          dt = softplus(dt + dt_bias)    A = -exp(A_log)
    S_t,h = exp(dt_t,h A_h) S_{t-1,h} + dt_t,h x_t,h (x) B_t,g(h)    S_{-1} = 0
    y_t,h = S_t,h C_t,g(h) + D_h x_t,h
    mixer = W_out GroupRMSNorm(y_t * silu(z_t))      G groups, gate first

``*``, attention: ``q = W_q u``, ``k = W_k u``, ``v = W_v u``, ``s_ij =
q_h,i . k_g,j / sqrt(head_dim)`` for ``j <= i`` and -inf elsewhere,
softmax, ``mixer = W_o concat_h(...)``. No rotation, no positional term.

``E``, experts, with the router's correction bias ``b`` (in the layer's
state; no gradient reaches it):

    s = sigmoid(W_r u)     S = the top_k largest of s + b
    c_e = scale * s_e / (sum_{e' in S} s_e' + 1e-20)
    mixer = sum_{e in S, e held here} c_e W_down,e relu2(W_up,e u)
          + W_down,s relu2(W_up,s u)

The model: a token embedding (a gather), the layers, a final RMSNorm, an
untied head over every row. ``loss`` = the mean over ``b L`` rows of
``-log softmax(logits_i)[labels_i]``, labels the next token. What
experts held elsewhere would add is left out, as in the program.

Departures: none from the equations above. The recurrence runs one
position at a time as written (a ``lax.scan``, checkpointed in blocks of
``SCAN_BLOCK`` positions so that a gradient fits at the timed size); the
program computes it in chunks of 128. Attention is computed for
``QUERY_ROWS`` query rows at a time and the experts one after another.

What ``correct`` compares on the chip
(``runners/train_fit_causal_tokens.py``), all of it on the seeded
initial parameters at the timed size, is what
``reference/sdar_moe.py`` lists for its cell: the first loss, the logits
of ``net.output`` entry by entry, every layer's train-mode output on the
program's own input to it (beyond one unit in the last place of the
stream's dtype: ``BLOCK_RTOL``), and the state the first dispatch leaves
(Adam's first moment and the parameters' change, leaf by leaf). The rows
of an ``E`` layer whose routing is within ``TIE_GAP`` of a tie are held
as that module holds them. A Mamba layer's state is not compared apart:
it is held through ``y``, to the limit of any activation. The decays
need float32: ``exp(dt A)`` lies in (0, 1] and a chunk multiplies up to
128 of them, which the program does as ``exp`` of a float32 sum; in bf16
the sum of 128 terms of about 0.05 carries three digits and the decay
``exp(-6.4)`` would be off by 5%, as much as fp8 moves a product.

Tolerances (readings on the v5e in PERF.md, Findings PR 33):
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.sdar_moe import _lowered, _mm, adam, rms_norm

__all__ = ["adam", "attention", "block", "experts", "kind_of", "logits",
           "loss", "mamba", "routing"]

TOP_K = 6
SCALE = 2.5
EPS = 1e-5
GROUPS = 8
HEAD_DIM = 128
QUERY_ROWS = 512
SCAN_BLOCK = 64

# The choice is made among s + b, s = sigmoid(logit). A logit is u . W_r
# [:, e] over 2,688 entries of about 1 x 0.02; the program's differ from
# this module's by what bf16 leaves in u, at most 0.010 in the SDAR cell
# (PERF.md Findings PR 31), and a sigmoid's slope is at most 1/4. 0.0125
# is that module's 0.05 of a logit in the score's own unit.
TIE_GAP = 0.0125

# A layer's output against ``block`` on the program's own input to it,
# entry by entry, for the rows not near a tie: what ``|system -
# reference|`` exceeds one unit in the last place of the stream's dtype
# by, relative to the largest entry of ``|reference - input|`` (what the
# layer adds to the stream); the runner's ``_beyond_rounding`` says why
# a unit is allowed (the stream is bf16 and ten times what a layer adds:
# without the allowance bf16 reads 0.020 to 0.048 and fp8 0.046 to
# 0.118, and no limit stands between them for a Mamba layer). Readings
# on the v5e over 11 seeds (PERF.md Findings PR 33), the program in bf16
# / this module against itself with every forward product's operands
# rounded to float8_e4m3fn, the nearest precision below bf16, left in
# the stream's dtype as a program would leave it, which has to fail:
# Mamba 0.005 to 0.011 / 0.042 to 0.048, experts 0.007 to 0.009 / 0.074
# to 0.088, attention 0.006 to 0.013 / 0.085 to 0.12. The rows near a
# tie (13 to 15 in a hundred of an expert layer) read 0.17 to 0.30
# against this and twice the largest entry one pair could add, 0.60 to
# 0.81.
BLOCK_RTOL = 0.024
# the logits of net.output, end to end, entry by entry, relative to the
# largest |reference logit|. LOGITS_RTOL for the rows whose routing was
# near a tie in no layer (54 to 60 in a hundred): bf16 reads 0.0076 to
# 0.0105 over 15 seeds, float8_e4m3fn 0.034 to 0.047. A row that took
# another expert somewhere carries the difference to its logits, and in
# a causal model to the rows after it: all rows read 0.050 to 0.082 and
# are held to what only a gross fault passes.
LOGITS_RTOL = 0.019
LOGITS_RTOL_ALL_ROWS = 0.2
# the first training step's loss against ``loss`` end to end, relative:
# 7e-6 to 3e-5 over the seeds. It cannot tell precisions apart (the
# logits do that); it catches a wrong label, weight or divisor.
LOSS_RTOL = 5e-4
# Adam's first moment after the first dispatch, a leaf at a time:
# |system - reference|_2 / |reference|_2, worst leaf. GRAD_RTOL, for the
# leaves outside the routed experts' matrices and the router's: bf16
# reads 0.022 to 0.041 over 15 seeds (the worst leaf is one of 64
# entries, a mixer's A_log or dt_bias, or an expert layer's norm
# weight, which rows that took another expert move), and this module's
# own gradient through an fp8 forward 0.42 to 0.44 (0.017 on the leaf it
# moves least). The routed matrices are sums over routed pairs
# (``reference/sdar_moe.py`` says why they are read by their median
# expert's slice): 0.085 to 0.11 against GRAD_RTOL_EXPERTS. The router's
# own matrix has no expert's slice to take and reads 0.08 to 0.18: read,
# not held.
GRAD_RTOL = 0.13
GRAD_RTOL_EXPERTS = 0.3
EXPERT_LEAVES = ("Wu", "Wd")
ROUTER_LEAVES = ("Wr",)
# the parameters' change over the first dispatch, likewise; 1 is what a
# state left unchanged reads. It reads 0.08 to 0.26, and the worst leaf
# is always one whose values are 1 or more (a mixer's D, a norm's
# weight, A_log): eight steps of 1e-7 move such an entry by 8e-7, under
# seven of the 1.2e-7 between its float32 neighbours, so every step's
# change is rounded to a whole neighbour or none, and Adam divides a
# gradient by its own size, so an entry near 0 takes its sign from the
# rounding. The matrices of entries near 0.02 read 0.02 to 0.04.
UPDATE_RTOL = 0.5


def relu2(a):
    return jnp.square(jnp.maximum(a, 0.0))


def kind_of(p) -> str:
    """``M``, ``*`` or ``E`` from a layer's parameter names."""
    return "M" if "W_in" in p else "*" if "Wq" in p else "E"


def recurrence(x, dt, a, b, c, d):
    """``x`` [L, H, P], ``dt`` [L, H], ``a`` and ``d`` [H], ``b`` and
    ``c`` [L, H, N] (each head given its group's) -> ``y`` [L, H, P], one
    position at a time."""
    length, heads, p = x.shape

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_t) + d[:, None] * x_t

    @jax.checkpoint
    def some(state, block):
        return jax.lax.scan(step, state, block)

    size = math.gcd(length, SCAN_BLOCK)
    blocks = jax.tree_util.tree_map(
        lambda v: v.reshape((length // size, size) + v.shape[1:]),
        (x, dt, b, c))
    _, y = jax.lax.scan(some, jnp.zeros((heads, p, b.shape[-1]),
                                        jnp.float32), blocks)
    return y.reshape(length, heads, p)


def mamba(p, x, *, groups=GROUPS, eps=EPS, dtype=None):
    """The Mamba-2 mixer on ``x`` [L, d] -> [L, d], residual included."""
    length = x.shape[0]
    x = x.astype(jnp.float32)
    heads, inner = p["A_log"].shape[0], p["norm_g"].shape[0]
    gn = (p["conv_w"].shape[0] - inner) // 2
    k = p["conv_w"].shape[1]
    z, xbc, dt = jnp.split(_mm(rms_norm(x, p["ln_g"], eps), p["W_in"], dtype),
                           [inner, 2 * inner + 2 * gn], axis=-1)
    before = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc])
    xbc = jax.nn.silu(p["conv_b"] + sum(
        p["conv_w"][:, i] * before[i:i + length] for i in range(k)))
    xs, b, c = jnp.split(_lowered(xbc, dtype), [inner, inner + gn], axis=-1)
    of_head = jnp.arange(heads) // (heads // groups)
    y = recurrence(
        xs.reshape(length, heads, -1),
        jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
        b.reshape(length, groups, -1)[:, of_head],
        c.reshape(length, groups, -1)[:, of_head], p["D"])
    gated = (y.reshape(length, inner) * jax.nn.silu(z)).reshape(
        length, groups, -1)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + eps)
    return x + _mm(normed.reshape(length, inner) * p["norm_g"], p["W_out"],
                   dtype)


def attention(p, x, *, head_dim=HEAD_DIM, eps=EPS, dtype=None):
    """The attention mixer on ``x`` [L, d] -> [L, d], residual
    included."""
    t = x.shape[0]
    x = x.astype(jnp.float32)
    u = rms_norm(x, p["attn_ln_g"], eps)
    q = _lowered(_mm(u, p["Wq"], dtype).reshape(t, -1, head_dim), dtype)
    k = _mm(u, p["Wk"], dtype).reshape(t, -1, head_dim)
    v = _mm(u, p["Wv"], dtype).reshape(t, -1, head_dim)
    group = q.shape[1] // k.shape[1]
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


def routing(w, p, bias, top_k, first_expert, scale):
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
    c = scale * c / (jnp.sum(c, axis=-1, keepdims=True) + 1e-20)
    held = p["Wu"].shape[0]
    ranked = jnp.take_along_axis(sel, order, axis=-1)
    to_other_side = jnp.where(chosen, sel - ranked[:, top_k:top_k + 1],
                              ranked[:, top_k - 1:top_k] - sel)
    risk = jnp.any(to_other_side[:, first_expert:first_expert + held]
                   < TIE_GAP, axis=-1)
    return c[:, first_expert:first_expert + held], jnp.max(c, axis=-1), risk


def experts(p, state, x, *, top_k=TOP_K, first_expert=0, scale=SCALE,
            eps=EPS, dtype=None):
    """The expert mixer on ``x`` [R, d] -> (y [R, d], risk [R], the
    largest entry that one (row, held expert) pair could add)."""
    x = x.astype(jnp.float32)
    w = rms_norm(x, p["ln_g"], eps)
    c, largest, risk = routing(w, p, state["router_bias"], top_k,
                               first_expert, scale)

    def one_expert(carry, e):
        y, most = carry
        wu, wd, ce = e
        out = _mm(relu2(_mm(w, wu, dtype)), wd, dtype)
        most = jnp.maximum(most, jnp.max(jnp.abs(largest[:, None] * out)))
        return (y + ce[:, None] * out, most), None

    shared = _mm(relu2(_mm(w, p["Ws_u"], dtype)), p["Ws_d"], dtype)
    (y, most), _ = jax.lax.scan(
        one_expert, (x + shared, jnp.zeros((), jnp.float32)),
        (p["Wu"], p["Wd"], c.T))
    return y, risk, most


def block(kind, p, state, x, *, top_k=TOP_K, first_expert=0, scale=SCALE,
          groups=GROUPS, head_dim=HEAD_DIM, eps=EPS, dtype=None):
    """One layer of ``kind`` on ``x`` [L, d] -> (y [L, d], risk [L], the
    largest entry of one pair), the last two as ``experts`` gives them
    and nothing for the other kinds."""
    if kind == "E":
        return experts(p, state, x, top_k=top_k, first_expert=first_expert,
                       scale=scale, eps=eps, dtype=dtype)
    y = (mamba(p, x, groups=groups, eps=eps, dtype=dtype) if kind == "M"
         else attention(p, x, head_dim=head_dim, eps=eps, dtype=dtype))
    return y, jnp.zeros(x.shape[:1], bool), jnp.zeros((), jnp.float32)


def _names(params):
    """The net's layers, named ``layer_<i>``, in order."""
    return sorted(params, key=lambda k: int(k.rsplit("_", 1)[1]))


def logits_and_risk(params, state, ids, **how):
    """ids int [b, L] -> logits [b, L, vocab], and for each row whether
    its routing was near a tie in any layer."""
    names = _names(params)
    eps, dtype = how.get("eps", EPS), how.get("dtype")

    def one(row):
        x = params[names[0]]["W"][row]
        risk = jnp.zeros(row.shape, bool)
        for name in names[1:-2]:
            layer = jax.checkpoint(functools.partial(
                block, kind_of(params[name]), **how))
            x, here, _ = layer(params[name], state.get(name, {}), x)
            risk |= here
        return _mm(rms_norm(x, params[names[-2]]["g"], eps),
                   params[names[-1]]["W"], dtype), risk

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
