"""Grouped gated feed-forward over routed (row, expert) pairs: the
matrix products of an expert layer that holds some of a model's experts
(nn/layers/decoder.py).

The pairs arrive sorted by held expert: pair ``p`` is row ``rows[p]`` of
``x`` weighted by ``coef[p]``, expert ``e`` owns the ``counts[e]`` pairs
after those of the experts before it, and whatever follows
``sum(counts)`` belongs to experts held elsewhere. The arrays are as
long as the worst case (every row choosing only experts held here); the
work is not. The sorted pairs are walked in chunks of ``chunk`` pairs of
one expert by a loop whose trip count is the number of chunks that hold
a pair, ``sum(ceil(counts / chunk))``: a chunk gathers its rows, runs the
three products of ``down(silu(gate) * up)`` against its expert's
weights, and scatter-adds the weighted result. Nothing is dropped
whatever the counts, nothing is padded into the result (the tail of an
expert's last chunk is weighted 0), and a step costs what its counts
cost, at most ``chunk - 1`` idle rows an expert.

The backward is written by hand, as a second loop over the same chunks:
a loop whose length is data cannot be differentiated in reverse, and a
scan over the worst case would save residuals for the worst case. It
recomputes gate and up from the gathered rows (two of eight products)
and saves nothing a chunk made.

The gathers and scatter-adds inside both loops are under the named scope
``route``, the rest under the caller's (``experts`` in the decoder
block), so a device trace tells moving rows from multiplying them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 128


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


def _forward(x, rows, coef, counts, wg, wu, wd, chunk):
    table = _chunk_table(counts, rows.shape[0], chunk)
    rows, coef = _pad(rows, coef, chunk)
    cd = x.dtype

    def body(c, y):
        with jax.named_scope("route"):
            e, _, idx, w, _ = _chunk_inputs(c, table, rows, coef, chunk)
            xs = jnp.take(x, idx, axis=0)
        gate = _dot(xs, wg[e], ((1,), (0,)))
        up = _dot(xs, wu[e], ((1,), (0,)))
        h = (jax.nn.silu(gate) * up).astype(cd)
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

    def body(c, carry):
        dx, dcoef, dwg, dwu, dwd = carry
        with jax.named_scope("route"):
            e, s, idx, w, live = _chunk_inputs(c, table, rows, coef, chunk)
            xs = jnp.take(x, idx, axis=0)
            dys = jnp.take(dy, idx, axis=0)
        gate = _dot(xs, wg[e], ((1,), (0,)))
        up = _dot(xs, wu[e], ((1,), (0,)))
        sig = jax.nn.sigmoid(gate)
        act = gate * sig
        h = act * up
        dh_pair = _dot(dys, wd[e], ((1,), (1,)))        # of one unit of coef
        dcoef = jax.lax.dynamic_update_slice(
            dcoef, jnp.where(live, jnp.sum(dh_pair * h, axis=1), 0.0), (s,))
        dh = dh_pair * w[:, None]
        dgate = (dh * up * (sig + act * (1.0 - sig))).astype(cd)
        dup = (dh * act).astype(cd)
        dwd = dwd.at[e].add(_dot((h * w[:, None]).astype(cd), dys,
                                 ((0,), (0,))))
        dwg = dwg.at[e].add(_dot(xs, dgate, ((0,), (0,))))
        dwu = dwu.at[e].add(_dot(xs, dup, ((0,), (0,))))
        dxs = (_dot(dgate, wg[e], ((1,), (1,)))
               + _dot(dup, wu[e], ((1,), (1,))))
        with jax.named_scope("route"):
            return dx.at[idx].add(dxs), dcoef, dwg, dwu, dwd

    zeros = [jnp.zeros(a.shape, jnp.float32) for a in (x, wg, wu, wd)]
    dx, dcoef, dwg, dwu, dwd = jax.lax.fori_loop(
        0, table[3], body,
        (zeros[0], jnp.zeros((n_pairs + chunk,), jnp.float32), *zeros[1:]))
    return dx, dcoef[:n_pairs], dwg, dwu, dwd


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _expert_ffn(x, rows, coef, counts, wg, wu, wd, chunk):
    return _forward(x, rows, coef, counts, wg, wu, wd, chunk)


def _expert_ffn_fwd(x, rows, coef, counts, wg, wu, wd, chunk):
    return (_forward(x, rows, coef, counts, wg, wu, wd, chunk),
            (x, rows, coef, counts, wg, wu, wd))


def _expert_ffn_bwd(chunk, residuals, dy):
    x, rows, coef, counts, wg, wu, wd = residuals
    dx, dcoef, dwg, dwu, dwd = _backward(*residuals, dy, chunk)

    def no_gradient(a):
        return np.zeros(a.shape, jax.dtypes.float0)

    return (dx.astype(x.dtype), no_gradient(rows), dcoef.astype(coef.dtype),
            no_gradient(counts), dwg.astype(wg.dtype), dwu.astype(wu.dtype),
            dwd.astype(wd.dtype))


_expert_ffn.defvjp(_expert_ffn_fwd, _expert_ffn_bwd)


def expert_ffn(x, rows, coef, counts, wg, wu, wd, *, chunk: int = CHUNK):
    """``y[r] = sum over held pairs (r, e) of coef * down_e(silu(gate_e
    x[r]) * up_e x[r])`` as float32 ``[R, d]``.

    ``x`` [R, d] and the weights ``wg``, ``wu`` [E, d, f], ``wd``
    [E, f, d] in the compute dtype; ``rows`` int32 [P] and ``coef``
    float32 [P] the pairs sorted by held expert; ``counts`` int32 [E]
    the pairs of each held expert. Differentiable in ``x``, ``coef`` and
    the weights."""
    _count_call("xla_chunks")
    return _expert_ffn(x, rows.astype(jnp.int32), coef,
                       counts.astype(jnp.int32), wg, wu, wd, chunk)
