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

Four batched products a chunk and no step a position. The decays, their
sums and the carried state are float32; the products take operands in
the compute dtype and accumulate in float32. ``L`` is made from the
masked difference, never from a quotient of two exponentials: a chunk
whose decay underflows (a large ``dt``) gives exact zeros, not 0 / 0.

One executor, ``xla``, that every platform runs (the registry seam is
there for a kernel: ``P`` = 64 is half a lane tile, and this module
claims no speed). It is one differentiable op with a hand-written
backward that saves the op's arguments and nothing a chunk made: the
backward computes the chunked form again and differentiates that, so of
the ``[H, chunks, chunk, chunk]`` decay matrices (134 MB a layer at 64
heads and 4,096 positions) only the layer in hand is alive.
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
