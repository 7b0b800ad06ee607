"""Causal multi-head attention through the op registry — the transformer
tier's one genuinely fusion-hungry op (ROADMAP item 1; the TVM thesis from
PAPERS.md applied to attention).

Three backends behind the ``causal_mha`` registry seam:

- ``xla`` (default): one scale/mask/softmax/matmul chain with both
  contractions lowered as fused multiply+reduce loops instead of dot
  primitives. A GEMM's k-accumulation order is tiled by shape — measured
  on this XLA, a tq=1 dot and a tq=T dot over the same rows disagree in
  the last ulp — while a fused reduce's order is independent of every
  non-reduced dimension. That lowering choice is the whole decode
  bit-identity contract (below). Scores and softmax run in f32 regardless
  of compute dtype (bf16 exponent range is not enough for long-sequence
  logits); masking uses ``-inf`` so masked positions contribute an EXACT
  0.0 to every reduction.
- ``xla_dot``: the same chain as batched f32-accumulating dots (the MXU
  lowering). Faster for big shapes off-TPU, tolerance-equivalent, NOT
  decode-stable — selectable via ``registry.use_backend`` where the
  contract is not in play.
- ``pallas``: a flash-style forward — online softmax over kv tiles with
  the running (m, l, acc) carried in f32 VMEM scratch, causal tile-skip
  above the diagonal, the [t, t] score matrix never materialized to HBM.
  Guarded by ``attention_supported``: hand-DMA'd streaming kernels
  measured 13-73 GB/s against XLA's ~700-800 GB/s on the previous
  software stack (PERF.md Findings, rounds 3-5; not re-measured on the
  installed one), so the kernel only runs where its VMEM-residency win
  (no score-matrix traffic) is structural, and it silently delegates to
  the xla backend everywhere else — the same graceful fallback as
  ops/fused_block.py. It compiles under Mosaic on the v5e and matches
  ``xla_dot`` there (``chip_smoke.py``, kernels phase). The backward
  recomputes through the xla_dot formulation (a custom_vjp): that same
  old verdict priced a hand-written flash backward as a net loss, and
  grad parity against the xla backend is what
  tests/test_backend_equivalence.py pins either way.

Incremental decode (``decode_mha`` + ``extend_cache``): a step's new-token
queries attend over a KV cache instead of recomputing the prefix. The
**bit-identity contract** (the ``rnn_time_step`` contract from
nn/multilayer.py:485 extended to attention): decoding token-by-token
through a cache of length C produces bit-identical outputs to the
full-sequence causal forward run *at the same kv extent C* — every query
row's visible set {j <= q_start + i} is identical in both paths, and the
exact lowering's reduction order is independent of the q extent. The kv
extent must MATCH between the compared paths: measured on this XLA, even
the fused-reduce lowering regroups its accumulation when the reduced axis
length changes (zero-padding keys from tk=33 to 64 moved f32 outputs by
1 ulp), so the attention layers allocate the cache once at
``max_cache_len`` and run prefill AND every decode step against that full
fixed-extent cache. Padded cache slots must be FINITE (the pool
zero-fills pages) — garbage k rows are masked out, but an inf/nan would
poison 0 * v. Pinned in tests/test_transformer.py.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops import registry

_NEG_INF = float("-inf")
# finite mask for the in-kernel tiles (f32 -inf breaks the m-subtraction
# when a row's running max is still the mask value; see the flash papers'
# convention). Every row's FIRST processed tile contains column 0 <= row,
# so the running max is always a real score by flush time.
_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def _positions(q_start, tq):
    """Absolute position of every query row: [b|1, tq] int32."""
    qs = jnp.asarray(q_start, jnp.int32)
    if qs.ndim == 0:
        qs = qs[None]
    return qs[:, None] + jnp.arange(tq, dtype=jnp.int32)[None, :]


def _mask_softmax(s, q_start, tq, tk):
    """Shared mask + online-softmax tail: returns (p, l) with p the
    unnormalized exp-weights and l the per-row partition sum."""
    qpos = _positions(q_start, tq)                       # [b|1, tq]
    j = jnp.arange(tk, dtype=jnp.int32)
    visible = qpos[:, None, :, None] >= j[None, None, None, :]
    s = jnp.where(visible, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)               # >= one real score
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)               # [b, h, tq, 1]
    return p, l


def _causal_mha_exact(q, k, v, q_start):
    """The contract-bearing formulation: both contractions are explicit
    multiply+reduce chains, NOT dot primitives. A dot lowers to a
    shape-tiled GEMM whose k-accumulation order changes with the q extent
    (measured on this XLA: tq=1 and tq=T disagree in the last ulp), while
    a fused reduce loops the contracted axis per output element — the
    order is independent of every other dimension. That is what makes
    incremental decode (tq=1..n over a cache) bit-identical to the
    full-sequence forward (tq=T). Products reduce in f32 regardless of
    compute dtype (the preferred_element_type=f32 semantics)."""
    b, tq, h, dh = q.shape
    tk = k.shape[1]
    cd = q.dtype
    scale = 1.0 / math.sqrt(dh)
    qh = jnp.moveaxis(q, 2, 1).astype(jnp.float32)       # [b, h, tq, dh]
    kh = jnp.moveaxis(k, 2, 1).astype(jnp.float32)       # [b, h, tk, dh]
    vh = jnp.moveaxis(v, 2, 1).astype(jnp.float32)
    s = jnp.sum(qh[:, :, :, None, :] * kh[:, :, None, :, :],
                axis=-1) * scale                         # [b, h, tq, tk]
    p, l = _mask_softmax(s, q_start, tq, tk)
    # normalize AFTER the weighted sum (the flash acc/l form) so no
    # division sits inside a reduction for XLA to reassociate
    out = jnp.sum(p[:, :, :, :, None] * vh[:, :, None, :, :],
                  axis=3)                                # [b, h, tq, dh]
    out = out / l
    return jnp.moveaxis(out, 1, 2).astype(cd)


def _causal_mha_dot(q, k, v, q_start):
    """The MXU formulation: both contractions as batched dots with f32
    accumulation — what the fused scale/mask/softmax/matmul chain should
    lower to on an accelerator. Tolerance-equivalent to the exact
    formulation (same math, GEMM-tiled reductions); NOT decode-stable,
    which is why it is a named backend rather than the default."""
    b, tq, h, dh = q.shape
    tk = k.shape[1]
    cd = q.dtype
    scale = 1.0 / math.sqrt(dh)
    qh = jnp.moveaxis(q, 2, 1)
    kh = jnp.moveaxis(k, 2, 1)
    vh = jnp.moveaxis(v, 2, 1)
    s = jax.lax.dot_general(
        qh, kh, dimension_numbers=(((3,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32) * scale      # [b, h, tq, tk]
    p, l = _mask_softmax(s, q_start, tq, tk)
    out = jax.lax.dot_general(
        p.astype(cd), vh, dimension_numbers=(((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32)              # [b, h, tq, dh]
    out = out / l
    return jnp.moveaxis(out, 1, 2).astype(cd)


@registry.register("causal_mha", backend="xla")
def causal_mha_xla(q, k, v, *, q_start=0):
    """Causal MHA, the default backend: fused scale/mask/softmax/matmul
    semantics in the decode-stable multiply+reduce lowering (see
    ``_causal_mha_exact`` — this is the formulation the bit-identity
    contract is pinned on)."""
    return _causal_mha_exact(q, k, v, q_start)


@registry.register("causal_mha", backend="xla_dot")
def causal_mha_xla_dot(q, k, v, *, q_start=0):
    """Batched-GEMM lowering of the same chain (MXU-friendly; decode
    tolerance documented in the module docstring)."""
    return _causal_mha_dot(q, k, v, q_start)


# --------------------------------------------------------------- pallas
_interpret = registry.pallas_interpret

_BQ = 128
_BK = 128
# one grid step's resident set must fit beside double-buffered tiles
_VMEM_BUDGET = 12 * 1024 * 1024


def attention_supported(q, k, v, q_start=0) -> bool:
    """Does the flash kernel cover this configuration? Decode steps
    (traced/nonzero q_start, tiny tq) stay on xla — a per-step GEMV has no
    score-matrix traffic to save and the per-grid-step overhead measured
    on the previous stack (~15-25us, PERF.md Findings) would dominate
    it."""
    if not (isinstance(q_start, int) and q_start == 0):
        return False
    if q.dtype not in (jnp.bfloat16, jnp.float32):
        return False
    if k.dtype != q.dtype or v.dtype != q.dtype:
        return False
    b, tq, h, dh = q.shape
    tk = k.shape[1]
    if tq != tk:
        return False
    if dh % 128 != 0 or tq % _BQ != 0 or tk % _BK != 0:
        return False
    itemsize = 2 if q.dtype == jnp.bfloat16 else 4
    foot = (3 * 2 * _BQ * dh * itemsize      # q/k/v tiles, double-buffered
            + _BQ * dh * (itemsize + 4)      # out tile + f32 accumulator
            + 2 * _BQ * 128 * 4              # m, l scratch
            + 2 * _BQ * _BK * 4)             # s, p intermediates
    if foot > _VMEM_BUDGET:
        return False
    if not _interpret() and jax.default_backend() != "tpu":
        return False
    return True


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale, bq, bk, kv_blocks):
    import jax.experimental.pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal tile-skip: process only tiles touching or below the diagonal
    @pl.when(ki * bk <= qi * bq + bq - 1)
    def _():
        qb = q_ref[0]
        kb = k_ref[0]
        s = jax.lax.dot_general(
            qb, kb, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(rows >= cols, s, _MASK_VALUE)
        m_prev = m_scr[:][:, :1]
        l_prev = l_scr[:][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == kv_blocks - 1)
    def _():
        o_ref[0] = (acc_scr[:] / l_scr[:][:, :1]).astype(o_ref.dtype)


def _flash_fwd_impl(q, k, v):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, tq, h, dh = q.shape
    tk = k.shape[1]
    scale = 1.0 / math.sqrt(dh)
    qh = jnp.moveaxis(q, 2, 1).reshape(b * h, tq, dh)
    kh = jnp.moveaxis(k, 2, 1).reshape(b * h, tk, dh)
    vh = jnp.moveaxis(v, 2, 1).reshape(b * h, tk, dh)
    qt, kt = tq // _BQ, tk // _BK

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, bq=_BQ, bk=_BK,
                          kv_blocks=kt),
        grid=(b * h, qt, kt),
        in_specs=[
            pl.BlockSpec((1, _BQ, dh), lambda bh, qi, ki: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _BK, dh), lambda bh, qi, ki: (bh, ki, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _BK, dh), lambda bh, qi, ki: (bh, ki, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, _BQ, dh), lambda bh, qi, ki: (bh, qi, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b * h, tq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((_BQ, 128), jnp.float32),
            pltpu.VMEM((_BQ, 128), jnp.float32),
            pltpu.VMEM((_BQ, dh), jnp.float32),
        ],
        interpret=_interpret(),
    )(qh, kh, vh)
    return jnp.moveaxis(out.reshape(b, h, tq, dh), 1, 2)


@jax.custom_vjp
def _flash(q, k, v):
    return _flash_fwd_impl(q, k, v)


def _flash_vjp_fwd(q, k, v):
    return _flash_fwd_impl(q, k, v), (q, k, v)


def _flash_vjp_bwd(res, g):
    # backward recomputes through the batched-dot formulation (module
    # docstring): the previous stack's Pallas DMA rates priced a hand
    # flash-backward as a net loss, and the dot lowering keeps the
    # recompute on the MXU
    q, k, v = res
    _, vjp = jax.vjp(
        lambda a, b_, c: _causal_mha_dot(a, b_, c, 0), q, k, v)
    return vjp(g)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@registry.register("causal_mha", backend="pallas")
def causal_mha_pallas(q, k, v, *, q_start=0):
    """Flash-style tiled forward; silently delegates to the xla backend
    for configurations the kernel does not cover (decode steps, unaligned
    shapes, non-TPU without interpret — see ``attention_supported``)."""
    if not attention_supported(q, k, v, q_start):
        return causal_mha_xla(q, k, v, q_start=q_start)
    return _flash(q, k, v)


# --------------------------------------------------------------- decode
def causal_mha(q, k, v, *, q_start=0):
    """Resolve the registered backend order and apply (layer-facing)."""
    return registry.get("causal_mha")(q, k, v, q_start=q_start)


def causal_mha_exact(q, k, v, *, q_start=0):
    """The contract-bearing exact formulation, OUTSIDE the registry seam:
    the attention layers' streaming (prefill/decode) path calls this
    directly so a ``use_backend`` override can never break the pinned
    decode bit-identity contract. The registry-resolved ``causal_mha``
    stays the training/throughput seam."""
    return _causal_mha_exact(q, k, v, q_start)


def decode_mha(q, k_cache, v_cache, pos):
    """Incremental decode: ``q`` [b, t_new, h, dh] holds the new tokens'
    queries, the caches hold every earlier position (plus the new tokens,
    already written by ``extend_cache``), ``pos`` [b] is each row's prefix
    length. Row i of the step attends keys j <= pos + i — exactly the
    visible set the full-sequence forward gives that absolute position, so
    outputs are bit-identical to the full forward's corresponding slice
    (module docstring contract)."""
    return causal_mha(q, k_cache, v_cache, q_start=pos)


def extend_cache(k_cache, v_cache, k_new, v_new, pos):
    """Write t_new per-row projections into the caches at each row's own
    offset: cache[i, pos[i]:pos[i]+t_new] = new[i]. Caches [b, T, h, dh];
    caller guarantees pos + t_new <= T (the serving tier re-buckets the
    gathered cache before the step that would overflow)."""
    pos = jnp.asarray(pos, jnp.int32)

    def _write(cache, new, p):
        # literal-int starts would promote to int64 under jax_enable_x64
        # and clash with the int32 position row
        z = jnp.zeros((), p.dtype)
        return jax.lax.dynamic_update_slice(cache, new, (p, z, z))

    return (jax.vmap(_write)(k_cache, k_new, pos),
            jax.vmap(_write)(v_cache, v_new, pos))
