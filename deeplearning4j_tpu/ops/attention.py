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
  Guarded by ``attention_supported``; it silently delegates to the xla
  backend everywhere else. It compiles under Mosaic on the v5e and matches
  ``xla_dot`` there (``chip_smoke.py``, kernels phase); no benchmark
  cell runs it, so its speed is still unmeasured. Its backward
  recomputes through the xla_dot formulation (a custom_vjp), which
  writes the [t, t] scores: grad parity against the xla backend is what
  tests/test_backend_equivalence.py pins. The tiled kernels further
  down (``block_diffusion_mha``) are the measured ones: on the v5e, in
  the cell ``sdar_30b_a3b-train-b1-l4096`` (2 x 4,096 rows, 32 query
  heads on 4 key/value heads of 128, tiles of 8 x 128 rows by 512
  keys), the forward runs at 46% and the tiled backward at 40% of the
  MXU's roofline for the visible pairs, which is 2.4 us a grid step for
  1.4 of products and, backward, 85% of the MXU's peak for the seven
  products the two kernels then executed (PERF.md, Findings PR 36;
  since PR 41 the backward is one kernel of five products). The
  causal path was not moved onto that kernel body: the body carries a
  window of key blocks per row and could carry "keys up to my own", but
  the causal path's contract is the decode bit-identity above, pinned
  on the xla lowering, and ROADMAP D1/D5 decide the flash forward's
  fate first.

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


# ------------------------------------------------- block-diffusion attention
# Attention under the block-diffusion training mask (SDAR; PERF.md
# section 4). A sequence of L tokens enters the layers twice, as 2L rows:
# rows 0..L-1 are the noised copy, rows L..2L-1 the clean copy, both at
# positions 0..L-1, in blocks of ``block_len`` tokens. Row i sees row j
# where ``block_diffusion_visible(i, j, L, block_len)``. The rule is a
# function of the two indices, computed from iotas wherever it is needed:
# no [2L, 2L] array exists on any path. Grouped-query heads: Hq query
# heads read Hkv key/value heads, query head h reading head h // (Hq/Hkv).
#
# Two backends behind ``block_diffusion_mha``:
#
# - ``xla``: masked softmax over the dense scores, for the CPU and for
#   shapes the kernels do not cover. The scores reach memory.
# - ``pallas``: a tiled forward (online softmax) and a tiled backward
#   (dK/dV kernel and dQ kernel, from the forward's saved row statistics)
#   whose scores never leave VMEM. One grid step takes the G = Hq/Hkv
#   query heads of a group against one key/value tile, so a key tile is
#   read once for 8 heads and the MXU streams G * bq rows per tile.
#   The query tile ``bq`` follows the group (``_bd_query_tile``): a
#   grid step has a fixed cost of about a microsecond whatever it
#   holds, so the tile is the one that brings G * bq to about 1,024
#   rows: 128 positions at groups of 8 and 16, 512 at a group of 1,
#   where 128 rows against 512 keys were 0.34 us of products in a step
#   of 1.21 (PERF.md, Findings PR 38). The
#   grid is the list of LIVE (query tile, key tile) pairs, made on the
#   host from the rule and handed to the kernels as scalar-prefetch
#   tables: a tile pair with no visible entry costs no grid step and no
#   DMA. Of the 4 L^2 pairs L^2 + L * block_len are visible; at L = 4096
#   and tiles of 128 x 512 the live tiles hold 1.25 times that.
#
#   Which way a kernel's score tile lies is chosen by what it reduces:
#   the forward and the backward hold it as [keys, G * bq rows].
#   Whatever a kernel knows per ROW (the forward's running max and sum,
#   the backward's saved log-sum-exp and ``di``) then lies along the
#   lanes, dense, and the forward's two reductions over a tile's keys go
#   down the sublanes on the VPU; dK and dV are sums over rows, which
#   that orientation hands to the MXU's contraction, and dQ^T = k^T dS^T
#   contracts the keys as the forward's v^T p^T does.
#
#   A row's mask is the same for every head of the group, so a step
#   makes it once for the query tile's [bk, bq] entries and lays it on
#   each head's lanes (``_on_heads``; a forward of one chunk compares
#   across its lanes instead, ``_bd_visible``). The forward makes its
#   scores in chunks of lanes (whole heads or equal slices of one head,
#   so whole rows and the arithmetic of one chunk), the next chunk's
#   product issued before this one's max, exp and sum (``_fwd_chunks``):
#   in the order written, Mosaic's schedule runs the product and the
#   vector passes one after the other.
#
#   The backward is ONE kernel (``_bd_bwd_kernel``, since PR 41): it
#   walks the pairs query-major as the forward does and makes the
#   scores, p, dP and dS once a pair, five products a step; dQ gathers
#   over a query tile's run, dK and dV of the whole key/value head stay
#   resident in VMEM. Where those resident blocks pass
#   ``_BD_RESIDENT_BUDGET`` (over 16,384 rows at heads of 128) it is two
#   kernels over the same pairs, a dQ kernel with the scores as
#   [rows, keys] and its statistics as columns, and a key-major dK/dV
#   kernel: seven products and two vector passes a pair.


def block_diffusion_visible(i, j, seq_len: int, block_len: int):
    """Whether row ``i`` may attend to row ``j`` of the ``2 * seq_len``
    rows (integer arrays that broadcast against each other)."""
    noised_i, noised_j = i < seq_len, j < seq_len
    bi = jnp.where(noised_i, i, i - seq_len) // block_len
    bj = jnp.where(noised_j, j, j - seq_len) // block_len
    return jnp.where(noised_i,
                     jnp.where(noised_j, bi == bj, bj < bi),
                     (~noised_j) & (bj <= bi))


def _count_calls(metric: str, what: str, direction: str,
                 backend: str) -> None:
    from deeplearning4j_tpu.observability.metrics import get_registry

    get_registry().counter(
        metric, f"{what} calls traced, by direction and backend",
        ("direction", "backend")).labels(
            direction=direction, backend=backend).inc()


def _count_block_attention(direction: str, backend: str) -> None:
    _count_calls("dl4j_block_attention_calls_total",
                 "Block-diffusion attention", direction, backend)


@registry.register("block_diffusion_mha", backend="xla")
def block_diffusion_mha_xla(q, k, v, *, seq_len: int, block_len: int):
    """q [b, 2L, Hq, dh], k and v [b, 2L, Hkv, dh] -> [b, 2L, Hq, dh].
    Scores and softmax in float32, both products with float32
    accumulation; autodiff gives the backward."""
    _count_block_attention("forward", "xla")
    rows = jnp.arange(q.shape[1], dtype=jnp.int32)
    return _dense_masked_mha(q, k, v, block_diffusion_visible(
        rows[:, None], rows[None, :], seq_len, block_len))


def _dense_masked_mha(q, k, v, visible):
    """Grouped-query attention under ``visible`` [T, T] over the dense
    scores: q [b, T, Hq, dh], k and v [b, T, Hkv, dh] -> [b, T, Hq, dh]."""
    b, t, hq, dh = q.shape
    hkv = k.shape[2]
    qg = q.reshape(b, t, hkv, hq // hkv, dh)
    s = jnp.einsum("bikgd,bjkd->bkgij", qg, k,
                   preferred_element_type=jnp.float32) / math.sqrt(dh)
    p = jax.nn.softmax(jnp.where(visible, s, _NEG_INF), axis=-1)
    out = jnp.einsum("bkgij,bjkd->bikgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, t, hq, dh).astype(q.dtype)


_BD_STEP_ROWS = 1024    # rows (G heads x query positions) a grid step aims at
_BD_CHUNK_LANES = 512   # score lanes a forward chunk holds at most


def _bd_key_tile(seq_len: int) -> int:
    return next(bk for bk in (512, 256, 128) if seq_len % bk == 0)


def _bd_query_tile(group: int, seq_len: int) -> int:
    """Query positions a tile, times ``group`` heads = the rows one grid
    step streams through the MXU: the largest power of two that keeps a
    step within ``_BD_STEP_ROWS`` rows, no less than 128 positions (the
    lane tile of the row statistics) and no more than the key tile (at a
    group of 1 a tile of 1,024 against keys of 512 holds 1.25 times the
    visible entries where 512 holds 1.125, and read 0.7% slower in the
    step on the v5e). The key tile divides ``seq_len``, so this one does
    and no tile straddles the two halves of a block-diffusion sequence.
    512 at a group of 1, 256 at 4, 128 from 8 on."""
    want = min(max(_BD_STEP_ROWS // group, 128), _bd_key_tile(seq_len))
    return next(bq for bq in (512, 256, 128) if bq <= want)


def _fwd_chunks(group: int, bq: int) -> int:
    """How many chunks of query lanes a forward grid step's [bk, group *
    bq] scores are cut into (``fwd_body``): two at least, so that one
    chunk's product runs beside the other's vector passes, and no more
    than ``_BD_CHUNK_LANES`` lanes a chunk; one below 256 lanes, where a
    chunk would hold fewer than 128. A chunk is whole heads or an equal
    slice of one head's positions, so where the lanes do not split so
    at that count the step takes the most chunks below it that do (one
    at a group of 3, 5 or 7). Timed alone on a v5e at the five decoder
    cells' tiles, two chunks beat four and eight at 512 and 1,024 lanes,
    and four beat eight and two at 2,048 in ten rounds of ten (PERF.md,
    Findings)."""
    lanes = group * bq
    want = max(lanes // _BD_CHUNK_LANES, 2 if lanes >= 256 else 1)
    return next(n for n in range(want, 0, -1) if group % n == 0
                or (n % group == 0 and bq % (n // group) == 0))


def block_attention_supported(q, k, v, seq_len: int, block_len: int) -> bool:
    """Whether the kernels cover this call: tiles may not straddle the
    two halves or a block, blocks are a power of two (the rule is shifts
    and compares in the kernel), heads of 128."""
    b, t, hq, dh = q.shape
    if t != 2 * seq_len or seq_len % 128 or dh % 128:
        return False
    if not _bd_operands_supported(q, k, v):
        return False
    if block_len & (block_len - 1):
        return False
    return _bd_query_tile(hq // k.shape[2], seq_len) % block_len == 0


def _bd_operands_supported(q, k, v) -> bool:
    """What the tiled kernels ask whatever the mask rule: one dtype the
    MXU takes, query heads in whole groups, and a TPU (or the tests'
    interpret mode) to run them."""
    if q.dtype not in (jnp.bfloat16, jnp.float32) or q.shape[2] % k.shape[2]:
        return False
    if k.dtype != q.dtype or v.dtype != q.dtype:
        return False
    return _interpret() or jax.default_backend() == "tpu"


@functools.lru_cache(maxsize=None)
def _bd_live_tiles(seq_len: int, block_len: int, bq: int, bk: int):
    """The (query tile, key tile) pairs that hold a visible entry, as
    rows ``(qi, ki, kind)`` in query-major order; kind 0 is noised rows
    on noised keys (the same block), 1 noised rows on clean keys (blocks
    before), 2 clean on clean (blocks up to its own)."""
    import numpy as np

    def blocks(lo, n):
        lo %= seq_len
        return lo // block_len, (lo + n - 1) // block_len

    pairs = []
    for qi in range(2 * seq_len // bq):
        q_noised = qi * bq < seq_len
        qb_lo, qb_hi = blocks(qi * bq, bq)
        for ki in range(2 * seq_len // bk):
            k_noised = ki * bk < seq_len
            kb_lo, kb_hi = blocks(ki * bk, bk)
            if q_noised and k_noised:
                live, kind = kb_lo <= qb_hi and qb_lo <= kb_hi, 0
            elif q_noised:
                live, kind = kb_lo < qb_hi, 1
            else:
                live, kind = (not k_noised) and kb_lo <= qb_hi, 2
            if live:
                pairs.append((qi, ki, kind))
    return np.asarray(pairs, np.int32)


def _causal_live_tiles(rows: int, bq: int, bk: int):
    """The live pairs of the causal rule over ``rows`` rows, "row i sees
    key j <= i": in the kernels' terms every row is a clean row whose
    block is itself (kind 2 with blocks of one position and no noised
    half), so the rule is a table and the kernels are the same."""
    import numpy as np

    return np.asarray([(qi, ki, 2) for qi in range(rows // bq)
                       for ki in range(rows // bk)
                       if ki * bk <= qi * bq + bq - 1], np.int32)


def _bd_tables(seq_len, block_len, bq, bk, key_major: bool):
    """Scalar-prefetch tables of the live pairs in the order a kernel
    walks them: ``qi, ki, kind, first, last`` where first/last mark the
    run of steps that share the tile the kernel accumulates for.
    ``block_len`` None: the causal rule over ``seq_len`` rows."""
    import numpy as np

    pairs = (_causal_live_tiles(seq_len, bq, bk) if block_len is None
             else _bd_live_tiles(seq_len, block_len, bq, bk))
    owner = 1 if key_major else 0
    if key_major:
        pairs = pairs[np.lexsort((pairs[:, 0], pairs[:, 1]))]
    change = np.flatnonzero(np.diff(pairs[:, owner])) + 1
    first = np.zeros(len(pairs), np.int32)
    last = np.zeros(len(pairs), np.int32)
    first[np.r_[0, change]] = 1
    last[np.r_[change - 1, len(pairs) - 1]] = 1
    return tuple(jnp.asarray(a) for a in
                 (pairs[:, 0], pairs[:, 1], pairs[:, 2], first, last))


def _bd_bounds(kind, q_lo, k_lo, seq_len, shift, q_iota, k_iota):
    """Block numbers of the tile's rows and keys and the window of key
    blocks each row sees: ``lower <= key block <= upper``. ``q_iota`` and
    ``k_iota`` are int32 iotas shaped to broadcast against each other."""
    q0 = jnp.where(q_lo >= seq_len, q_lo - seq_len, q_lo)
    k0 = jnp.where(k_lo >= seq_len, k_lo - seq_len, k_lo)
    qb = jnp.right_shift(q0 + q_iota, jnp.int32(shift))
    kb = jnp.right_shift(k0 + k_iota, jnp.int32(shift))
    upper = qb - jnp.where(kind == 1, 1, 0)
    lower = jnp.where(kind == 0, qb, -1)
    return kb, lower, upper


def _rows_to_col(rows, g, bq):
    """[g, bq] -> [g * bq, 1] with elementwise ops and a lane reduction
    only (no relayout): lane r of head h lands in row h * bq + r."""
    eye = (jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 1))
    return jnp.concatenate(
        [jnp.sum(jnp.where(eye, rows[h:h + 1], 0.0), axis=1, keepdims=True)
         for h in range(g)], axis=0)


def _rows_to_lanes(rows, g):
    """[g, bq] -> [1, g * bq]: the heads side by side along the lanes."""
    return jnp.concatenate([rows[h:h + 1] for h in range(g)], axis=1)


def _bd_scores(qi, ki, kind, q_ref, k_ref, scale, seq_len, shift):
    """The masked scaled scores of one live tile pair, [g * bq, bk]
    float32: the G heads' rows of the query tile against the key tile,
    the rule computed once for the tile and laid over every head."""
    g, bq, dh = q_ref.shape[1:]
    bk = k_ref.shape[1]
    kb, lower, upper = _bd_bounds(
        kind, qi * bq, ki * bk, seq_len, shift,
        jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0),
        jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1))
    visible = (kb <= upper) & (kb >= lower)                 # [bq, bk]
    s = jax.lax.dot_general(
        q_ref[0].reshape(g * bq, dh), k_ref[0],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    return jnp.where(visible[None], s.reshape(g, bq, bk),
                     _MASK_VALUE).reshape(g * bq, bk)


def _bd_fwd_kernel(qi_ref, ki_ref, kind_ref, first_ref, last_ref,
                   q_ref, k_ref, v_ref, o_ref, lse_ref,
                   m_scr, l_scr, acc_scr, *, scale, seq_len, shift):
    """Online softmax with the scores as [keys, rows] (``k . q^T``, as
    ``_bd_dkv_kernel`` makes them): a row's running max, sum and
    rescaling factor are [1, g * bq] along the lanes, 8 dense vregs for
    8 heads, and the max and the sum over a tile's keys run down the
    sublanes, elementwise across vregs, on the VPU. As [rows, keys] both
    are reductions across the 128 lanes of every vreg of the tile and
    the statistics [g * bq, 1] columns, one lane of a vreg in use: on a
    v5e that is half of such a kernel's time (PERF.md, Findings PR 36).
    The accumulator follows as [dh, g * bq] (``v^T . p^T``) and is turned
    once a query tile, head by head; the log-sum-exp leaves by splitting
    the lanes. The split backward's dQ kernel keeps [rows, keys]: it
    reduces nothing over the keys and its products want the rows
    streamed."""
    import jax.experimental.pallas as pl

    s_id = pl.program_id(1)
    g, bq, _ = q_ref.shape[1:]
    bk = k_ref.shape[1]
    fwd_body(lambda: first_ref[s_id] == 1, lambda: last_ref[s_id] == 1,
             _bd_visible(kind_ref, qi_ref, ki_ref, s_id, g, bq, bk, seq_len,
                         shift, shared=_fwd_chunks(g, bq) > 1),
             q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
             scale=scale)


def _bd_visible(kind_ref, qi_ref, ki_ref, s_id, g, bq, bk, seq_len, shift,
                *, shared=True):
    """``visible`` of the kernel bodies under a rule's table at grid step
    ``s_id``, whose ``mask(lo, hi)`` is the mask of score lanes
    ``lo:hi``: the rule's compare made once a step for the query tile's
    [bk, bq] entries and laid on the lanes' heads (``_on_heads``), or
    with ``shared`` False the compare made across the step's lanes, lane
    ``h * bq + r`` as row ``r``. On a v5e the first reads faster in a
    chunked forward and in the backward, the second in a forward of one
    chunk (PERF.md, Findings)."""
    def visible():
        lanes = jax.lax.broadcasted_iota(jnp.int32, (1, bq if shared
                                                     else g * bq), 1)
        kb, lower, upper = _bd_bounds(
            kind_ref[s_id], qi_ref[s_id] * bq, ki_ref[s_id] * bk, seq_len,
            shift, lanes if shared else lanes & (bq - 1),
            jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0))
        if shared:
            return functools.partial(_on_heads,
                                     (kb <= upper) & (kb >= lower))
        return lambda lo, hi: ((kb <= upper[:, lo:hi])
                               & (kb >= lower[:, lo:hi]))
    return visible


def _on_heads(keep, lo, hi):
    """Lanes ``lo:hi`` of a step's scores under the query tile's mask
    ``keep`` [bk, bq], which every head of the group shares (lane
    ``h * bq + r`` is row ``r`` of head ``h``): a slice of one head's
    rows, or one copy a head."""
    bq = keep.shape[1]
    if hi - lo == bq:
        return keep
    if hi - lo < bq:
        return keep[:, lo % bq:lo % bq + hi - lo]
    return jnp.concatenate([keep] * ((hi - lo) // bq), axis=1)


def _chunk_rows(ref, lo, hi):
    """Rows ``lo:hi`` of ``ref[0]`` [g, bq, dh] seen as [g * bq, dh]: whole
    heads, or a slice of one head's positions."""
    bq, dh = ref.shape[2:]
    if hi - lo >= bq:
        return ref[0, lo // bq:hi // bq].reshape(hi - lo, dh)
    return ref[0, lo // bq, lo % bq:lo % bq + hi - lo]


def fwd_body(first, last, visible, q_ref, k_ref, v_ref, o_ref, lse_ref,
             m_scr, l_scr, acc_scr, *, scale):
    """One step of the forward kernels: the pair's scores as [keys,
    rows] where the mask is true, the running statistics reset where
    ``first()`` and the output written where ``last()``. ``visible()``
    reads what the mask is made of and returns ``mask(lo, hi)``, the mask
    of score lanes ``lo:hi`` ([bk, hi - lo] or broadcasting to it).
    ``ops/sparse_attention.py`` runs it under a mask made from the data.

    The scores are made in ``_fwd_chunks`` independent chunks of lanes,
    unrolled: a chunk is whole rows (every row's statistics lie in its
    own lane), so each makes its own product, max, exp, sum and second
    product and reads and writes its own lanes of the scratch once, with
    the arithmetic of one chunk. Each chunk's scores are issued before
    the passes of the chunk before it, so that the MXU has a product to
    run beside the vector unit's passes (PERF.md, Findings)."""
    import jax.experimental.pallas as pl

    g, bq, dh = q_ref.shape[1:]

    @pl.when(first())
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    mask = visible()
    width = g * bq // _fwd_chunks(g, bq)

    def scores(lo):                                          # [bk, width]
        st = jax.lax.dot_general(
            k_ref[0], _chunk_rows(q_ref, lo, lo + width),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        return jnp.where(mask(lo, lo + width), st, _MASK_VALUE)

    # the next chunk's product is written before this chunk's passes:
    # written after them, it ran after them
    st = scores(0)
    for lo in range(0, g * bq, width):
        following = scores(lo + width) if lo + width < g * bq else None
        lanes = slice(lo, lo + width)
        m_prev = m_scr[:, lanes]
        m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pt = jnp.exp(st - m_new)
        l_scr[:, lanes] = (alpha * l_scr[:, lanes]
                           + jnp.sum(pt, axis=0, keepdims=True))
        m_scr[:, lanes] = m_new
        acc_scr[:, lanes] = acc_scr[:, lanes] * alpha + jax.lax.dot_general(
            v_ref[0], pt.astype(v_ref.dtype),
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [dh, width]
        st = following

    @pl.when(last())
    def _():
        l = l_scr[:]
        out = acc_scr[:] / l
        lse = m_scr[:] + jnp.log(l)
        for h in range(g):
            o_ref[0, h] = out[:, h * bq:(h + 1) * bq].T.astype(o_ref.dtype)
            lse_ref[0, h:h + 1, :] = lse[:, h * bq:(h + 1) * bq]


# The split backward's dQ: scores as [rows, keys] (``_bd_scores``), the
# row statistics read once a query tile into [g * bq, 1] columns; nothing
# is reduced over keys.
def _bd_dq_kernel(qi_ref, ki_ref, kind_ref, first_ref, last_ref,
                  q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
                  lse_scr, di_scr, acc_scr, *, scale, seq_len, shift):
    import jax.experimental.pallas as pl

    s_id = pl.program_id(1)
    g, bq, dh = q_ref.shape[1:]

    @pl.when(first_ref[s_id] == 1)
    def _():
        lse_scr[:] = _rows_to_col(lse_ref[0], g, bq)
        di_scr[:] = _rows_to_col(di_ref[0], g, bq)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    s = _bd_scores(qi_ref[s_id], ki_ref[s_id], kind_ref[s_id], q_ref, k_ref,
                   scale, seq_len, shift)
    p = jnp.exp(s - lse_scr[:])
    dp = jax.lax.dot_general(
        do_ref[0].reshape(g * bq, dh), v_ref[0],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - di_scr[:]) * scale
    acc_scr[:] += jax.lax.dot_general(
        ds.astype(k_ref.dtype), k_ref[0],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(last_ref[s_id] == 1)
    def _():
        dq_ref[0] = acc_scr[:].reshape(g, bq, dh).astype(dq_ref.dtype)


def _bd_dkv_kernel(qi_ref, ki_ref, kind_ref, first_ref, last_ref,
                   q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                   dk_ref, dv_ref, dk_scr, dv_scr, *, scale, seq_len, shift):
    """The transposed products: scores as [keys, rows], so the row
    statistics lie along the lanes as they are stored, and dV, dK are
    plain products with the G heads summed by the contraction."""
    import jax.experimental.pallas as pl

    s_id = pl.program_id(1)
    g, bq, dh = q_ref.shape[1:]
    bk = k_ref.shape[1]

    @pl.when(first_ref[s_id] == 1)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, g * bq), 1)
    kb, lower, upper = _bd_bounds(
        kind_ref[s_id], qi_ref[s_id] * bq, ki_ref[s_id] * bk, seq_len, shift,
        lanes & (bq - 1),
        jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0))
    q = q_ref[0].reshape(g * bq, dh)
    do = do_ref[0].reshape(g * bq, dh)
    st = jax.lax.dot_general(
        k_ref[0], q, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale          # [bk, g*bq]
    st = jnp.where((kb <= upper) & (kb >= lower), st, _MASK_VALUE)
    pt = jnp.exp(st - _rows_to_lanes(lse_ref[0], g))
    dv_scr[:] += jax.lax.dot_general(
        pt.astype(do.dtype), do, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dpt = jax.lax.dot_general(
        v_ref[0], do, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    dst = pt * (dpt - _rows_to_lanes(di_ref[0], g)) * scale
    dk_scr[:] += jax.lax.dot_general(
        dst.astype(q.dtype), q, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(last_ref[s_id] == 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bd_bwd_kernel(qi_ref, ki_ref, kind_ref, first_ref, last_ref,
                   q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                   dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr,
                   *, scale, seq_len, shift):
    """dQ, dK and dV from one walk over the live pairs in the forward's
    query-major order: the scores, p, dP and dS of a pair are made once,
    as [keys, rows] (``_bd_dkv_kernel``'s orientation), five products a
    step. dQ gathers as [dh, rows] (``k^T . dS^T``, the forward's
    ``v^T . p^T``) over a query tile's run and is turned head by head at
    its end; dK and dV gather in float32 scratch that holds every key
    tile of the key/value head, indexed by the step's key tile, zeroed
    at the head's first step and written at its last."""
    import jax.experimental.pallas as pl

    s_id = pl.program_id(1)
    g, bq, _ = q_ref.shape[1:]
    bk = k_ref.shape[1]
    bwd_body(s_id, lambda: first_ref[s_id] == 1,
             lambda: last_ref[s_id] == 1, ki_ref[s_id],
             _bd_visible(kind_ref, qi_ref, ki_ref, s_id, g, bq, bk, seq_len,
                         shift),
             q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
             dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, scale=scale)


def bwd_body(s_id, first, last, ki, visible, q_ref, k_ref, v_ref, do_ref,
             lse_ref, di_ref, dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr,
             *, scale, live=True):
    """One step of the one-kernel backward (``_bd_bwd_kernel``'s
    docstring) at grid step ``s_id``, the pair's entries where the mask
    is true, ``first``, ``last`` and ``visible`` as ``fwd_body``'s.
    ``live`` False (traced) makes a step that computes nothing: the
    kernels of ``ops/sparse_attention.py`` end their walk with such
    steps."""
    import jax.experimental.pallas as pl

    g, bq, dh = q_ref.shape[1:]

    @pl.when(s_id == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(first())
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def step():
        mask = visible()
        q = q_ref[0].reshape(g * bq, dh)
        do = do_ref[0].reshape(g * bq, dh)
        st = jax.lax.dot_general(
            k_ref[0], q, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [bk, g*bq]
        st = jnp.where(mask(0, g * bq), st, _MASK_VALUE)
        pt = jnp.exp(st - _rows_to_lanes(lse_ref[0], g))
        dpt = jax.lax.dot_general(
            v_ref[0], do, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dst = (pt * (dpt - _rows_to_lanes(di_ref[0], g))
               * scale).astype(q.dtype)
        dv_scr[ki] += jax.lax.dot_general(
            pt.astype(do.dtype), do,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[ki] += jax.lax.dot_general(
            dst, q, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dq_scr[:] += jax.lax.dot_general(
            k_ref[0], dst, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [dh, g*bq]

    if live is True:
        step()
    else:
        pl.when(live)(step)

    @pl.when(last())
    def _():
        dq = dq_scr[:]
        for h in range(g):
            dq_ref[0, h] = dq[:, h * bq:(h + 1) * bq].T.astype(dq_ref.dtype)

    @pl.when(s_id == pl.num_programs(1) - 1)
    def _():
        dk_ref[0] = dk_scr[:].reshape(-1, dh).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].reshape(-1, dh).astype(dv_ref.dtype)


_BD_VMEM_LIMIT = 64 * 1024 * 1024
# what the fused backward's resident dK and dV may take of that limit
_BD_RESIDENT_BUDGET = _BD_VMEM_LIMIT // 2


def _bd_fused_fits(t: int, dh: int, dtype) -> bool:
    """Whether dK and dV of one key/value head of ``t`` rows can stay in
    VMEM for the fused backward: their float32 scratch and the two
    outputs' double buffers, a row at least a lane tile wide, within
    ``_BD_RESIDENT_BUDGET``. Up to 16,384 rows at heads of 128 in bf16."""
    lanes = max(dh, 128)
    return (2 * t * lanes * (4 + 2 * jnp.dtype(dtype).itemsize)
            <= _BD_RESIDENT_BUDGET)


def _bd_call(kernel, tables, n_steps, bh, in_specs, out_specs, out_shape,
             scratch, **static):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        functools.partial(kernel, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables), grid=(bh, n_steps),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_BD_VMEM_LIMIT),
        interpret=_interpret(),
    )


def _bd_specs(g, bq, bk, dh):
    """Block specs by what a block follows: the step's query tile or its
    key tile (``qi_ref`` and ``ki_ref`` are the first two tables)."""
    import jax.experimental.pallas as pl

    def rows(bh, s, qi, ki, *_):
        return bh, 0, qi[s], 0

    def stats(bh, s, qi, ki, *_):
        return bh, 0, qi[s]

    def keys(bh, s, qi, ki, *_):
        return bh, ki[s], 0

    return (pl.BlockSpec((1, g, bq, dh), rows),
            pl.BlockSpec((1, g, bq), stats),
            pl.BlockSpec((1, bk, dh), keys))


def _bd_split(q, k, v):
    """[b, T, H, dh] -> queries [b*Hkv, G, T, dh], keys and values
    [b*Hkv, T, dh]."""
    b, t, hq, dh = q.shape
    hkv = k.shape[2]
    qg = jnp.moveaxis(q.reshape(b, t, hkv, hq // hkv, dh), 1, 3)
    return (qg.reshape(b * hkv, hq // hkv, t, dh),
            jnp.moveaxis(k, 1, 2).reshape(b * hkv, t, dh),
            jnp.moveaxis(v, 1, 2).reshape(b * hkv, t, dh))


def _bd_join(og, b):
    """The inverse of ``_bd_split`` for a query-shaped array."""
    bh, g, t, dh = og.shape
    return jnp.moveaxis(og.reshape(b, bh // b, g, t, dh), 3, 1).reshape(
        b, t, bh // b * g, dh)


def _bd_shift(block_len) -> int:
    """A position's block is the position shifted by this; the causal
    rule (``block_len`` None) has blocks of one."""
    return 0 if block_len is None else block_len.bit_length() - 1


def _count_tiled_attention(direction: str, group: int, bq: int) -> None:
    from deeplearning4j_tpu.observability.metrics import get_registry

    get_registry().counter(
        "dl4j_tiled_attention_calls_total",
        "Tiled attention kernel calls traced (a forward kernel, or a "
        "backward in either form), by direction, by the query heads a "
        "key/value head and by the query positions a tile that group was "
        "given",
        ("direction", "group", "query_tile")).labels(
            direction=direction, group=str(group), query_tile=str(bq)).inc()


def _count_forward_chunks(g: int, bq: int) -> None:
    from deeplearning4j_tpu.observability.metrics import get_registry

    get_registry().counter(
        "dl4j_tiled_attention_forward_chunks_total",
        "Tiled attention forward calls traced (under a rule's table or a "
        "selection), by the chunks of query lanes a grid step's scores "
        "were cut into and by the query heads a key/value head",
        ("chunks", "group")).labels(
            chunks=str(_fwd_chunks(g, bq)), group=str(g)).inc()


def _count_tiled_backward(form: str, group: int) -> None:
    from deeplearning4j_tpu.observability.metrics import get_registry

    get_registry().counter(
        "dl4j_tiled_attention_backward_total",
        "Tiled attention backward calls traced, by form (fused: one kernel "
        "makes dQ, dK and dV; split: a dQ and a dK/dV kernel, where the "
        "resident dK and dV would not fit) and by the query heads a "
        "key/value head", ("form", "group")).labels(
            form=form, group=str(group)).inc()


def _bd_forward(qg, kg, vg, seq_len, block_len):
    from jax.experimental.pallas import tpu as pltpu

    bh, g, t, dh = qg.shape
    bq, bk = _bd_query_tile(g, seq_len), _bd_key_tile(seq_len)
    _count_tiled_attention("forward", g, bq)
    _count_forward_chunks(g, bq)
    tables = _bd_tables(seq_len, block_len, bq, bk, key_major=False)
    rows, stats, keys = _bd_specs(g, bq, bk, dh)
    return _bd_call(
        _bd_fwd_kernel, tables, len(tables[0]), bh,
        in_specs=[rows, keys, keys], out_specs=[rows, stats],
        out_shape=[jax.ShapeDtypeStruct(qg.shape, qg.dtype),
                   jax.ShapeDtypeStruct((bh, g, t), jnp.float32)],
        scratch=[pltpu.VMEM((1, g * bq), jnp.float32),
                 pltpu.VMEM((1, g * bq), jnp.float32),
                 pltpu.VMEM((dh, g * bq), jnp.float32)],
        scale=1.0 / math.sqrt(dh), seq_len=seq_len,
        shift=_bd_shift(block_len))(*tables, qg, kg, vg)


def _bd_backward(qg, kg, vg, og, lse, dog, seq_len, block_len):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, g, t, dh = qg.shape
    bq, bk = _bd_query_tile(g, seq_len), _bd_key_tile(seq_len)
    _count_tiled_attention("backward", g, bq)
    fused = _bd_fused_fits(t, dh, kg.dtype)
    _count_tiled_backward("fused" if fused else "split", g)
    static = dict(scale=1.0 / math.sqrt(dh), seq_len=seq_len,
                  shift=_bd_shift(block_len))
    di = jnp.sum(og.astype(jnp.float32) * dog.astype(jnp.float32), axis=-1)
    rows, stats, keys = _bd_specs(g, bq, bk, dh)
    operands = (qg, kg, vg, dog, lse, di)
    in_specs = [rows, keys, keys, rows, stats, stats]
    tables = _bd_tables(seq_len, block_len, bq, bk, key_major=False)
    if fused:
        head = pl.BlockSpec((1, t, dh), lambda b, *_: (b, 0, 0))
        return tuple(_bd_call(
            _bd_bwd_kernel, tables, len(tables[0]), bh,
            in_specs=in_specs, out_specs=[rows, head, head],
            out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype)
                       for a in (qg, kg, vg)],
            scratch=[pltpu.VMEM((dh, g * bq), jnp.float32),
                     pltpu.VMEM((t // bk, bk, dh), jnp.float32),
                     pltpu.VMEM((t // bk, bk, dh), jnp.float32)],
            **static)(*tables, *operands))
    dq = _bd_call(
        _bd_dq_kernel, tables, len(tables[0]), bh,
        in_specs=in_specs, out_specs=rows,
        out_shape=jax.ShapeDtypeStruct(qg.shape, qg.dtype),
        scratch=[pltpu.VMEM((g * bq, 1), jnp.float32),
                 pltpu.VMEM((g * bq, 1), jnp.float32),
                 pltpu.VMEM((g * bq, dh), jnp.float32)],
        **static)(*tables, *operands)
    tables = _bd_tables(seq_len, block_len, bq, bk, key_major=True)
    dk, dv = _bd_call(
        _bd_dkv_kernel, tables, len(tables[0]), bh,
        in_specs=in_specs, out_specs=[keys, keys],
        out_shape=[jax.ShapeDtypeStruct(kg.shape, kg.dtype),
                   jax.ShapeDtypeStruct(vg.shape, vg.dtype)],
        scratch=[pltpu.VMEM((bk, dh), jnp.float32),
                 pltpu.VMEM((bk, dh), jnp.float32)],
        **static)(*tables, *operands)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _bd_attention(qg, kg, vg, seq_len, block_len):
    return _bd_forward(qg, kg, vg, seq_len, block_len)[0]


def _bd_attention_fwd(qg, kg, vg, seq_len, block_len):
    _count_block_attention("forward", "pallas")
    og, lse = _bd_forward(qg, kg, vg, seq_len, block_len)
    return og, (qg, kg, vg, og, lse)


def _bd_attention_bwd(seq_len, block_len, residuals, dog):
    _count_block_attention("backward", "pallas")
    return _bd_backward(*residuals, dog, seq_len, block_len)


_bd_attention.defvjp(_bd_attention_fwd, _bd_attention_bwd)


@registry.register("block_diffusion_mha", backend="pallas")
def block_diffusion_mha_pallas(q, k, v, *, seq_len: int, block_len: int):
    """The tiled forward and backward; delegates to the xla backend for
    calls ``block_attention_supported`` refuses."""
    if not block_attention_supported(q, k, v, seq_len, block_len):
        return block_diffusion_mha_xla(q, k, v, seq_len=seq_len,
                                       block_len=block_len)
    og = _bd_attention(*_bd_split(q, k, v), seq_len, block_len)
    return _bd_join(og, q.shape[0])


def block_diffusion_mha(q, k, v, *, seq_len: int, block_len: int):
    """Resolve the registered backend order and apply (layer-facing)."""
    return registry.get("block_diffusion_mha")(
        q, k, v, seq_len=seq_len, block_len=block_len)


# ------------------------------------------- causal attention for training
# The causal rule on the tiled kernels above: forward, dQ and dK/dV over
# the live tiles on and under the diagonal, scores never in HBM. This is
# the training path of a causal decoder layer (nn/layers/decoder.py
# ``CausalAttentionLayer``); ``causal_mha`` further up stays the serving
# path's, with its decode bit-identity contract, and is not touched.


def _count_causal_attention(direction: str, backend: str) -> None:
    _count_calls("dl4j_causal_attention_calls_total",
                 "Causal training attention", direction, backend)


@registry.register("causal_attention", backend="xla")
def causal_attention_xla(q, k, v):
    """q [b, T, Hq, dh], k and v [b, T, Hkv, dh] -> [b, T, Hq, dh]:
    masked softmax over the dense scores in float32 (they reach
    memory); autodiff gives the backward."""
    _count_causal_attention("forward", "xla")
    rows = jnp.arange(q.shape[1], dtype=jnp.int32)
    return _dense_masked_mha(q, k, v, rows[:, None] >= rows[None, :])


def causal_attention_supported(q, k, v) -> bool:
    """Whether the tiled kernels cover this call: whole tiles of 128
    rows, heads of whole lane tiles or of 64 (the head is the blocks'
    whole last dimension, so q, k, v and the output stay 64 wide in
    memory; a product then fills half of the MXU's contraction for the
    scores and half of its columns for the values), and what
    ``_bd_operands_supported`` asks."""
    if q.shape[1] % 128 or (q.shape[3] % 128 and q.shape[3] != 64):
        return False
    return _bd_operands_supported(q, k, v)


@jax.custom_vjp
def _causal_tiled(qg, kg, vg):
    return _bd_forward(qg, kg, vg, qg.shape[2], None)[0]


def _causal_tiled_fwd(qg, kg, vg):
    _count_causal_attention("forward", "pallas")
    og, lse = _bd_forward(qg, kg, vg, qg.shape[2], None)
    return og, (qg, kg, vg, og, lse)


def _causal_tiled_bwd(residuals, dog):
    _count_causal_attention("backward", "pallas")
    return _bd_backward(*residuals, dog, residuals[0].shape[2], None)


_causal_tiled.defvjp(_causal_tiled_fwd, _causal_tiled_bwd)


@registry.register("causal_attention", backend="pallas")
def causal_attention_pallas(q, k, v):
    """The tiled forward and backward; delegates to the xla backend for
    calls ``causal_attention_supported`` refuses."""
    if not causal_attention_supported(q, k, v):
        return causal_attention_xla(q, k, v)
    return _bd_join(_causal_tiled(*_bd_split(q, k, v)), q.shape[0])


def causal_attention(q, k, v):
    """Causal grouped-query attention of a training step: resolve the
    registered backend order and apply (layer-facing)."""
    return registry.get("causal_attention")(q, k, v)
