"""Sequence (context) parallelism for recurrent models.

The reference has NO sequence-length mechanism beyond truncated BPTT and
masking (SURVEY.md §5.7 — 2017, pre-attention). This framework treats the
sequence dimension as a first-class shardable axis, the way ring attention
treats context for transformers: the TIME axis is sharded over a mesh
axis, and the recurrent carry travels the device ring with
``jax.lax.ppermute`` — a WAVEFRONT schedule.

What this buys (and what it does not):
- Activation/residual memory for the sequence is split D ways: sequences
  D× longer than one device's HBM can be trained (the long-context
  enabler). The input projection x @ Wx (the FLOPs-heavy part at large
  f) and every per-timestep layer around the LSTM run fully parallel on
  their local time chunks.
- The recurrent chain itself is inherently sequential, so the cell scans
  execute one device at a time (each under ``lax.cond``, so off-turn
  devices idle rather than recompute); wall-clock for the scan matches a
  single device. This is the correct physics for an RNN — parallelism in
  TIME is what attention buys and the reference predates.

Built on ``shard_map`` so XLA emits the ICI ppermute collectives; works
on any mesh axis (virtual CPU devices in tests, ICI ring on hardware).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def shard_sequence(mesh: Mesh, seq_axis: str, x, time_dim: int = 1):
    """Place [b, T, ...] with the TIME axis sharded over ``seq_axis``."""
    spec = [None] * np.ndim(x)
    spec[time_dim] = seq_axis
    return jax.device_put(jnp.asarray(x), NamedSharding(mesh, P(*spec)))


def sequence_parallel_lstm(mesh: Mesh, seq_axis: str, params, x, h0, c0,
                           *, mask=None, gate_act: str = "sigmoid",
                           cell_act: str = "tanh"):
    """Graves-LSTM forward over a time-sharded sequence.

    ``params``: the GravesLSTM param dict {Wx, Wh, b, p} (replicated);
    ``x``: [b, T, f] with T sharded over ``seq_axis`` (see
    ``shard_sequence``); ``h0``/``c0``: [b, n] replicated initial carry;
    ``mask``: optional [b, T] per-timestep mask, time-sharded like ``x``
    — masked steps carry (h, c) through unchanged and emit zero output
    (the reference-parity masking semantics, MaskedReductionUtil /
    GravesLSTM masking), including across chunk boundaries: a chunk whose
    steps are all masked hands its carry down the ring untouched.
    Returns (y [b, T, n] time-sharded, hT, cT replicated).

    Schedule: D wavefront steps; at step s the device holding chunk s
    runs its local cell scan (through the ``lstm_sequence`` registry op —
    the Pallas kernel on TPU), then the carry ppermutes one hop along the
    ring.
    """
    from deeplearning4j_tpu.ops import registry as ops

    n = params["Wh"].shape[0]
    d = mesh.shape[seq_axis]
    if x.shape[1] % d != 0:
        raise ValueError(
            f"sequence length {x.shape[1]} is not divisible by the "
            f"'{seq_axis}' mesh axis ({d} devices) — pad the time axis")
    lstm_seq = ops.get("lstm_sequence")
    has_mask = mask is not None

    def local(params, x_local, h0, c0, m_local):
        idx = jax.lax.axis_index(seq_axis)
        cd = x_local.dtype
        p_cd = {k: v.astype(cd) for k, v in params.items()}
        x_t = jnp.moveaxis(x_local, 1, 0)                 # [t_local, b, f]
        m_t = (jnp.moveaxis(m_local.astype(cd), 1, 0)     # [t_local, b]
               if has_mask else None)

        def turn(carry):
            h, c = carry
            ys, hT, cT = lstm_seq(x_t, p_cd["Wx"], p_cd["b"], h, c,
                                  p_cd["Wh"], p_cd["p"], m_t,
                                  gate_act=gate_act, cell_act=cell_act)
            return ys, (hT, cT)

        def wait(carry):
            return jnp.zeros(x_t.shape[:2] + (n,), cd), carry

        y0 = jnp.zeros(x_t.shape[:2] + (n,), cd)

        def body(carry, s):
            ring, y_acc, fin = carry
            ys, new_carry = jax.lax.cond(idx == s, turn, wait, ring)
            # accumulate my own turn's output in a single [t_local, b, n]
            # buffer — stacking all d steps would materialize the FULL
            # sequence's output on every device and defeat the memory
            # scaling this module exists for
            y_acc = y_acc + ys
            # the final (hT, cT) is whatever the LAST wavefront step's
            # owner computed
            fin = jax.lax.cond(s == d - 1, lambda _: new_carry,
                               lambda f: f, fin)
            # hand the carry one hop down the ring
            passed = jax.lax.ppermute(
                new_carry, seq_axis,
                perm=[(i, (i + 1) % d) for i in range(d)])
            return (passed, y_acc, fin), None

        carry0 = (h0.astype(cd), c0.astype(cd))
        (_, y_local_t, (h_fin, c_fin)), _ = jax.lax.scan(
            body, (carry0, y0, carry0), jnp.arange(d))
        y_local = jnp.moveaxis(y_local_t, 0, 1)  # [b, t_local, n]
        # the true final carry lives on device d-1; indicator-mask + psum
        # broadcasts it (a one-to-all "send" is not a valid ppermute
        # permutation)
        is_last = (idx == d - 1).astype(cd)
        hT = jax.lax.psum(h_fin * is_last, seq_axis)
        cT = jax.lax.psum(c_fin * is_last, seq_axis)
        return y_local, hT, cT

    if not has_mask:
        # shard_map needs a concrete operand per spec — feed a scalar
        # placeholder that the traced body never touches
        mask = jnp.zeros((), x.dtype)
    from deeplearning4j_tpu.parallel.mesh import compat_shard_map
    fn = compat_shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(None, seq_axis, None), P(), P(),
                  P(None, seq_axis) if has_mask else P()),
        out_specs=(P(None, seq_axis, None), P(), P()))
    return fn(params, x, h0, c0, mask)
