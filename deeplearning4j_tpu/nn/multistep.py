"""Shared machinery for running n fused train steps in one XLA execution
(lax.scan over a network's raw step_fn) — used by both MultiLayerNetwork
and ComputationGraph fit_batch_repeated."""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Scan unroll factor: 2 lets XLA overlap the tail of one step with the
# head of the next, measured ~2% on the ResNet-50 bench; 4 was measured
# NEUTRAL there — more unrolling only grows the program.
_UNROLL = 2


def build_multi_step(step_fn, n_steps: int):
    """jit(scan(step_fn, length=n_steps)). The returned callable has the
    same signature as step_fn; the rng argument is split once per inner
    step, and the returned score is the last step's."""
    if n_steps < 1:
        raise ValueError(f"n_steps must be >= 1, got {n_steps}")

    def multi(params, state, opt_state, it0, *data_args):
        rng = data_args[-1]
        rest = data_args[:-1]

        def body(carry, i):
            p, s, o, key = carry
            key, sub = jax.random.split(key)
            p, s, o, score = step_fn(p, s, o, it0 + i, *rest, sub)
            return (p, s, o, key), score

        (p, s, o, _), scores = jax.lax.scan(
            body, (params, state, opt_state, rng), jnp.arange(n_steps),
            unroll=min(_UNROLL, n_steps))
        return p, s, o, scores[-1]

    return jax.jit(multi, donate_argnums=(0, 1, 2))


def get_multi_step(net, n_steps: int):
    """Cache-aware accessor for a network's scanned multi-step."""
    if n_steps < 1:
        raise ValueError(f"n_steps must be >= 1, got {n_steps}")
    jitted = net._multi_steps.get(n_steps)
    if jitted is None:
        jitted = build_multi_step(net._step_fn(), n_steps)
        net._multi_steps[n_steps] = jitted
    return jitted


def build_multi_batch_step(step_fn):
    """jit(scan(step_fn)) over a chunk of k DISTINCT batches (leading axis
    of every data leaf is the chunk), bit-identical to k sequential
    ``fit_batch`` calls: the scan body replays fit_batch's exact rng
    discipline — ``key, sub = split(key)``, the step consumes ``sub`` —
    and the final carried key is returned so the caller can store it back
    as the net's rng chain. (``build_multi_step`` above scans the SAME
    batch and burns one extra split; it is not sequentially identical,
    which is fine for benchmarking but not for the fit path.)

    Signature: ``(params, state, opt_state, it0, key, steps, data) ->
    (params, state, opt_state, key, scores)`` where ``steps`` is
    ``arange(k, int32)``, ``data`` is a pytree of stacked per-step args
    (``None`` leaves allowed for absent masks), and ``scores`` has shape
    ``(k,)``. One builder per net; jit re-specializes per (k, shapes).
    """

    def multi(params, state, opt_state, it0, key, steps, data):
        def body(carry, inp):
            p, s, o, k = carry
            i, args = inp
            k, sub = jax.random.split(k)
            p, s, o, score = step_fn(p, s, o, it0 + i, *args, sub)
            return (p, s, o, k), score

        # unroll is pinned to 1: unrolling lets XLA fuse across step
        # boundaries, which perturbs float rounding (~1 ulp, measured) —
        # the fit path's win is collapsed dispatch, and bit-identity with
        # the sequential loop is a hard contract here
        (p, s, o, key), scores = jax.lax.scan(
            body, (params, state, opt_state, key), (steps, data), unroll=1)
        return p, s, o, key, scores

    return jax.jit(multi, donate_argnums=(0, 1, 2))


def get_multi_batch_step(net):
    """Cache-aware accessor for a network's chunked-fit dispatcher (one
    jitted callable per net; distinct chunk sizes/shapes become jit cache
    entries). Invalidated with the rest of ``net._multi_steps`` by
    ``set_lr_scale`` and friends."""
    key = "multi_batch"
    jitted = net._multi_steps.get(key)
    if jitted is None:
        jitted = build_multi_batch_step(net._step_fn())
        net._multi_steps[key] = jitted
    return jitted
