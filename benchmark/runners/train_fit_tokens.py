"""Runner for traffic of kind ``train_fit_tokens``: one training job on
integer token ids through ``net.fit(iterator)`` with default arguments.

The listener and its barriers, the memory sampler, the profiler's start
and the profile's reduction, the net's build and the rates are
``runners/train_fit.py``'s, imported; what ``train_examples_per_s`` and
``setup_s`` mean is written there. Its warm-up and window build their
iterator from ``(features, labels)`` pairs inside themselves, and a
token batch has a weight per label too, so ``_warm_up`` and ``_window``
here are theirs over a ring of DataSets (PERF.md section 7 asks the next
``benchmark`` issue to let them take the iterator). This module brings:

- the ring: ``ring_batches`` batches of ``batch`` sequences of
  ``seq_len`` ids drawn uniformly from the held vocabulary less its last
  id, ``[MASK]``, and noised once from ``--seed`` by the program's
  ``BlockDiffusionPreProcessor`` (features ``int32 [b, 2L]``, labels
  ``int32 [b, L]``, a weight per label);
- the counts the step returns in the net's state (rows a step for every
  held expert of every layer), read before and after the window;
- ``correct``, against ``benchmark/reference/sdar_moe.py`` on the seeded
  initial parameters and the ring at the timed size; what is compared
  and why is in that module's docstring. The same comparisons are made
  of the reference against itself with every forward product's operands
  rounded to float8_e4m3fn, and have to fail: the line says so
  (``fp8_would_fail``) in every run. On a TPU the Pallas attention has
  to have been traced (``dl4j_block_attention_calls_total``).

Traffic parameters: ``batch``, ``seq_len``, ``ring_batches``,
``warmup_steps``, ``trace_seconds``, ``rehearsal``.
"""

from __future__ import annotations

import functools
import os
import time
from importlib import import_module

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.measure import Measurement
from benchmark.runners import train_fit
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.preprocessors import (
    BlockDiffusionPreProcessor)
from deeplearning4j_tpu.observability import metrics as obs
from deeplearning4j_tpu.observability import moe as obs_moe
from deeplearning4j_tpu.observability.trace import Tracer, set_tracer


class TokenRing(train_fit.RingIterator):
    """Over DataSets, where the parent takes (features, labels) pairs."""

    def __init__(self, ring, group: int, go_on):
        self.batches, self.group, self.go_on = list(ring), group, go_on


def make_ring(config: dict, traffic: dict, seed: int) -> list:
    vocab = config["kwargs"]["vocab_size"]
    rng = np.random.default_rng(seed)
    noise = BlockDiffusionPreProcessor(
        config["input"]["block_len"], mask_id=vocab - 1, seed=seed)
    return [noise.pre_process(DataSet(rng.integers(
        0, vocab - 1, (traffic["batch"], traffic["seq_len"]))))
        for _ in range(traffic["ring_batches"])]


def _warm_up(net, ring, steps: int, fit_kwargs: dict):
    """``train_fit._warm_up`` over a ring of DataSets."""
    tracer = Tracer()
    previous = set_tracer(tracer)
    listener = train_fit.WindowListener()
    net.set_listeners(listener)
    left = iter(range(steps))
    try:
        net.fit(TokenRing(ring, 1, lambda: next(left, None) is not None),
                **fit_kwargs)
        listener.finish()
    finally:
        set_tracer(previous)
    group = max((s.attrs or {}).get("steps", 1)
                for s in tracer.spans() if s.name == "host_dispatch")
    return [float(s) for s in jax.device_get(listener.scores)], group


def _window(ctx, net, ring, group: int, fit_kwargs: dict, trace_dir: str):
    """``train_fit._window`` over a ring of DataSets."""
    tracer = Tracer()
    previous = set_tracer(tracer)
    tracer_epoch = time.perf_counter()
    profiled = []

    def start_trace():
        train_fit._start_profiler(trace_dir)
        profiled.append(True)

    deadline = time.perf_counter() + ctx.seconds
    listener = train_fit.WindowListener(
        trace_at=deadline - ctx.cell.traffic["trace_seconds"],
        start_trace=start_trace if ctx.trace else None)
    net.set_listeners(listener)
    try:
        net.fit(TokenRing(ring, group,
                          lambda: time.perf_counter() < deadline),
                **fit_kwargs)
        listener.finish()
    finally:
        if profiled:
            jax.profiler.stop_trace()
        set_tracer(previous)
    return listener, tracer, tracer_epoch, bool(profiled)


def _expert_rows(net) -> np.ndarray:
    """Rows given so far to every held expert of every layer that counts
    them, [layers, experts]."""
    counted = obs_moe.expert_rows(net)
    names = sorted(counted, key=lambda n: int(n.rsplit("_", 1)[1]))
    return (np.stack([counted[n][1] for n in names]) if names
            else np.zeros((0, 0), np.int64))


def _attention_calls(backend: str) -> float:
    return sum(s["value"] for s in obs.get_registry().snapshot().get(
        "dl4j_block_attention_calls_total", [])
        if s["labels"]["backend"] == backend)


def _worst(system, reference, rows=None, given=0.0):
    """max |system - reference| over the entries of ``rows`` (all when
    None), relative to the largest entry of |reference - given|: of what
    the layer added to its input, where ``given`` is that input."""
    diff = jnp.abs(jnp.asarray(system, jnp.float32) - reference)
    if rows is not None:
        diff = jnp.where(rows[:, None], diff, 0.0)
    return float(diff.max() / jnp.abs(reference - given).max())


def _relative(system, reference, axes=None):
    d = jnp.sqrt(jnp.sum(jnp.square(system - reference), axis=axes))
    n = jnp.sqrt(jnp.sum(jnp.square(reference), axis=axes))
    return jnp.where(d > 0, jnp.minimum(d / n, 1e6), 0.0)


@functools.partial(jax.jit, static_argnames="by_expert")
def _leaf_errors(system, reference, by_expert=()):
    """|system - reference|_2 / |reference|_2 of every leaf (0 where both
    are 0). The experts' matrices, named in ``by_expert``, are sums over
    routed pairs, and where the rows of one token, ``[MASK]``, sit at a
    tie, the two experts they are shared between differ in bf16, and
    only they: such a leaf is taken an expert's slice at a time, and its
    error is the median slice's."""
    def one(path, s, r):
        if path[-1].key not in by_expert:
            return _relative(s, r)
        return jnp.median(_relative(s, r, tuple(range(1, s.ndim))))
    return jax.tree_util.tree_map_with_path(one, system, reference)


FP8 = jnp.float8_e4m3fn


def _check_blocks(ref, how, params, activations) -> tuple:
    """The layers one at a time on the program's own inputs.
    ``activations``: the train-mode forward's; batch row 0 is compared
    (the rows of one sequence are the unit)."""
    names = sorted(params, key=lambda k: int(k.rsplit("_", 1)[1]))
    block = jax.jit(lambda p, x, lowered: ref.block(
        p, x, dtype=(FP8, lowered), **how))
    checks = {"blocks": []}
    ok = True
    for i, name in enumerate(names[1:-2], start=1):
        x = jnp.asarray(activations[i - 1][0], jnp.float32)
        want, risk, pair = block(params[name], x, False)
        got = activations[i][0]
        # near a tie one pair may go for another: two pairs' worth
        near_ties_tol = ref.BLOCK_RTOL + 2 * float(
            pair / jnp.abs(want - x).max())
        reading = {
            "layer": name, "rel_err": _worst(got, want, ~risk, x),
            "rel_err_near_ties": _worst(got, want, risk, x),
            "near_ties_tol": near_ties_tol,
            "near_ties_share": float(jnp.mean(risk)),
            "finite": bool(np.all(np.isfinite(
                np.asarray(got, np.float32))))}
        checks["blocks"].append(reading)
        ok &= (reading["finite"] and reading["rel_err"] <= ref.BLOCK_RTOL
               and reading["rel_err_near_ties"] <= near_ties_tol)
        if i == 1:
            lowered = block(params[name], x, True)[0]
            checks["block_fp8_reading"] = _worst(lowered, want, ~risk, x)
    return checks, ok


def _check_logits(system, reference, risk, lowered) -> dict:
    """``net.output`` against the reference end to end, entry by entry,
    relative to the largest logit: the rows never near a tie, and all."""
    scale = jnp.abs(reference).max()
    by_row = jnp.abs(jnp.asarray(system) - reference).max(axis=-1) / scale
    fp8_by_row = jnp.abs(lowered - reference).max(axis=-1) / scale
    return {"rel_err": float(jnp.where(risk, 0.0, by_row).max()),
            "rel_err_all_rows": float(by_row.max()),
            "near_ties_share": float(jnp.mean(risk)),
            "fp8_reading": float(jnp.where(risk, 0.0, fp8_by_row).max())}


def _check(config, init, first, ring, activations, logits,
           first_loss) -> dict:
    """``init``: the seeded parameters and state; ``first``: what the
    first dispatch left (steps, parameters, Adam's first moment);
    ``activations``, ``logits``: the train-mode forward and
    ``net.output`` of ring batch 0 on ``init``. Runs after the window,
    with the net's arrays gone, so that the reference's memory neither
    counts into the cell's peak nor has to fit beside them."""
    ref = import_module(config["reference"])
    how = config["reference_kwargs"]
    params, state = init
    batches = [tuple(jnp.asarray(a) for a in (
        ds.features, ds.labels, ds.labels_mask)) for ds in ring]
    # one program for both precisions: ``lowered`` is traced
    reference = jax.jit(jax.value_and_grad(
        lambda p, lowered, *batch: ref.loss(
            p, state, *batch, with_logits=True, dtype=(FP8, lowered), **how),
        has_aux=True))
    with jax.default_matmul_precision("highest"):
        checks, ok = _check_blocks(ref, how, params, activations)
        device_params = jax.device_put(params)
        (ref_loss, (want, risk)), grad = reference(
            device_params, False, *batches[0])
        (_, (lowered, _)), lowered_grad = reference(
            device_params, True, *batches[0])
        checks["logits"] = _check_logits(logits[0], want[0], risk[0],
                                         lowered[0])
        fp8 = jax.device_get(_leaf_errors(lowered_grad, grad))
        del want, lowered, lowered_grad
        grads = [grad] + [reference(device_params, False, *b)[1]
                          for b in batches[1:]]
    rel = train_fit._rel(first_loss, float(ref_loss))
    checks["first_loss"] = {"system": first_loss,
                            "reference": float(ref_loss), "rel_err": rel}

    adam = jax.jit(lambda g, p: ref.adam(g, p, first["steps"],
                                         **config["reference_updater"]))
    leaves = {}
    for name in params:
        moment, change = adam([g[name] for g in grads], device_params[name])
        moved = jax.tree_util.tree_map(np.subtract, first["params"][name],
                                       params[name])
        by = jax.device_get({
            "grad": _leaf_errors(first["moment"][name], moment,
                                 ref.EXPERT_LEAVES),
            "update": _leaf_errors(moved, change, ref.EXPERT_LEAVES)})
        for leaf in params[name]:
            leaves[f"{name}.{leaf}"] = {
                "grad": float(by["grad"][leaf]),
                "update": float(by["update"][leaf]),
                "grad_fp8": float(fp8[name][leaf])}

    def worst(key, experts):
        held = [v[key] for k, v in leaves.items()
                if k.rsplit(".", 1)[1] not in ref.ROUTER_LEAVES
                and (k.rsplit(".", 1)[1] in ref.EXPERT_LEAVES) == experts]
        return max(held)

    now = checks["first_dispatch"] = {
        "steps": first["steps"],
        "grad_rel_err": worst("grad", False),
        "grad_rel_err_experts": worst("grad", True),
        "grad_fp8_reading": worst("grad_fp8", False),
        "update_rel_err": worst("update", False),
        "update_rel_err_experts": worst("update", True),
        "leaves": leaves}
    checks["fp8_would_fail"] = bool(
        checks["block_fp8_reading"] > ref.BLOCK_RTOL
        and checks["logits"]["fp8_reading"] > ref.LOGITS_RTOL
        and now["grad_fp8_reading"] > ref.GRAD_RTOL)
    checks["tol"] = {
        "block": ref.BLOCK_RTOL, "logits": ref.LOGITS_RTOL,
        "logits_all_rows": ref.LOGITS_RTOL_ALL_ROWS, "loss": ref.LOSS_RTOL,
        "grad": ref.GRAD_RTOL, "grad_experts": ref.GRAD_RTOL_EXPERTS,
        "update": ref.UPDATE_RTOL}
    checks["ok"] = bool(
        ok and checks["logits"]["rel_err"] <= ref.LOGITS_RTOL
        and checks["logits"]["rel_err_all_rows"] <= ref.LOGITS_RTOL_ALL_ROWS
        and rel <= ref.LOSS_RTOL
        and now["grad_rel_err"] <= ref.GRAD_RTOL
        and now["grad_rel_err_experts"] <= ref.GRAD_RTOL_EXPERTS
        and max(now["update_rel_err"], now["update_rel_err_experts"])
        <= ref.UPDATE_RTOL)
    return checks


def run(ctx) -> dict:
    config, traffic = ctx.cell.config, ctx.cell.traffic
    fit_kwargs = (dict(train_fit.REHEARSAL_FIT_KWARGS) if ctx.rehearse
                  else {})
    batch = traffic["batch"]
    snap_setup = obs.compile_snapshot()
    phases = {"program_import": time.time() - ctx.t0}
    net, _ = train_fit._build(ctx)
    phases["build"] = time.time() - ctx.t0 - sum(phases.values())
    ring = make_ring(config, traffic, ctx.seed)
    phases["ring"] = time.time() - ctx.t0 - sum(phases.values())
    # what the checks compare, taken before the first step moves the
    # parameters
    init = jax.device_get((net.params, net.state))
    activations = jax.device_get(net.feed_forward(ring[0].features,
                                                  train=True))
    logits = np.asarray(net.output(ring[0].features))
    phases["forward_check"] = time.time() - ctx.t0 - sum(phases.values())
    trace_dir = os.path.join(ctx.root, ".bench_trace", ctx.cell.name)

    sampler = train_fit.MemorySampler(jax.local_devices())
    sampler.start()
    try:
        # the first dispatch apart: the check reads the state it leaves
        half = traffic["warmup_steps"] // 2
        warm_losses, group = _warm_up(net, ring, half, fit_kwargs)
        moments = {name: s["m"] for name, s in net.opt_state.items()
                   if isinstance(s, dict) and "m" in s}
        first = jax.device_get({"params": net.params, "moment": moments})
        first["steps"] = int(net.iteration)
        phases["first_dispatch"] = (time.time() - ctx.t0
                                    - sum(phases.values()))
        more, group = _warm_up(net, ring, traffic["warmup_steps"] - half,
                               fit_kwargs)
        warm_losses += more
        setup_compile = obs.compile_delta(snap_setup)
        rows_before = _expert_rows(net)
        snap_window = obs.compile_snapshot()
        setup_s = time.time() - ctx.t0
        phases["warm_up"] = setup_s - sum(phases.values())
        listener, tracer, tracer_epoch, profiled = _window(
            ctx, net, ring, group, fit_kwargs, trace_dir)
        window_compile = obs.compile_delta(snap_window)
    finally:
        memory = sampler.finish()

    barriers = listener.barriers
    if len(barriers) < 2:
        raise RuntimeError(f"the window held {len(barriers)} dispatches; "
                           "a rate needs two")
    window_s = barriers[-1][0] - barriers[0][0]
    lo_us = (barriers[0][0] - tracer_epoch) * 1e6
    hi_us = (barriers[-1][0] - tracer_epoch) * 1e6
    spans = [s for s in tracer.spans()
             if s.ts_us >= lo_us and s.ts_us + s.dur_us <= hi_us]
    losses = np.asarray(jax.device_get(listener.scores), np.float64)
    rows = (_expert_rows(net) - rows_before) / max(len(losses), 1)

    counters = {
        "window_steps": barriers[-1][1] - barriers[0][1],
        "steps_per_dispatch": group,
        "setup_cache_misses": setup_compile["cache_misses"],
        "setup_cache_hits": setup_compile["cache_hits"],
        "setup_compile_s": setup_compile["seconds"],
        "window_compiles": window_compile["count"],
        "memory_peak_bytes": memory["peak_bytes"],
        "memory_arrays_bytes_at_peak": memory["arrays"],
        "memory_programs_bytes_at_peak": memory["programs"],
        "memory_limit_bytes": memory["limit"],
        # mean over the window's steps
        "moe_pairs_per_step": float(rows.sum()),
        "moe_expert_rows": rows.reshape(-1).tolist(),
        "block_attention_pallas_calls": _attention_calls("pallas"),
    }
    split = listener.traced_from
    if split is not None:
        counters["rate_profiler_off"] = train_fit._rate(barriers[:split],
                                                        batch)
        counters["rate_profiler_on"] = train_fit._rate(barriers[split:],
                                                       batch)
    undisturbed = barriers[:split]
    counters["rate_whole_window"] = train_fit._rate(undisturbed, batch)
    counters["rate_median_dispatch"] = train_fit._median_rate(undisturbed,
                                                              batch)
    reduction = (train_fit._reduce_profile(trace_dir, tracer)
                 if profiled else None)

    for leaf in jax.tree_util.tree_leaves((net.params, net.opt_state)):
        leaf.delete()
    checks = _check(config, init, first, ring, activations, logits,
                    warm_losses[0])
    checks["loss"] = {"first": warm_losses[0], "warmup_last": warm_losses[-1],
                      "window_last": float(losses[-1])}
    failed = int(np.sum(~np.isfinite(losses)))
    # like with like: the ring's batches weigh their tokens differently
    # (1/t), so the last loss is held against the first on its own batch
    same_batch = warm_losses[(len(losses) - 1) % len(ring)]
    checks["loss"]["first_on_last_batch"] = same_batch
    kernels = (jax.default_backend() != "tpu"
               or counters["block_attention_pallas_calls"] > 0)
    correct = bool(checks.pop("ok") and not failed and kernels
                   and losses[-1] < same_batch
                   and window_compile["count"] == 0)
    rate = train_fit._median_rate(barriers, batch)

    return {
        "correct": correct,
        "attempted": int(len(losses)),
        "failed": failed,
        "end_to_end": {"train_examples_per_s": rate, "setup_s": setup_s},
        "measurement": Measurement(
            config=config, traffic=traffic, chips=ctx.cell.chips,
            peaks=ctx.peaks, window_s=window_s, spans=spans,
            counters=counters, trace=reduction),
        "memory_peak_bytes": int(memory["peak_bytes"]),
        "info": {"checks": checks, "counters": counters,
                 "window_s": window_s, "dispatches": len(barriers),
                 "step_s_quantiles": dict(zip(
                     ("min", "p10", "median", "p90", "max"),
                     np.quantile(train_fit._seconds_per_step(barriers),
                                 (0, 0.1, 0.5, 0.9, 1)).tolist())),
                 "train_examples_per_s": rate,
                 "setup_s": setup_s, "setup_phases_s": phases},
    }
