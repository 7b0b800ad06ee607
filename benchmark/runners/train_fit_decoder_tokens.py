"""Runner for traffic of kind ``train_fit_decoder_tokens``: one causal
language-model training job on integer token ids through
``net.fit(iterator)`` with default arguments, for a decoder whose kinds
of layer and whose kernels the configuration's own files name.

The listener and its barriers, the memory sampler, the profiler, the
net's build and the rates are ``runners/train_fit.py``'s; the ring
iterator over DataSets, the warm-up, the window, the experts' counts
and the leaf-by-leaf comparison are ``runners/train_fit_tokens.py``'s;
the ring of ids, the allowance of one unit of the stream's rounding and
the counters by backend are ``runners/train_fit_causal_tokens.py``'s;
all imported. What ``train_examples_per_s`` and ``setup_s`` mean is
written in ``train_fit.py``. This module brings what the three token
runners before it wrote per model, read from the cell's files instead:

- the kinds of block come from the reference (``ref.KINDS`` and
  ``ref.kind_of``, None for a layer that is no block), so a layer is
  found by what it holds and not by where it stands, and a net whose
  head stores no matrix (tied to the embedding) is walked like any
  other;
- the counters ``correct`` requires on a TPU come from the
  configuration's file, ``required_kernels``: ``{counter: backend}``,
  each of which has to have been traced at that backend and at no
  other (here the gated short convolution, the causal attention and the
  grouped products, all ``pallas``);
- ``correct``, against the configuration's reference module on the
  seeded initial parameters and the ring at the timed size, as
  ``train_fit_causal_tokens.py`` makes it: the first loss, every block
  by its kind on the program's own input, the logits of ``net.output``,
  and Adam's first moment and the first change of every leaf; a leaf
  with two users (the tied embedding: the gather's and the head's) is
  read apart besides (``grad_rel_err_tied``), the check that shows a
  copy or a dropped use. The routed matrices are taken an expert's slice
  at a time in the layers that have a router, and in no other. The same
  comparisons are made of the reference against itself with every
  forward product's operands rounded to float8_e4m3fn, and have to fail
  (``fp8_would_fail``).

Traffic parameters: ``batch``, ``seq_len``, ``ring_batches``,
``warmup_steps``, ``trace_seconds``, ``rehearsal``.
"""

from __future__ import annotations

import os
import time
from importlib import import_module

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.measure import Measurement
from benchmark.runners import train_fit
from benchmark.runners.train_fit_causal_tokens import (
    _beyond_rounding, _calls_by_backend, make_ring)
from benchmark.runners.train_fit_tokens import (
    FP8, _check_logits, _expert_rows, _leaf_errors, _warm_up, _window)
from deeplearning4j_tpu.observability import metrics as obs

__all__ = ["make_ring", "run"]


def _index(name: str) -> int:
    return int(name.rsplit("_", 1)[1])


def _check_blocks(ref, how, params, state, activations) -> tuple:
    """The blocks one at a time on the program's own inputs
    (``activations``: the train-mode forward's, by the layer's index;
    batch row 0). Per kind, the fp8 reading of the first layer of it."""
    blocks = {kind: jax.jit(lambda p, s, x, lowered, kind=kind: ref.block(
        kind, p, s, x, dtype=(FP8, lowered), **how)) for kind in ref.KINDS}
    checks = {"blocks": [], "block_fp8_reading": {}}
    ok = True
    for name in sorted(params, key=_index):
        kind = ref.kind_of(params[name])
        if kind is None:
            continue
        i = _index(name)
        x = jnp.asarray(activations[i - 1][0], jnp.float32)
        layer_state = state.get(name, {})
        want, risk, pair = blocks[kind](params[name], layer_state, x, False)
        got = activations[i][0]
        # near a tie one pair may go for another: two pairs' worth
        near_ties_tol = ref.BLOCK_RTOL + 2 * float(
            pair / jnp.abs(want - x).max())
        reading = {
            "layer": name, "kind": kind,
            "rel_err": _beyond_rounding(got, want, ~risk, x),
            "rel_err_near_ties": _beyond_rounding(got, want, risk, x),
            "near_ties_tol": near_ties_tol,
            "near_ties_share": float(jnp.mean(risk)),
            "finite": bool(np.all(np.isfinite(
                np.asarray(got, np.float32))))}
        checks["blocks"].append(reading)
        ok &= (reading["finite"] and reading["rel_err"] <= ref.BLOCK_RTOL
               and reading["rel_err_near_ties"] <= near_ties_tol)
        if kind not in checks["block_fp8_reading"]:
            # as a program in fp8 would leave it: in the stream's dtype
            lowered = blocks[kind](params[name], layer_state, x, True)[0]
            checks["block_fp8_reading"][kind] = _beyond_rounding(
                lowered.astype(got.dtype), want, ~risk, x)
    return checks, ok


def _check(config, init, first, ring, activations, logits, first_loss,
           tied) -> dict:
    """``init`` the seeded parameters and state, ``first`` what the
    first dispatch left, ``activations`` and ``logits`` the train-mode
    forward and ``net.output`` of ring batch 0 on ``init``, ``tied`` the
    ``layer.leaf`` names that have two users. Runs after the window,
    with the net's arrays gone."""
    ref = import_module(config["reference"])
    how = config["reference_kwargs"]
    params, state = init
    batches = [(jnp.asarray(ds.features), jnp.asarray(ds.labels))
               for ds in ring]
    # one program for both precisions: ``lowered`` is traced
    reference = jax.jit(jax.value_and_grad(
        lambda p, lowered, *batch: ref.loss(
            p, state, *batch, with_logits=True, dtype=(FP8, lowered), **how),
        has_aux=True))
    with jax.default_matmul_precision("highest"):
        checks, ok = _check_blocks(ref, how, params, state, activations)
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
        # an expert's slice at a time only where there are experts
        routed = (ref.EXPERT_LEAVES if set(ref.ROUTER_LEAVES)
                  & set(params[name]) else ())
        moment, change = adam([g[name] for g in grads], device_params[name])
        moved = jax.tree_util.tree_map(np.subtract, first["params"][name],
                                       params[name])
        by = jax.device_get({
            "grad": _leaf_errors(first["moment"][name], moment, routed),
            "update": _leaf_errors(moved, change, routed)})
        for leaf in params[name]:
            leaves[f"{name}.{leaf}"] = {
                "grad": float(by["grad"][leaf]),
                "update": float(by["update"][leaf]),
                "grad_fp8": float(fp8[name][leaf]),
                "kind": ("router" if leaf in ref.ROUTER_LEAVES
                         else "experts" if leaf in routed else "plain")}

    def worst(key, kind):
        return max(v[key] for v in leaves.values() if v["kind"] == kind)

    now = checks["first_dispatch"] = {
        "steps": first["steps"],
        "grad_rel_err": worst("grad", "plain"),
        "grad_rel_err_experts": worst("grad", "experts"),
        # the leaves with two users
        "grad_rel_err_tied": max(leaves[k]["grad"] for k in tied),
        "grad_fp8_reading": worst("grad_fp8", "plain"),
        "update_rel_err": worst("update", "plain"),
        "update_rel_err_experts": worst("update", "experts"),
        "leaves": leaves}
    checks["fp8_would_fail"] = bool(
        min(checks["block_fp8_reading"].values()) > ref.BLOCK_RTOL
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


def _kernels_traced(required: dict) -> tuple:
    """The traces counted under each required counter by backend, and
    whether each was traced at its backend and at no other."""
    calls = {metric: _calls_by_backend(metric) for metric in required}
    return calls, all(
        calls[metric].get(backend, 0) > 0 and set(calls[metric]) == {backend}
        for metric, backend in required.items())


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
    # the leaves another layer reads too, by their owner
    tied = sorted({f"{owner}.{leaf}" for layer in net.layers
                   for owner, leaf in (getattr(layer, "shares", None)
                                       or {}).values()})
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
    kernel_calls, kernels = _kernels_traced(config.get("required_kernels",
                                                       {}))

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
        "moe_pairs_per_layer": rows.sum(axis=1).tolist(),
        "moe_expert_rows": rows.reshape(-1).tolist(),
        # which executor was traced for each required kernel
        "kernel_calls_by_backend": kernel_calls,
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
                    warm_losses[0], tied)
    checks["loss"] = {"first": warm_losses[0], "warmup_last": warm_losses[-1],
                      "window_last": float(losses[-1])}
    failed = int(np.sum(~np.isfinite(losses)))
    # like with like: the last loss is held against the first on its own
    # ring batch
    same_batch = warm_losses[(len(losses) - 1) % len(ring)]
    checks["loss"]["first_on_last_batch"] = same_batch
    correct = bool(checks.pop("ok") and not failed
                   and (kernels or jax.default_backend() != "tpu")
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
