"""Runner for traffic of kind ``train_fit_mtp_tokens``: one causal
language-model training job with a multi-token-prediction module on
integer token ids through ``net.fit(iterator)`` with default arguments.

The listener and its barriers, the memory sampler, the profiler, the
net's build and the rates are ``runners/train_fit.py``'s; the ring
iterator over DataSets, the warm-up, the window, the experts' counts
and the leaf-by-leaf comparison are ``runners/train_fit_tokens.py``'s;
the allowance of one unit of the stream's rounding and the counters by
backend are ``runners/train_fit_causal_tokens.py``'s; all imported. What
``train_examples_per_s`` and ``setup_s`` mean is written in
``train_fit.py``. This module brings:

- the ring: ``ring_batches`` batches of ``batch`` sequences of
  ``seq_len + 2`` ids drawn uniformly from the held vocabulary from
  ``--seed``: features the first ``seq_len`` (``int32 [b, L]``), labels
  ``int32 [b, 2, L]``, ids ``1..L`` (the next token of every row) and
  ids ``2..L+1`` (the one after it), no mask (every weight 1). The
  second loss is weighed by the traffic's ``mtp_weight``. Nothing here
  touches the net's parameters;
- ``correct``, against the configuration's reference module on the
  seeded initial parameters and the ring at the timed size
  (``benchmark/reference/glm4_moe_lite.py`` says what is compared and
  why): both losses apart; every block by its kind, dense, expert and
  the module (the module on the program's own last hidden rows); the
  logits of ``net.output`` and the module's; Adam's first moment and
  the first change of every leaf, the shared embedding and head among
  them. The same comparisons are made of the reference against itself
  with every forward product's operands rounded to float8_e4m3fn, and
  have to fail (``fp8_would_fail``). On a TPU every attention and every
  grouped product has to have been traced on its Pallas kernels
  (``dl4j_causal_attention_calls_total``,
  ``dl4j_moe_grouped_matmul_calls_total``).

Traffic parameters: ``batch``, ``seq_len``, ``ring_batches``,
``warmup_steps``, ``trace_seconds``, ``mtp_weight``, ``rehearsal``.
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
    _beyond_rounding, _calls_by_backend)
from benchmark.runners.train_fit_tokens import (
    FP8, _check_logits, _expert_rows, _leaf_errors, _warm_up, _window)
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.observability import metrics as obs
from deeplearning4j_tpu.observability import moe as obs_moe


def make_ring(config: dict, traffic: dict, seed: int) -> list:
    rng = np.random.default_rng(seed)
    seq = traffic["seq_len"]
    ring = []
    for _ in range(traffic["ring_batches"]):
        ids = rng.integers(0, config["kwargs"]["vocab_size"],
                           (traffic["batch"], seq + 2), dtype=np.int32)
        ring.append(DataSet(ids[:, :seq], np.stack(
            [ids[:, 1:seq + 1], ids[:, 2:]], axis=1)))
    return ring


def _forward_check(net, ds) -> dict:
    """What the checks compare of the program on its seeded parameters,
    taken before the first step moves them: every layer's train-mode
    output, ``net.output``, the module's input, output and logits
    through the output layer's own methods, and both losses."""
    out = net.layers[-1]
    activations = net.feed_forward(ds.features, train=True)
    labels = jnp.asarray(ds.labels)

    def module(params, state, h):
        # one program from the last hidden rows on: the layer's own loss
        # (whose state carries both parts) and, for the block check, the
        # module it holds, which the compiler finds in the loss again
        p, s = net._layer_params(out, params), state[out.name]
        given, g, _ = out.module(p, s, h, labels[:, 0])
        _, after = out.loss(p, h, labels, state=s)
        return given, g, out._logits(p, "mtp_norm_g", g), after["mtp_loss"]

    given, g, ahead, losses = jax.jit(module)(net.params, net.state,
                                              activations[-2])
    return jax.device_get({
        "activations": activations, "logits": net.output(ds.features),
        "module": (given, g), "module_logits": ahead, "losses": losses})


def _reading(got, want, risk, given, pair, tol) -> dict:
    # near a tie one pair may go for another: two pairs' worth
    near_ties_tol = tol + 2 * float(pair / jnp.abs(want - given).max())
    reading = {
        "rel_err": _beyond_rounding(got, want, ~risk, given),
        "rel_err_near_ties": _beyond_rounding(got, want, risk, given),
        "near_ties_tol": near_ties_tol,
        "near_ties_share": float(jnp.mean(risk)),
        "finite": bool(np.all(np.isfinite(np.asarray(got, np.float32))))}
    reading["ok"] = bool(
        reading["finite"] and reading["rel_err"] <= tol
        and reading["rel_err_near_ties"] <= near_ties_tol)
    return reading


def _check_blocks(ref, how, params, state, ring, seen) -> tuple:
    """The layers one at a time on the program's own inputs (batch row
    0), the module among them on the program's last hidden rows. Per
    kind, the fp8 reading of the first layer of it."""
    names = sorted(params, key=lambda k: int(k.rsplit("_", 1)[1]))
    blocks = {kind: jax.jit(lambda p, s, x, lowered, kind=kind: ref.block(
        kind, p, s, x, dtype=(FP8, lowered), **how)) for kind in "DE"}
    module = jax.jit(lambda p, s, emb, h, ahead, lowered: ref.module(
        p, s, emb, h, ahead, dtype=(FP8, lowered), **how))
    activations = seen["activations"]
    checks = {"blocks": [], "block_fp8_reading": {}}
    for i, name in enumerate(names[1:], start=1):
        kind = ref.kind_of(params[name])
        x = jnp.asarray(activations[i - 1][0], jnp.float32)
        layer_state = state.get(name, {})
        if kind == "M":
            args = (params[name], layer_state, params[names[0]]["W"], x,
                    jnp.asarray(ring[0].labels[0, 0]))
            given, want, risk, pair = module(*args, False)
            got = seen["module"][1][0]
            lowered = lambda: module(*args, True)[1]
        else:
            args = (params[name], layer_state, x)
            given = x
            want, risk, pair = blocks[kind](*args, False)
            got = activations[i][0]
            lowered = lambda: blocks[kind](*args, True)[0]
        checks["blocks"].append({
            "layer": name, "kind": kind,
            **_reading(got, want, risk, given, pair, ref.BLOCK_RTOL)})
        if kind not in checks["block_fp8_reading"]:
            # as a program in fp8 would leave it: in the stream's dtype
            checks["block_fp8_reading"][kind] = _beyond_rounding(
                lowered().astype(got.dtype), want, ~risk, given)
    return checks, all(b["ok"] for b in checks["blocks"])


def _check(config, traffic, init, first, ring, seen, first_loss) -> dict:
    """``init`` the seeded parameters and state, ``first`` what the
    first dispatch left, ``seen`` what ``_forward_check`` took of ring
    batch 0 on ``init``. Runs after the window, with the net's arrays
    gone."""
    ref = import_module(config["reference"])
    how = config["reference_kwargs"]
    params, state = init
    batches = [(jnp.asarray(ds.features), jnp.asarray(ds.labels))
               for ds in ring]
    # one program for both precisions: ``lowered`` is traced
    reference = jax.jit(jax.value_and_grad(
        lambda p, lowered, *batch: ref.loss(
            p, state, *batch, with_logits=True,
            mtp_weight=traffic["mtp_weight"], dtype=(FP8, lowered), **how),
        has_aux=True))
    with jax.default_matmul_precision("highest"):
        checks, ok = _check_blocks(ref, how, params, state, ring, seen)
        device_params = jax.device_put(params)
        (ref_loss, (parts, want, risk, ahead, risk_ahead)), grad = reference(
            device_params, False, *batches[0])
        (_, (_, lowered, _, lowered_ahead, _)), lowered_grad = reference(
            device_params, True, *batches[0])
        checks["logits"] = _check_logits(seen["logits"][0], want[0], risk[0],
                                         lowered[0])
        checks["module_logits"] = _check_logits(
            seen["module_logits"][0], ahead[0], risk_ahead[0],
            lowered_ahead[0])
        fp8 = jax.device_get(_leaf_errors(lowered_grad, grad))
        del want, lowered, ahead, lowered_ahead, lowered_grad
        grads = [grad] + [reference(device_params, False, *b)[1]
                          for b in batches[1:]]
    parts = [float(v) for v in parts]
    losses = {"system": [float(v) for v in seen["losses"]],
              "reference": parts,
              "rel_err": [train_fit._rel(float(s), r)
                          for s, r in zip(seen["losses"], parts)]}
    rel = train_fit._rel(first_loss, float(ref_loss))
    checks["first_loss"] = {"system": first_loss,
                            "reference": float(ref_loss), "rel_err": rel,
                            "main_and_mtp": losses}

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
        return max(v[key] for k, v in leaves.items()
                   if k.rsplit(".", 1)[1] not in ref.ROUTER_LEAVES
                   and (k.rsplit(".", 1)[1] in ref.EXPERT_LEAVES) == experts)

    names = sorted(params, key=lambda k: int(k.rsplit("_", 1)[1]))
    shared = [f"{names[0]}.W", f"{names[-1]}.W"]
    now = checks["first_dispatch"] = {
        "steps": first["steps"],
        "grad_rel_err": worst("grad", False),
        "grad_rel_err_experts": worst("grad", True),
        # the leaves with two users: the embedding's matrix, the head's
        "grad_rel_err_shared": max(leaves[k]["grad"] for k in shared),
        "grad_fp8_reading": worst("grad_fp8", False),
        "update_rel_err": worst("update", False),
        "update_rel_err_experts": worst("update", True),
        "leaves": leaves}
    checks["fp8_would_fail"] = bool(
        min(checks["block_fp8_reading"].values()) > ref.BLOCK_RTOL
        and checks["logits"]["fp8_reading"] > ref.LOGITS_RTOL
        and checks["module_logits"]["fp8_reading"] > ref.LOGITS_RTOL
        and now["grad_fp8_reading"] > ref.GRAD_RTOL)
    checks["tol"] = {
        "block": ref.BLOCK_RTOL, "logits": ref.LOGITS_RTOL,
        "logits_all_rows": ref.LOGITS_RTOL_ALL_ROWS, "loss": ref.LOSS_RTOL,
        "grad": ref.GRAD_RTOL, "grad_experts": ref.GRAD_RTOL_EXPERTS,
        "update": ref.UPDATE_RTOL}
    checks["ok"] = bool(
        ok and all(
            checks[z]["rel_err"] <= ref.LOGITS_RTOL
            and checks[z]["rel_err_all_rows"] <= ref.LOGITS_RTOL_ALL_ROWS
            for z in ("logits", "module_logits"))
        and max(rel, *losses["rel_err"]) <= ref.LOSS_RTOL
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
    # the traffic's weight of the second loss is the job's
    config["kwargs"]["mtp_weight"] = traffic["mtp_weight"]
    net, _ = train_fit._build(ctx)
    phases["build"] = time.time() - ctx.t0 - sum(phases.values())
    ring = make_ring(config, traffic, ctx.seed)
    phases["ring"] = time.time() - ctx.t0 - sum(phases.values())
    init = jax.device_get((net.params, net.state))
    seen = _forward_check(net, ring[0])
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
    last_parts = obs_moe.mtp_losses(net)

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
        # mean over the window's steps; the last layer is the module's
        "moe_pairs_per_step": float(rows.sum()),
        "moe_pairs_per_layer": rows.sum(axis=1).tolist(),
        "moe_expert_rows": rows.reshape(-1).tolist(),
        "causal_attention_calls_by_backend": _calls_by_backend(
            "dl4j_causal_attention_calls_total"),
        "grouped_matmul_calls_by_backend": _calls_by_backend(
            "dl4j_moe_grouped_matmul_calls_total"),
        "mla_layers_traced": obs.get_registry().snapshot().get(
            "dl4j_mla_layers_traced_total", 0.0),
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
    checks = _check(config, traffic, init, first, ring, seen, warm_losses[0])
    checks["loss"] = {"first": warm_losses[0], "warmup_last": warm_losses[-1],
                      "window_last": float(losses[-1]),
                      "window_last_main_and_mtp": last_parts}
    failed = int(np.sum(~np.isfinite(losses)))
    # like with like: the last loss is held against the first on its own
    # ring batch
    same_batch = warm_losses[(len(losses) - 1) % len(ring)]
    checks["loss"]["first_on_last_batch"] = same_batch

    def all_pallas(calls):
        return calls.get("pallas", 0) > 0 and set(calls) == {"pallas"}

    kernels = jax.default_backend() != "tpu" or (
        all_pallas(counters["causal_attention_calls_by_backend"])
        and all_pallas(counters["grouped_matmul_calls_by_backend"]))
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
