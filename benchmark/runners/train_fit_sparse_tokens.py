"""Runner for traffic of kind ``train_fit_sparse_tokens``: one causal
language-model training job on integer token ids through
``net.fit(iterator)`` with default arguments, for a decoder whose
attention takes, for every row, the keys a learned indexer selects, and
whose indexer trains on a loss of its own beside the data loss.

The ring, the blocks' check and the required kernels by backend are
``runners/train_fit_decoder_tokens.py``'s, the warm-up, the window and
the leaf-by-leaf comparison ``runners/train_fit_tokens.py``'s, all
imported; ``run`` is ``train_fit_decoder_tokens.run`` with the
selections taken before the first step and the indexers' counts beside
the result. What ``train_examples_per_s`` and ``setup_s`` mean is
written in ``train_fit.py``. This module brings the check against the
configuration's reference (``benchmark/reference/keye_vl2_moe.py`` says
what is compared and why), on the seeded initial parameters and the
ring at the timed size:

- the selection: what every sparse layer chose in the net's own
  train-mode forward of each ring batch (``net._forward``, traced with
  the layers' ``hands_back_selection`` set, before the first step; the
  timed step never hands it out), held to the reference's scores on
  that forward's own input to the layer (``check_selection``), beside
  what the check has to refuse, read on the first layer: a selection at
  random, one by position, and the reference's own with its indexer
  rounded to float8_e4m3fn; the reference then follows the program's
  selection downstream, so the limits below compare the same sums;
- how far the selection moves over the first dispatch (the same forward
  on the parameters it leaves, ring batch 0: keys swapped a row), and
  what that moves in the reference's gradient of every leaf
  (``grad_selection_moved``): the steps after the first choose on moved
  parameters, which the reference, computing its gradients on the
  seeded ones, cannot follow, so a leaf outside the experts, the router
  and the indexer is held to ``GRAD_RTOL`` beyond that move;
- the first loss, and apart its two parts, the cross-entropy and the
  indexers' losses summed (the program's parts from its layers' own
  state on ring batch 0); every block on the program's own input; the
  logits of ``net.output``; Adam's first moment and the first change of
  every leaf, the indexer's five leaves as a kind of their own. The
  same comparisons are made of the reference against itself with every
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
from benchmark.runners.train_fit_decoder_tokens import (
    _check_blocks, _index, _kernels_traced, make_ring)
from benchmark.runners.train_fit_tokens import (
    FP8, _check_logits, _expert_rows, _leaf_errors, _warm_up, _window)
from deeplearning4j_tpu.observability import metrics as obs
from deeplearning4j_tpu.observability import moe as obs_moe

__all__ = ["make_ring", "run"]


def _sparse_layers(net) -> list:
    return [layer for layer in net.layers
            if hasattr(layer, "hands_back_selection")]


def _check_forward(net):
    """The check's train-mode forward: (parameters, a ring batch) ->
    (every layer's activation, {layer: (the selection's words [b, L/32,
    L], the indexer's loss)} of every sparse layer), as the layers make
    them on the net's own path, one program for both, so that a
    selection is held to the scores of the very input it was made from.
    The layers hand the selection back only in this program: the flag is
    set while it is traced."""
    layers = _sparse_layers(net)

    def forward(params, state, x):
        acts, new = net._forward(params, state, x, train=True, rng=None,
                                 collect=True)
        return acts, {layer.name: (new[layer.name]["selection"],
                                   new[layer.name]["dsa_indexer_kl"])
                      for layer in layers}

    forward = jax.jit(forward)

    def call(params, ds, activations=False):
        for layer in layers:
            layer.hands_back_selection = True
        try:
            acts, chosen = forward(params, net.state,
                                   jnp.asarray(ds.features))
        finally:
            for layer in layers:
                layer.hands_back_selection = False
        return jax.device_get((acts, chosen) if activations else chosen)

    return call


def _swaps_a_row(words, other) -> float:
    """Keys kept by one selection and not the other, a row."""
    return float(np.sum(np.unpackbits(np.asarray(
        np.bitwise_xor(words, other)).view(np.uint8)))) / 2 / words.shape[-1]


def _check_selections(ref, how, params, activations, words, moved) -> dict:
    """Every sparse layer's selection of batch row 0 held to the
    reference's scores on the program's input, and how many keys a row
    the first dispatch moved it by (``moved``); on the first layer, the
    selections the check has to refuse."""
    check = jax.jit(lambda p, x, w: ref.check_selection(p, x, w, **how))
    topk = how["topk"]

    def held(name, w):
        x = jnp.asarray(activations[_index(name) - 1][0], jnp.float32)
        return jax.device_get(check(params[name], x, jnp.asarray(w)))

    readings = []
    for name in sorted(words, key=_index):
        h = held(name, words[name][0])
        readings.append({
            "layer": name,
            "rows_count_ok": bool(np.all(h["rows_count_ok"])),
            "worst": float(np.max(h["worst"])),
            "swaps_per_row": float(np.mean(h["swaps"])),
            "rows_with_a_swap": float(np.mean(h["swaps"] > 0)),
            "moved_by_first_dispatch": _swaps_a_row(words[name][0],
                                                    moved[name][0])})
    first = readings[0]["layer"]
    length = activations[0].shape[1]
    fp8 = jax.jit(lambda p, x: ref.selection_of(p, x, dtype=FP8, **how))(
        params[first], jnp.asarray(activations[_index(first) - 1][0],
                                   jnp.float32))
    refusals = {
        "fp8_indexer": fp8,
        "at_random": jax.jit(lambda: ref.at_random(jax.random.PRNGKey(0),
                                                   length, topk))(),
        "by_position": jax.jit(lambda: ref.by_position(length, topk))()}
    refused = {}
    for kind, w in refusals.items():
        h = held(first, w)
        refused[kind] = float(np.max(h["worst"])) if np.all(
            h["rows_count_ok"]) else float("inf")
    return {"layers": readings, "delta_units": ref.SELECT_UNITS,
            "refused_on_first_layer": refused,
            "ok": all(r["rows_count_ok"] and r["worst"] <= 1.0
                      for r in readings)}


def _routed(ref, p) -> tuple:
    """The leaves of a layer's parameters ``p`` read a held expert's
    slice at a time."""
    return ref.EXPERT_LEAVES if set(ref.ROUTER_LEAVES) & set(p) else ()


def _check(config, init, first, ring, activations, logits, first_loss,
           tied, chosen, moved) -> dict:
    """As ``train_fit_decoder_tokens._check``, the reference following
    the program's selections (``chosen``: the check's forward of each
    ring batch), with the selection's own check, its move over the first
    dispatch (``moved``: the same forward on the parameters it leaves,
    ring batch 0) and the loss's parts besides."""
    ref = import_module(config["reference"])
    how = config["reference_kwargs"]
    params, state = init

    def following(words):
        return {name: {**s, "selection": words[name]} if name in words
                else s for name, s in state.items()}

    selections = [{n: w for n, (w, _) in c.items()} for c in chosen]
    index_loss = float(sum(kl for _, kl in chosen[0].values()))
    batches = [(jnp.asarray(ds.features), jnp.asarray(ds.labels))
               for ds in ring]
    reference = jax.jit(jax.value_and_grad(
        lambda p, s, lowered, *batch: ref.loss(
            p, s, *batch, with_logits=True, dtype=(FP8, lowered), **how),
        has_aux=True))
    row0 = following({n: w[0] for n, w in selections[0].items()})
    with jax.default_matmul_precision("highest"):
        selection = _check_selections(
            ref, how, params, activations, selections[0],
            {n: w for n, (w, _) in moved.items()})
        checks, ok = _check_blocks(ref, how, params, row0, activations)
        device_params = jax.device_put(params)
        s0 = following(selections[0])
        (ref_loss, (want, risk, parts)), grad = reference(
            device_params, s0, False, *batches[0])
        (_, (lowered, _, _)), lowered_grad = reference(
            device_params, s0, True, *batches[0])
        checks["logits"] = _check_logits(logits[0], want[0], risk[0],
                                         lowered[0])
        fp8 = jax.device_get(_leaf_errors(lowered_grad, grad))
        del want, lowered, lowered_grad
        drift = reference(device_params, following(
            {n: w for n, (w, _) in moved.items()}), False, *batches[0])[1]
        drift = jax.device_get({
            name: _leaf_errors(drift[name], grad[name], _routed(ref, p))
            for name, p in params.items()})
        grads = [grad] + [reference(device_params, following(words), False,
                                    *b)[1]
                          for words, b in zip(selections[1:], batches[1:])]
    checks["selection"] = selection
    rel = train_fit._rel(first_loss, float(ref_loss))
    parts = [float(v) for v in parts]
    system = [first_loss - index_loss, index_loss]
    checks["first_loss"] = {
        "system": first_loss, "reference": float(ref_loss), "rel_err": rel,
        "lm_and_indexer": {"system": system, "reference": parts,
                           "rel_err": [train_fit._rel(s, r) for s, r in
                                       zip(system, parts)]}}

    adam = jax.jit(lambda g, p: ref.adam(g, p, first["steps"],
                                         **config["reference_updater"]))
    leaves = {}
    for name in params:
        routed = _routed(ref, params[name])
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
                "grad_selection_moved": float(drift[name][leaf]),
                "kind": ("router" if leaf in ref.ROUTER_LEAVES and routed
                         else "experts" if leaf in routed
                         else "indexer" if leaf in ref.INDEXER_LEAVES
                         else "plain")}

    def worst(key, kind):
        return max(v[key] for v in leaves.values() if v["kind"] == kind)

    def beyond_move(key):
        return max(v[key] - v["grad_selection_moved"]
                   for v in leaves.values() if v["kind"] == "plain")

    now = checks["first_dispatch"] = {
        "steps": first["steps"],
        "grad_rel_err": worst("grad", "plain"),
        "grad_rel_err_beyond_selection_move": beyond_move("grad"),
        "grad_rel_err_experts": worst("grad", "experts"),
        "grad_rel_err_indexer": worst("grad", "indexer"),
        "grad_rel_err_router": worst("grad", "router"),
        "grad_fp8_reading": worst("grad_fp8", "plain"),
        "grad_fp8_reading_beyond_selection_move": beyond_move("grad_fp8"),
        "grad_fp8_reading_indexer": worst("grad_fp8", "indexer"),
        "grad_selection_moved_reading": worst("grad_selection_moved",
                                              "plain"),
        "grad_selection_moved_reading_indexer": worst(
            "grad_selection_moved", "indexer"),
        "update_rel_err": worst("update", "plain"),
        "update_rel_err_experts": worst("update", "experts"),
        "update_rel_err_indexer": worst("update", "indexer"),
        "leaves": leaves}
    if tied:
        now["grad_rel_err_tied"] = max(leaves[k]["grad"] for k in tied)
    checks["fp8_would_fail"] = bool(
        min(checks["block_fp8_reading"].values()) > ref.BLOCK_RTOL
        and checks["logits"]["fp8_reading"] > ref.LOGITS_RTOL
        and now["grad_fp8_reading_beyond_selection_move"] > ref.GRAD_RTOL
        and selection["refused_on_first_layer"]["fp8_indexer"] > 1.0)
    checks["tol"] = {
        "block": ref.BLOCK_RTOL, "logits": ref.LOGITS_RTOL,
        "logits_all_rows": ref.LOGITS_RTOL_ALL_ROWS, "loss": ref.LOSS_RTOL,
        "grad": ref.GRAD_RTOL, "grad_experts": ref.GRAD_RTOL_EXPERTS,
        "grad_indexer": ref.GRAD_RTOL_INDEXER, "update": ref.UPDATE_RTOL,
        "selection_worst": 1.0}
    checks["ok"] = bool(
        ok and selection["ok"]
        and checks["logits"]["rel_err"] <= ref.LOGITS_RTOL
        and checks["logits"]["rel_err_all_rows"] <= ref.LOGITS_RTOL_ALL_ROWS
        and rel <= ref.LOSS_RTOL
        and all(r <= ref.LOSS_RTOL for r in
                checks["first_loss"]["lm_and_indexer"]["rel_err"])
        and now["grad_rel_err_beyond_selection_move"] <= ref.GRAD_RTOL
        and now["grad_rel_err_experts"] <= ref.GRAD_RTOL_EXPERTS
        and now["grad_rel_err_indexer"] <= ref.GRAD_RTOL_INDEXER
        and max(now["update_rel_err"], now["update_rel_err_experts"],
                now["update_rel_err_indexer"]) <= ref.UPDATE_RTOL)
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
    tied = sorted({f"{owner}.{leaf}" for layer in net.layers
                   for owner, leaf in (getattr(layer, "shares", None)
                                       or {}).values()})
    # what the checks compare, taken before the first step moves the
    # parameters
    init = jax.device_get((net.params, net.state))
    forward = _check_forward(net)
    activations, chosen = forward(net.params, ring[0], activations=True)
    chosen = [chosen] + [forward(net.params, ds) for ds in ring[1:]]
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
        moved = forward(net.params, ring[0])
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
    sparse = obs_moe.sparse_attention(net)

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
        # the last step's selection and the kernels' walk, by layer
        "dsa_selected_pairs": {n: v["pairs"] for n, v in sparse.items()},
        "dsa_tiles_walked_skipped": {n: [v["walked"], v["skipped"]]
                                     for n, v in sparse.items()},
        "dsa_indexer_kl": {n: v["kl"] for n, v in sparse.items()},
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
                    warm_losses[0], tied, chosen, moved)
    last_index = sum(v["kl"] for v in sparse.values())
    checks["loss"] = {"first": warm_losses[0], "warmup_last": warm_losses[-1],
                      "window_last": float(losses[-1]),
                      "window_last_lm_and_indexer": [
                          float(losses[-1]) - last_index, last_index]}
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
