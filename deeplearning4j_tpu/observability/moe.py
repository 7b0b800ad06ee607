"""Routing counts of expert layers, as metrics.

An expert layer (nn/layers/decoder.py) counts the rows a step gave each
expert it holds and returns the counts in the net's state, beside the
score: ``expert_rows`` (the last step's, int32 ``[experts_held]``) and
``expert_rows_total`` (all steps', two int32 limbs). Nothing in the fit
loop reads them.
``install(net)`` (``Trainer.fit`` calls it) registers a collector that
reads them when the registry is scraped, the moment an operator reads
the score gauge too:

- ``dl4j_moe_pairs_total{layer}``: (row, expert) pairs computed here;
- ``dl4j_moe_expert_rows{layer, expert}``: rows of the last step, the
  expert numbered as the router numbers it;
- ``dl4j_mtp_loss{layer, part}``: where the output layer holds a
  multi-token-prediction module (``MtpTokenOutput``, whose own expert
  layer is counted above like any other), the last step's two losses
  apart, ``part`` ``main`` and ``mtp``, unweighted (``mtp_loss`` in that
  layer's state; ``mtp_losses(net)`` reads it);
- where a layer chooses its attention's keys with a learned indexer
  (``SparseMoeBlock``), from that layer's state: ``dl4j_dsa_indexer_kl
  {layer}``, the last step's indexer loss; ``dl4j_dsa_selected_pairs
  {layer}``, the (row, key) pairs its selection kept; and
  ``dl4j_sparse_attention_tiles{layer, kind}``, the causal tile pairs
  the attention kernels ``walked`` and ``skipped`` (none held a kept
  key; where the XLA form ran, one tile a sequence).
  ``sparse_attention(net)`` reads them.
"""

from __future__ import annotations

import weakref

import jax
import numpy as np

from deeplearning4j_tpu.observability.metrics import (
    MetricFamily, get_registry)


def expert_rows(net) -> dict:
    """``{layer: (last step's rows, rows so far)}`` as numpy, for every
    layer of ``net`` that counts them, in the net's order. One host
    read."""
    from deeplearning4j_tpu.nn.layers.decoder import rows_total

    state = {name: s for name, s in (net.state or {}).items()
             if "expert_rows_total" in s}
    return {name: (np.asarray(s["expert_rows"]),
                   rows_total(np.asarray(s["expert_rows_total"])))
            for name, s in jax.device_get(state).items()}


def mtp_losses(net):
    """``(L_main, L_mtp)`` of the last step as floats, or None for a net
    without a multi-token-prediction module."""
    for s in (net.state or {}).values():
        if "mtp_loss" in s:
            return tuple(float(v) for v in np.asarray(s["mtp_loss"]))
    return None


def sparse_attention(net) -> dict:
    """``{layer: {"kl", "pairs", "walked", "skipped"}}`` of the last step
    for every layer of ``net`` that selects its keys, as Python numbers.
    One host read."""
    state = {name: {k: s[k] for k in ("dsa_indexer_kl", "dsa_selected_pairs",
                                      "dsa_tiles")}
             for name, s in (net.state or {}).items()
             if "dsa_indexer_kl" in s}
    return {name: {"kl": float(s["dsa_indexer_kl"]),
                   "pairs": int(s["dsa_selected_pairs"]),
                   "walked": int(s["dsa_tiles"][0]),
                   "skipped": int(s["dsa_tiles"][1])}
            for name, s in jax.device_get(state).items()}


def install(net) -> None:
    """Register the collector for ``net`` once; a net without expert
    layers registers nothing."""
    if getattr(net, "_moe_collector", None) is not None or not any(
            "expert_rows_total" in s for s in (net.state or {}).values()):
        return
    ref = weakref.ref(net)
    first = {layer.name: int(getattr(layer.conf, "first_expert", 0))
             for layer in net.layers}

    def collect():
        live = ref()
        if live is None:
            return []
        pairs = MetricFamily(
            "dl4j_moe_pairs_total", "counter",
            "(row, expert) pairs the experts held here computed, by layer")
        rows = MetricFamily(
            "dl4j_moe_expert_rows", "gauge",
            "Rows the last step gave each expert held here")
        for name, (last, total) in expert_rows(live).items():
            pairs.add(float(total.sum()), {"layer": name})
            for i, n in enumerate(last):
                rows.add(float(n), {"layer": name,
                                    "expert": str(first[name] + i)})
        families = [pairs, rows]
        for name, s in live.state.items():
            if "mtp_loss" in s:
                parts = MetricFamily(
                    "dl4j_mtp_loss", "gauge",
                    "The last step's next-token loss (main) and the "
                    "prediction module's (mtp), unweighted")
                for part, value in zip(("main", "mtp"),
                                       np.asarray(s["mtp_loss"])):
                    parts.add(float(value), {"layer": name, "part": part})
                families.append(parts)
        sparse = sparse_attention(live)
        if sparse:
            kl = MetricFamily(
                "dl4j_dsa_indexer_kl", "gauge",
                "The last step's indexer loss (KL divergence of the "
                "indexer's softmax from the attention's), by layer")
            pairs = MetricFamily(
                "dl4j_dsa_selected_pairs", "gauge",
                "(row, key) pairs the last step's selection kept, by layer")
            tiles = MetricFamily(
                "dl4j_sparse_attention_tiles", "gauge",
                "Causal tile pairs the last step's sparse-attention kernels "
                "walked and skipped, by layer")
            for name, v in sparse.items():
                kl.add(v["kl"], {"layer": name})
                pairs.add(float(v["pairs"]), {"layer": name})
                for kind in ("walked", "skipped"):
                    tiles.add(float(v[kind]), {"layer": name, "kind": kind})
            families += [kl, pairs, tiles]
        return families

    net._moe_collector = get_registry().register_collector(collect)
