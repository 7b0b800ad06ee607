"""Operations and bytes the short-convolution / attention decoder's
training step needs (``zoo.lfm2_moe``), from shapes (conventions:
``benchmark/flops.py``; one multiply-add is 2 FLOPs, a training step 3
times the forward's products, elementwise work not counted in the step's
total): a yardstick of the work, whatever implements it.

Every function takes ``(config, traffic, counters=None)``.

A short-convolution operator is two products a row (``W_in`` to ``3 d``
columns, ``W_out``) and, between them, the gate, the filter and the
gate: bound by bytes, so its own roofline counts its streams (``B``,
``C``, ``x~`` read and ``y`` written forward, 4 a column; the four
again, ``dy`` for ``y``, and the three gradients written backward, 7)
in the compute dtype, and ``2 K + 2`` FLOPs a column forward (``K``
multiply-adds and two gates) and twice that backward. An attention
operator is four products a row and a core that counts the visible
pairs and nothing else: ``L (L + 1) / 2`` a sequence and head, ``4 *
head_dim`` FLOPs a pair forward and twice that backward.

The routed experts are counted at the expected load in the step's total,
``rows * experts_per_token * experts_held / n_experts`` pairs a layer,
and at the pairs the run counted in their own roofline
(``lfm2_experts``).
"""

from __future__ import annotations

from benchmark.flops_sdar_moe import _attention_bytes


def _sizes(config: dict, traffic: dict) -> dict:
    kw = config["kwargs"]
    seq, batch = traffic["seq_len"], traffic["batch"]
    pattern, n_dense = kw["pattern"], kw["n_dense"]
    return dict(
        kw, seq=seq, batch=batch, rows=seq * batch,
        conv_layers=pattern.count("c"), attention_layers=pattern.count("a"),
        dense_layers=n_dense, expert_layers=len(pattern) - n_dense,
        visible_pairs=batch * seq * (seq + 1) // 2,
        itemsize=config["compute_itemsize"])


def expected_pairs_a_layer(config: dict, traffic: dict) -> float:
    s = _sizes(config, traffic)
    return (s["rows"] * s["experts_per_token"] * s["experts_held"]
            / s["n_experts"])


def _operator_macs_a_row(s) -> int:
    """``W_in`` and ``W_out`` of one short-convolution operator."""
    return 4 * s["hidden"] * s["hidden"]


def _attention_macs_a_row(s) -> int:
    """``W_q``, ``W_k``, ``W_v`` and ``W_o`` of one attention operator."""
    return 2 * s["hidden"] * s["head_dim"] * (s["n_heads"] + s["n_kv_heads"])


def macs_a_row(config: dict, traffic: dict) -> float:
    """Multiply-adds of the matrix products one row meets on its way
    through the model, the routed experts at the expected load (half an
    expert a row and layer at 4 of 64 with 8 held), the tied head once
    (the embedding's side is a gather)."""
    s = _sizes(config, traffic)
    d, f = s["hidden"], s["expert_width"]
    an_expert_layer = (
        d * s["n_experts"]
        + s["experts_per_token"] * s["experts_held"] / s["n_experts"]
        * 3 * d * f)
    return (s["conv_layers"] * _operator_macs_a_row(s)
            + s["attention_layers"] * _attention_macs_a_row(s)
            + s["dense_layers"] * 3 * d * s["mlp_width"]
            + s["expert_layers"] * an_expert_layer
            + d * s["vocab_size"])


def train_step(config: dict, traffic: dict, counters=None) -> dict:
    """One optimizer step, the routed experts at the expected load."""
    s = _sizes(config, traffic)
    cores = (s["attention_layers"] * 12 * s["head_dim"] * s["n_heads"]
             * s["visible_pairs"])
    return {"flops": 6 * s["rows"] * macs_a_row(config, traffic) + cores}


def _conv_columns(s) -> int:
    return s["conv_layers"] * s["rows"] * s["hidden"]


def shortconv_fwd(config: dict, traffic: dict, counters=None) -> dict:
    """All forward gated short convolutions: reads ``B``, ``C``, ``x~``,
    writes ``y``."""
    s = _sizes(config, traffic)
    return {"flops": _conv_columns(s) * (2 * s["conv_kernel"] + 2),
            "bytes": _conv_columns(s) * 4 * s["itemsize"]}


def shortconv_bwd(config: dict, traffic: dict, counters=None) -> dict:
    """All backward gated short convolutions: reads ``B``, ``C``, ``x~``
    and ``dy``, writes the three gradients (the filter's is ``K`` numbers
    a column, nothing beside them)."""
    s = _sizes(config, traffic)
    return {"flops": _conv_columns(s) * 2 * (2 * s["conv_kernel"] + 2),
            "bytes": _conv_columns(s) * 7 * s["itemsize"]}


def shortconv_projections(config: dict, traffic: dict,
                          counters=None) -> dict:
    """``W_in`` and ``W_out`` of every short-convolution operator,
    forward and backward. Bytes: both matrices read in the compute dtype
    forward and backward and their float32 gradients written; every
    product's input row read and output row written forward (``d`` and
    ``3 d``, ``d`` and ``d``), and twice that backward."""
    s = _sizes(config, traffic)
    return {
        "flops": 6 * s["conv_layers"] * s["rows"] * _operator_macs_a_row(s),
        "bytes": s["conv_layers"] * (
            _operator_macs_a_row(s) * (2 * s["itemsize"] + 4)
            + 3 * s["rows"] * 6 * s["hidden"] * s["itemsize"])}


def gqa64_core_fwd(config: dict, traffic: dict, counters=None) -> dict:
    """All forward attention cores: reads q, k, v, writes the output and
    one float32 statistic a row and head."""
    s = _sizes(config, traffic)
    return {"flops": s["attention_layers"] * 4 * s["head_dim"]
            * s["n_heads"] * s["visible_pairs"],
            "bytes": s["attention_layers"] * _attention_bytes(s, 2, 2, 1)}


def gqa64_core_bwd(config: dict, traffic: dict, counters=None) -> dict:
    """All backward attention cores: reads q, k, v, the output, its
    gradient and the statistic, writes dq, dk, dv."""
    s = _sizes(config, traffic)
    return {"flops": s["attention_layers"] * 8 * s["head_dim"]
            * s["n_heads"] * s["visible_pairs"],
            "bytes": s["attention_layers"] * _attention_bytes(s, 4, 4, 1)}


def lfm2_experts(config: dict, traffic: dict, counters=None):
    """The nine grouped products of a step (three forward, six backward)
    over the pairs the run counted (``moe_pairs_per_step``), None without
    a count. Bytes: every held expert's three matrices read in the
    compute dtype forward and backward and their float32 gradients
    written; a pair's row read forward, row and output gradient read
    backward, and a float32 row added forward (the result) and backward
    (the input gradient)."""
    pairs = (counters or {}).get("moe_pairs_per_step")
    if not pairs:
        return None
    s = _sizes(config, traffic)
    weights = (s["expert_layers"] * s["experts_held"] * 3 * s["hidden"]
               * s["expert_width"])
    return {
        "flops": 6 * pairs * 3 * s["hidden"] * s["expert_width"],
        "bytes": weights * (2 * s["itemsize"] + 4)
        + pairs * s["hidden"] * (3 * s["itemsize"] + 2 * 4)}
