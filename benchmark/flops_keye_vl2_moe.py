"""Operations and bytes the sparse-attention decoder's training step
needs (``zoo.keye_vl2_moe``), from shapes (conventions:
``benchmark/flops.py``; one multiply-add is 2 FLOPs, a training step 3
times the forward's products, elementwise work not counted): a
yardstick of the work, whatever implements it.

Every function takes ``(config, traffic, counters=None)``.

A layer's attention counts the SELECTED pairs, ``sum_t min(t + 1,
topk)`` a sequence (14,681,088 at 8,192 rows and a top 2,048, 43.7% of
the 33,558,528 visible), ``4 * head_dim`` FLOPs a pair and head forward
and twice that backward: a kernel that walked every causal tile could
read at most 43.7% of this yardstick. The indexer scores every VISIBLE
pair, ``2 * index_heads * index_head_dim`` FLOPs a pair forward (the
selection needs all of them), and its loss sends a gradient into ``qI``
and ``kI`` through the selected pairs alone, twice that a pair. The
attention's scores that the indexer's loss averages over the heads are
made again there and not counted: a recomputation is no required work.

The routed experts are counted at the expected load in the step's
total, ``rows * experts_per_token * experts_held / n_experts`` pairs a
layer; ``keye_experts`` counts the pairs the run counted.
"""

from __future__ import annotations

from benchmark.flops_sdar_moe import _attention_bytes


def _sizes(config: dict, traffic: dict) -> dict:
    kw = config["kwargs"]
    seq, batch = traffic["seq_len"], traffic["batch"]
    topk = kw["index_topk"]
    return dict(
        kw, seq=seq, batch=batch, rows=seq * batch,
        visible_pairs=batch * seq * (seq + 1) // 2,
        selected_pairs=batch * selected_pairs_a_sequence(seq, topk),
        itemsize=config["compute_itemsize"])


def selected_pairs_a_sequence(seq: int, topk: int) -> int:
    """``sum_t min(t + 1, topk)`` over ``t < seq``."""
    head = min(seq, topk)
    return head * (head + 1) // 2 + (seq - head) * topk


def expected_pairs_a_layer(config: dict, traffic: dict) -> float:
    s = _sizes(config, traffic)
    return (s["rows"] * s["experts_per_token"] * s["experts_held"]
            / s["n_experts"])


def _indexer_macs_a_row(s) -> int:
    """``W_IQ``, ``W_IK`` and ``W_w`` of one layer."""
    n, di = s["index_heads"], s["index_head_dim"]
    return s["hidden"] * (n * di + di + n)


def macs_a_row(config: dict, traffic: dict) -> float:
    """Multiply-adds of the matrix products one row meets on its way
    through the model: the four attention projections, the indexer's
    three, the router and the routed experts at the expected load (one
    expert a row and layer at 8 of 128 with 16 held), and the head."""
    s = _sizes(config, traffic)
    d, f = s["hidden"], s["expert_width"]
    a_layer = (2 * d * s["head_dim"] * (s["n_heads"] + s["n_kv_heads"])
               + _indexer_macs_a_row(s) + d * s["n_experts"]
               + s["experts_per_token"] * s["experts_held"] / s["n_experts"]
               * 3 * d * f)
    return s["n_layers"] * a_layer + d * s["vocab_size"]


def train_step(config: dict, traffic: dict, counters=None) -> dict:
    """One optimizer step: the products, the attention over the selected
    pairs, the indexer's scores of the visible pairs and its gradient
    through the selected ones."""
    s = _sizes(config, traffic)
    n_di = s["index_heads"] * s["index_head_dim"]
    a_layer = (12 * s["head_dim"] * s["n_heads"] * s["selected_pairs"]
               + 2 * n_di * s["visible_pairs"]
               + 4 * n_di * s["selected_pairs"])
    return {"flops": 6 * s["rows"] * macs_a_row(config, traffic)
            + s["n_layers"] * a_layer}


def _words(s) -> int:
    """Bytes of one layer's selection: one bit a (row, key) pair."""
    return s["batch"] * s["seq"] * s["seq"] // 8


def dsa_core_fwd(config: dict, traffic: dict, counters=None) -> dict:
    """All forward attention cores over the selected pairs: reads q, k,
    v and the selection, writes the output and one float32 statistic a
    row and head."""
    s = _sizes(config, traffic)
    return {"flops": s["n_layers"] * 4 * s["head_dim"] * s["n_heads"]
            * s["selected_pairs"],
            "bytes": s["n_layers"] * (_attention_bytes(s, 2, 2, 1)
                                      + _words(s))}


def dsa_core_bwd(config: dict, traffic: dict, counters=None) -> dict:
    """All backward attention cores over the selected pairs: reads q, k,
    v, the output, its gradient, the statistic and the selection,
    writes dq, dk, dv."""
    s = _sizes(config, traffic)
    return {"flops": s["n_layers"] * 8 * s["head_dim"] * s["n_heads"]
            * s["selected_pairs"],
            "bytes": s["n_layers"] * (_attention_bytes(s, 4, 4, 1)
                                      + _words(s))}


def dsa_indexer(config: dict, traffic: dict, counters=None) -> dict:
    """Every layer's selection: the indexer's scores of the visible
    pairs; reads qI, kI (the compute dtype) and w (float32), writes the
    selection and one float32 log-sum-exp a row."""
    s = _sizes(config, traffic)
    n, di = s["index_heads"], s["index_head_dim"]
    return {"flops": s["n_layers"] * 2 * n * di * s["visible_pairs"],
            "bytes": s["n_layers"] * (
                s["rows"] * ((n * di + di) * s["itemsize"] + 4 * n + 4)
                + _words(s))}


def keye_experts(config: dict, traffic: dict, counters=None):
    """The nine grouped products of a step (three forward, six backward)
    over the pairs the run counted (``moe_pairs_per_step``, all layers),
    None without a count. Bytes: every held expert's three matrices read
    in the compute dtype forward and backward and their float32
    gradients written; a pair's row read forward, row and output
    gradient read backward, and a float32 row added forward (the result)
    and backward (the input gradient)."""
    pairs = (counters or {}).get("moe_pairs_per_step")
    if not pairs:
        return None
    s = _sizes(config, traffic)
    weights = (s["n_layers"] * s["experts_held"] * 3 * s["hidden"]
               * s["expert_width"])
    return {
        "flops": 6 * pairs * 3 * s["hidden"] * s["expert_width"],
        "bytes": weights * (2 * s["itemsize"] + 4)
        + pairs * s["hidden"] * (3 * s["itemsize"] + 2 * 4)}
