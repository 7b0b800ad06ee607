"""Operations and bytes the SDAR decoder's training step needs, from
shapes (conventions: ``benchmark/flops.py``; one multiply-add is 2
FLOPs, a training step 3 times the forward's products, elementwise work
not counted).

Every function takes ``(config, traffic, counters=None)``: the two data
files of a cell and, where the work depends on the data, what the run
counted.

Attention counts the pairs the block-diffusion mask allows and nothing
else: of a sequence's ``(2L)^2`` (row, key) pairs ``L * B + L^2`` are
visible (a noised row sees its own block, ``B`` keys, and the clean
blocks before it; a clean row the clean blocks up to its own; summed,
``L * B + L^2``). A pair costs one head ``4 * head_dim`` FLOPs forward
(score and weighted value) and ``8 * head_dim`` backward (dV, dP, dQ,
dK); recomputing the score in the backward is not required work.

The experts' work depends on the routing. ``train_step`` counts them at
the expected load, ``rows * experts_per_token * experts_held /
num_experts`` pairs a layer (uniform ids and a seeded router come within
a few percent of it); ``moe_experts`` counts the pairs the run counted.
"""

from __future__ import annotations


def _sizes(config: dict, traffic: dict) -> dict:
    kw = config["kwargs"]
    seq, batch = traffic["seq_len"], traffic["batch"]
    return dict(
        kw, seq=seq, batch=batch, rows=2 * seq * batch,
        visible_pairs=batch * (seq * kw["block_len"] + seq * seq),
        itemsize=config["compute_itemsize"])


def _projection_macs(s) -> int:
    q = s["n_heads"] * s["head_dim"]
    kv = s["n_kv_heads"] * s["head_dim"]
    return s["rows"] * s["hidden"] * (2 * q + 2 * kv)


def _expert_macs(s, pairs_a_layer: float) -> float:
    return pairs_a_layer * 3 * s["hidden"] * s["expert_width"]


def expected_pairs_a_layer(config: dict, traffic: dict) -> float:
    s = _sizes(config, traffic)
    return (s["rows"] * s["experts_per_token"] * s["experts_held"]
            / s["n_experts"])


def train_step(config: dict, traffic: dict, counters=None) -> dict:
    """One optimizer step, the experts at the expected load."""
    s = _sizes(config, traffic)
    layer_macs = (_projection_macs(s)
                  + s["rows"] * s["hidden"] * s["n_experts"]      # router
                  + _expert_macs(s, expected_pairs_a_layer(config, traffic)))
    head_macs = s["batch"] * s["seq"] * s["hidden"] * s["vocab_size"]
    attention = 12 * s["head_dim"] * s["n_heads"] * s["visible_pairs"]
    return {"flops": s["n_layers"] * (6 * layer_macs + attention)
            + 6 * head_macs}


def _attention_bytes(s, query_shaped: int, key_shaped: int,
                     row_stats: int) -> int:
    per_row = (query_shaped * s["n_heads"] + key_shaped * s["n_kv_heads"]
               ) * s["head_dim"] * s["itemsize"]
    return s["rows"] * (per_row + row_stats * s["n_heads"] * 4)


def block_attention_fwd(config: dict, traffic: dict, counters=None) -> dict:
    """All forward attention calls of a step: reads q, k, v, writes the
    output and one float32 statistic a row and head."""
    s = _sizes(config, traffic)
    return {
        "flops": s["n_layers"] * 4 * s["head_dim"] * s["n_heads"]
        * s["visible_pairs"],
        "bytes": s["n_layers"] * _attention_bytes(s, 2, 2, 1)}


def block_attention_bwd(config: dict, traffic: dict, counters=None) -> dict:
    """All backward attention calls: reads q, k, v, the output and its
    gradient and the statistic, writes dq, dk, dv."""
    s = _sizes(config, traffic)
    return {
        "flops": s["n_layers"] * 8 * s["head_dim"] * s["n_heads"]
        * s["visible_pairs"],
        "bytes": s["n_layers"] * _attention_bytes(s, 4, 4, 1)}


def moe_experts(config: dict, traffic: dict, counters=None):
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
        "flops": 6 * _expert_macs(s, pairs),
        "bytes": weights * (2 * s["itemsize"] + 4)
        + pairs * s["hidden"] * (3 * s["itemsize"] + 2 * 4)}
