"""Operations and bytes the latent-attention decoder's training step
needs (``zoo.glm4_moe_lite``), from shapes (conventions:
``benchmark/flops.py``; one multiply-add is 2 FLOPs, a training step 3
times the forward's products, elementwise work not counted): a yardstick
of the work, whatever implements it.

Every function takes ``(config, traffic, counters=None)``.

A latent-attention layer is five products a row (the two compressions,
the two expansions, the output) and a core that counts the visible pairs
and nothing else: ``L (L + 1) / 2`` a sequence and head, ``4 * (nope +
rope)`` FLOPs a pair forward (scores and values, values as wide as the
queries) and twice that backward. The model has ``n_layers`` of them and
the prediction module one more; the module also has ``eh_proj`` and the
head a second time.

The routed experts are counted at the expected load in the step's total,
``rows * experts_per_token * experts_held / n_experts`` pairs a layer,
and at the pairs the run counted in their own roofline
(``gated_experts``).
"""

from __future__ import annotations

from benchmark.flops_sdar_moe import _attention_bytes


def _sizes(config: dict, traffic: dict) -> dict:
    kw = config["kwargs"]
    seq, batch = traffic["seq_len"], traffic["batch"]
    modules = kw["mtp_modules"]
    head_dim = kw["nope_dim"] + kw["rope_dim"]
    return dict(
        kw, seq=seq, batch=batch, rows=seq * batch, head_dim=head_dim,
        n_kv_heads=kw["n_heads"],               # every head its own keys
        attention_layers=kw["n_layers"] + modules,
        expert_layers=kw["n_layers"] - kw["first_dense"] + modules,
        visible_pairs=batch * seq * (seq + 1) // 2,
        itemsize=config["compute_itemsize"])


def expected_pairs_a_layer(config: dict, traffic: dict) -> float:
    s = _sizes(config, traffic)
    return (s["rows"] * s["experts_per_token"] * s["experts_held"]
            / s["n_experts"])


def _projection_macs_a_row(s) -> int:
    d, h = s["hidden"], s["n_heads"]
    return (d * s["q_rank"] + s["q_rank"] * h * s["head_dim"]
            + d * (s["kv_rank"] + s["rope_dim"])
            + s["kv_rank"] * h * (s["nope_dim"] + s["v_dim"])
            + h * s["v_dim"] * d)


def macs_a_row(config: dict, traffic: dict) -> float:
    """Multiply-adds of the matrix products one row meets on its way
    through the model and the module, the routed experts at the expected
    load (half an expert a row at 4 of 64 with 8 held)."""
    s = _sizes(config, traffic)
    d, f = s["hidden"], s["expert_width"]
    an_expert_layer = (
        d * s["n_experts"] + 3 * d * s["shared_width"]
        + s["experts_per_token"] * s["experts_held"] / s["n_experts"]
        * 3 * d * f)
    return (s["attention_layers"] * _projection_macs_a_row(s)
            + s["first_dense"] * 3 * d * s["mlp_width"]
            + s["expert_layers"] * an_expert_layer
            + s["mtp_modules"] * 2 * d * d                  # eh_proj
            + (1 + s["mtp_modules"]) * d * s["vocab_size"])


def train_step(config: dict, traffic: dict, counters=None) -> dict:
    """One optimizer step, the routed experts at the expected load."""
    s = _sizes(config, traffic)
    cores = (s["attention_layers"] * 12 * s["head_dim"] * s["n_heads"]
             * s["visible_pairs"])
    return {"flops": 6 * s["rows"] * macs_a_row(config, traffic) + cores}


def mla_core_fwd(config: dict, traffic: dict, counters=None) -> dict:
    """All forward attention cores: reads q, k, v, writes the output and
    one float32 statistic a row and head."""
    s = _sizes(config, traffic)
    return {"flops": s["attention_layers"] * 4 * s["head_dim"]
            * s["n_heads"] * s["visible_pairs"],
            "bytes": s["attention_layers"] * _attention_bytes(s, 2, 2, 1)}


def mla_core_bwd(config: dict, traffic: dict, counters=None) -> dict:
    """All backward attention cores: reads q, k, v, the output, its
    gradient and the statistic, writes dq, dk, dv."""
    s = _sizes(config, traffic)
    return {"flops": s["attention_layers"] * 8 * s["head_dim"]
            * s["n_heads"] * s["visible_pairs"],
            "bytes": s["attention_layers"] * _attention_bytes(s, 4, 4, 1)}


def mla_projections(config: dict, traffic: dict, counters=None) -> dict:
    """The five products of every latent-attention layer, forward and
    backward. Bytes: the five matrices read in the compute dtype forward
    and backward and their float32 gradients written; every product's
    input row read and output row written forward, and twice that
    backward (the row and the output's gradient read for the matrix's
    gradient, the input's gradient written)."""
    s = _sizes(config, traffic)
    d, h = s["hidden"], s["n_heads"]
    a_row = (2 * d + 2 * s["q_rank"] + 2 * s["kv_rank"] + s["rope_dim"]
             + h * s["head_dim"] + h * (s["nope_dim"] + s["v_dim"])
             + h * s["v_dim"] + d)
    return {
        "flops": 6 * s["attention_layers"] * s["rows"]
        * _projection_macs_a_row(s),
        "bytes": s["attention_layers"] * (
            _projection_macs_a_row(s) * (2 * s["itemsize"] + 4)
            + 3 * s["rows"] * a_row * s["itemsize"])}


def gated_experts(config: dict, traffic: dict, counters=None):
    """The nine grouped products of a step (three forward, six backward)
    over the pairs the run counted (``moe_pairs_per_step``, the module's
    layer among them), None without a count. Bytes: every held expert's
    three matrices read in the compute dtype forward and backward and
    their float32 gradients written; a pair's row read forward, row and
    output gradient read backward, and a float32 row added forward (the
    result) and backward (the input gradient). The shared expert is not
    here: it is three plain products under its own scope."""
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
