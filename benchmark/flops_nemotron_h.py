"""Operations and bytes the hybrid decoder's training step needs
(``zoo.nemotron_h``), from shapes (conventions: ``benchmark/flops.py``;
one multiply-add is 2 FLOPs, a training step 3 times the forward's
products, elementwise work not counted).

Every function takes ``(config, traffic, counters=None)``.

The recurrence is counted in its chunked form, whatever implements it (a
later kernel is read on this yardstick): a position of a chunk of ``Q``
costs ``G Q N`` multiply-adds for ``C B^T``, ``H Q P`` for the product
inside the chunk, ``H P N`` for the chunk's state and ``H P N`` for what
the past adds, 3.4 MFLOP a position and layer at the published sizes
(all ``Q`` keys of a chunk, not the visible half: the form multiplies
the whole tile), and twice that backward. Its bytes are what the op
must move whoever writes it: ``x``, ``B``, ``C`` in the compute dtype
and ``dt`` in float32 read, ``y`` written, and the float32 state of
every chunk boundary written once and read once; backward, the same
arguments and ``dy`` read, the four gradients written, the boundary
states read and their gradients written and read.

Attention counts the visible pairs and nothing else: ``L (L + 1) / 2`` a
sequence and head, ``4 * head_dim`` FLOPs a pair forward and ``8 *
head_dim`` backward.

The routed experts are counted at the expected load in the step's
total, ``rows * experts_per_token * experts_held / n_experts`` pairs a
layer, and at the pairs the run counted in their own roofline
(``routed_experts``).
"""

from __future__ import annotations

from benchmark.flops_sdar_moe import _attention_bytes


def _sizes(config: dict, traffic: dict) -> dict:
    kw = config["kwargs"]
    seq, batch = traffic["seq_len"], traffic["batch"]
    return dict(
        kw, seq=seq, batch=batch, rows=seq * batch,
        inner=kw["mamba_heads"] * kw["mamba_head_dim"],
        bc=kw["n_groups"] * kw["state_size"],
        layers={kind: kw["pattern"].count(kind) for kind in "ME*"},
        visible_pairs=batch * seq * (seq + 1) // 2,
        itemsize=config["compute_itemsize"])


def expected_pairs_a_layer(config: dict, traffic: dict) -> float:
    s = _sizes(config, traffic)
    return (s["rows"] * s["experts_per_token"] * s["experts_held"]
            / s["n_experts"])


def _scan_macs_a_row(s) -> int:
    h, p, n = s["mamba_heads"], s["mamba_head_dim"], s["state_size"]
    return (s["n_groups"] * s["chunk"] * n + h * s["chunk"] * p
            + 2 * h * p * n)


def train_step(config: dict, traffic: dict, counters=None) -> dict:
    """One optimizer step, the routed experts at the expected load."""
    s = _sizes(config, traffic)
    d, rows = s["hidden"], s["rows"]
    mamba = rows * (d * (2 * s["inner"] + 2 * s["bc"] + s["mamba_heads"])
                    + s["inner"] * d + _scan_macs_a_row(s))
    experts = (rows * d * (s["n_experts"] + 2 * s["shared_width"])
               + expected_pairs_a_layer(config, traffic) * 2 * d
               * s["expert_width"])
    q, kv = s["n_heads"] * s["head_dim"], s["n_kv_heads"] * s["head_dim"]
    attention = rows * d * (2 * q + 2 * kv)
    scores = 12 * s["head_dim"] * s["n_heads"] * s["visible_pairs"]
    head = rows * d * s["vocab_size"]
    layers = s["layers"]
    return {"flops": 6 * (layers["M"] * mamba + layers["E"] * experts
                          + layers["*"] * attention + head)
            + layers["*"] * scores}


def _scan_bytes(s, reads: int, writes: int, states: int) -> int:
    """``reads`` and ``writes`` of the op's sequence-shaped operands
    (``x``-shaped and ``B``, ``C`` in the compute dtype, ``dt`` in
    float32) and ``states`` passes over the chunk boundaries' float32
    states."""
    h, p = s["mamba_heads"], s["mamba_head_dim"]
    a_row = (h * p + 2 * s["bc"]) * s["itemsize"] + h * 4
    boundary = s["rows"] // s["chunk"] * h * p * s["state_size"] * 4
    return (s["rows"] * ((reads + writes) * a_row
                         + h * p * s["itemsize"])      # y, or dy
            + states * boundary)


def ssm_scan_fwd(config: dict, traffic: dict, counters=None) -> dict:
    """All forward recurrences of a step."""
    s = _sizes(config, traffic)
    return {"flops": s["layers"]["M"] * 2 * s["rows"] * _scan_macs_a_row(s),
            "bytes": s["layers"]["M"] * _scan_bytes(s, 1, 0, 2)}


def ssm_scan_bwd(config: dict, traffic: dict, counters=None) -> dict:
    """All backward recurrences: two gradient products for each forward
    product (making the forward's again is not required work)."""
    s = _sizes(config, traffic)
    return {"flops": s["layers"]["M"] * 4 * s["rows"] * _scan_macs_a_row(s),
            "bytes": s["layers"]["M"] * _scan_bytes(s, 1, 1, 3)}


def causal_attention_fwd(config: dict, traffic: dict, counters=None) -> dict:
    """All forward attention calls: reads q, k, v, writes the output and
    one float32 statistic a row and head."""
    s = _sizes(config, traffic)
    return {"flops": s["layers"]["*"] * 4 * s["head_dim"] * s["n_heads"]
            * s["visible_pairs"],
            "bytes": s["layers"]["*"] * _attention_bytes(s, 2, 2, 1)}


def causal_attention_bwd(config: dict, traffic: dict, counters=None) -> dict:
    """All backward attention calls: reads q, k, v, the output, its
    gradient and the statistic, writes dq, dk, dv."""
    s = _sizes(config, traffic)
    return {"flops": s["layers"]["*"] * 8 * s["head_dim"] * s["n_heads"]
            * s["visible_pairs"],
            "bytes": s["layers"]["*"] * _attention_bytes(s, 4, 4, 1)}


def routed_experts(config: dict, traffic: dict, counters=None):
    """The six grouped products of a step (up and down forward, four
    backward) over the pairs the run counted (``moe_pairs_per_step``, all
    layers), None without a count. Bytes: every held expert's two
    matrices read in the compute dtype forward and backward and their
    float32 gradients written; a pair's row read forward, row and output
    gradient read backward, and a float32 row added forward (the result)
    and backward (the input gradient). The shared expert is not here: it
    is two plain products under its own scope."""
    pairs = (counters or {}).get("moe_pairs_per_step")
    if not pairs:
        return None
    s = _sizes(config, traffic)
    weights = (s["layers"]["E"] * s["experts_held"] * 2 * s["hidden"]
               * s["expert_width"])
    return {
        "flops": 6 * pairs * 2 * s["hidden"] * s["expert_width"],
        "bytes": weights * (2 * s["itemsize"] + 4)
        + pairs * s["hidden"] * (3 * s["itemsize"] + 2 * 4)}
