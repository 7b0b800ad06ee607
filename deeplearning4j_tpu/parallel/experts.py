"""Expert (MoE) parallelism over an ``expert`` mesh axis.

The last of the five mesh axes (dp/tp/pp/sp/ep). A mixture-of-experts
feed-forward bank: each token is routed to its top-k experts, expert
weights live stacked with a leading expert dim SHARDED over the
``expert`` axis, and the dispatch/combine einsums against the one-hot
routing tensors are the classic Shazeer formulation — GSPMD partitions
them and inserts the all-to-alls over ICI, exactly as it inserts the
gradient all-reduce for dp. No reference analogue (2017-era DL4J
predates MoE); included because expert parallelism is a first-class
scaling axis on TPU and shapes the framework's mesh design.

Capacity semantics: each expert processes at most ``capacity`` tokens
per batch; overflow tokens are DROPPED from the expert path (standard
GShard behavior) and pass through with zero expert contribution —
training remains differentiable through the router probabilities.

The expert layer of the net path is ``nn/layers/decoder.py``
(``RoutedExpertsLayer``: told which experts it holds, drops nothing);
this module stays outside it (ROADMAP D6).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def init_moe_params(key, n_experts: int, f_in: int, f_hidden: int,
                    f_out: Optional[int] = None, dtype=jnp.float32):
    """Router + stacked expert FFN params (expert dim leads)."""
    f_out = f_out or f_in
    k_r, k_1, k_2 = jax.random.split(key, 3)
    s1 = (2.0 / (f_in + f_hidden)) ** 0.5
    s2 = (2.0 / (f_hidden + f_out)) ** 0.5
    return {
        "router": jax.random.normal(k_r, (f_in, n_experts), dtype) * 0.02,
        "W1": jax.random.normal(k_1, (n_experts, f_in, f_hidden),
                                dtype) * s1,
        "b1": jnp.zeros((n_experts, f_hidden), dtype),
        "W2": jax.random.normal(k_2, (n_experts, f_hidden, f_out),
                                dtype) * s2,
        "b2": jnp.zeros((n_experts, f_out), dtype),
    }


def shard_experts(mesh: Mesh, expert_axis: str, params):
    """Place MoE params: expert-stacked weights sharded on the expert
    dim, the router replicated."""
    def put(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name == "router":
            spec = P()
        else:
            spec = P(expert_axis, *([None] * (leaf.ndim - 1)))
        return jax.device_put(leaf, NamedSharding(mesh, spec))
    return jax.tree_util.tree_map_with_path(put, params)


def moe_ffn(params, x, *, capacity: Optional[int] = None, top_k: int = 1):
    """Routed mixture-of-experts FFN on ``x`` [tokens, f_in].

    Pure function of sharded params — under jit on a mesh whose
    ``expert`` axis holds the stacked weights, GSPMD turns the dispatch/
    combine einsums into all-to-alls and runs each expert's FFN on its
    own devices. Returns ([tokens, f_out], aux_loss) where aux_loss is
    the standard load-balancing loss (mean_prob * mean_assignment * E)."""
    n_tokens = x.shape[0]
    n_experts = params["W1"].shape[0]
    if capacity is None:
        capacity = max(2 * top_k * n_tokens // n_experts, 4)

    logits = x @ params["router"].astype(x.dtype)       # [T, E]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    combine_chunks = []
    masked_probs = probs
    occupancy = jnp.zeros((n_experts,), probs.dtype)  # kept tokens so far
    assign_chunks = []  # pre-capacity routing decisions, per round
    for _ in range(top_k):
        idx = jnp.argmax(masked_probs, axis=-1)          # [T]
        onehot = jax.nn.one_hot(idx, n_experts, dtype=probs.dtype)
        assign_chunks.append(onehot)
        # 1-based position in the chosen expert's queue, CONTINUING after
        # the slots earlier routing rounds already claimed (per-round
        # restarts would collide round-1 and round-2 tokens in one slot)
        pos = (jnp.cumsum(onehot, axis=0) + occupancy[None, :]) * onehot
        keep = (pos <= capacity).astype(probs.dtype) * onehot
        occupancy = occupancy + keep.sum(0)
        gate = (masked_probs * keep).sum(-1, keepdims=True)  # [T, 1]
        pos_oh = jax.nn.one_hot(((pos * keep).sum(-1) - 1).astype(jnp.int32),
                                capacity, dtype=probs.dtype)
        # [T, E, C] dispatch/combine tensors (Shazeer einsum form)
        combine_chunks.append(
            gate[:, :, None] * keep[:, :, None] * pos_oh[:, None, :])
        masked_probs = masked_probs * (1.0 - onehot)
    combine = sum(combine_chunks)                        # [T, E, C]
    dispatch = (combine > 0).astype(x.dtype)

    expert_in = jnp.einsum("tec,tf->ecf", dispatch, x)   # [E, C, f_in]
    h = jax.nn.relu(jnp.einsum("ecf,efh->ech", expert_in,
                               params["W1"].astype(x.dtype))
                    + params["b1"][:, None, :].astype(x.dtype))
    expert_out = (jnp.einsum("ech,eho->eco", h,
                             params["W2"].astype(x.dtype))
                  + params["b2"][:, None, :].astype(x.dtype))
    y = jnp.einsum("tec,eco->to", combine.astype(x.dtype), expert_out)

    # load-balancing auxiliary (GShard/Switch): encourages uniform
    # routing; differentiable through probs. The assignment fraction
    # comes from the router's PRE-capacity one-hot choices, not the
    # post-drop dispatch tensor: under heavy overflow the dropped tokens
    # are concentrated on exactly the overloaded experts, so counting
    # only kept tokens would under-penalize the imbalance the loss
    # exists to correct (Switch §2.2 / GShard semantics).
    assign = sum(assign_chunks).astype(jnp.float32)      # [T, E]
    aux = (probs.mean(0) * assign.mean(0)).sum() * n_experts
    return y, aux
