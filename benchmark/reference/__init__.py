"""Plain float32 ``jax.numpy`` references, one module per configuration.

A reference module has ``logits(params, state, x, train)``: the
architecture's forward pass written from its published description, with
no kernels, no mixed precision and none of the program's layer code,
reading the net's own parameter tree. Callers run it under
``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul is
otherwise one bf16 pass. Each module also states the tolerances the
system is held to against it, with the reason for each.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def mcxent_mean(logits, labels):
    """Mean over rows of ``-sum(labels * log_softmax(logits))``: the
    program's score for a softmax + MCXENT head with no regularization."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    rows = -jnp.sum(labels * logp, axis=-1)
    return jnp.mean(rows)

