"""Stacked peephole LSTM with a per-timestep softmax head (Graves 2013,
arXiv:1308.0850, section 2; DL4J's GravesLSTM + RnnOutputLayer).

Per layer and timestep, with gate order i, f, o, g in the 4n columns:

    z = x W_x + h_prev W_h + b
    i = sigmoid(z_i + p_i * c_prev)      f = sigmoid(z_f + p_f * c_prev)
    g = tanh(z_g)                        c = f * c_prev + i * g
    o = sigmoid(z_o + p_o * c)           h = o * tanh(c)

with h and c zero before the first character. The head is a dense layer
on every h of the last LSTM. A plain ``lax.scan`` over time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# The system runs the LSTMs in bf16 (float32 parameters cast down, bf16
# activations and gate values, float32 accumulation inside the Pallas
# kernel) and the head's softmax in float32. Both checks run on the
# seeded initial parameters, so neither moves with the length of the
# window. On the v5e (my chip runs, PR 22) the logits of 16 sequences of
# 64 characters through the forward kernel were 5.4e-3 to 9.4e-3 (20
# seeds) of their spread from the reference's: the recurrence compounds
# the rounding of h and c at every step. The bound is three times the
# worst seen. With the forget-gate bias of one layer dropped the
# reference moves by 0.84 of the spread (CPU, PR 22: arithmetic, not a
# device measurement). The peepholes are zero at the seeded init, so a
# fault in a peephole term passes both checks (PERF.md section 7). The
# first step's loss starts from near-uniform outputs (ln 80) and was
# 0.1e-6 to 4.2e-6 off, so it can only catch a gross fault; its bound is
# five times the worst seen.
LOGITS_RTOL = 0.03      # max|log p - log_softmax(reference)| / spread of the reference logits
LOSS_RTOL = 2e-5        # first training step's loss, relative


def _layer(p, x):
    n = p["Wh"].shape[0]
    xz = jnp.einsum("btf,fg->tbg", x, p["Wx"]) + p["b"]
    h0 = jnp.zeros((x.shape[0], n), jnp.float32)

    def step(carry, z):
        h, c = carry
        z = z + h @ p["Wh"]
        i = jax.nn.sigmoid(z[:, :n] + p["p"][0] * c)
        f = jax.nn.sigmoid(z[:, n:2 * n] + p["p"][1] * c)
        g = jnp.tanh(z[:, 3 * n:])
        c = f * c + i * g
        o = jax.nn.sigmoid(z[:, 2 * n:3 * n] + p["p"][2] * c)
        h = o * jnp.tanh(c)
        return (h, c), h

    _, ys = jax.lax.scan(step, (h0, h0), xz)
    return jnp.moveaxis(ys, 0, 1)


def logits(params, state, x, train: bool):
    """``train`` changes nothing: the stack has no dropout and no batch
    statistics."""
    names = sorted(params, key=lambda k: int(k.rsplit("_", 1)[1]))
    x = x.astype(jnp.float32)
    for name in names[:-1]:
        x = _layer(params[name], x)
    head = params[names[-1]]
    return x @ head["W"] + head["b"]
