"""Runtime layers of a modern decoder (configs and the equations:
nn/conf/layers_decoder.py; PERF.md section 4 has the model they were
written for).

Precision under a mixed policy: parameters in the param dtype, every
large product in the compute dtype with float32 accumulation, and in
float32 throughout: the norms' statistics, the rotation, the router (its
product too, at ``Precision.HIGHEST``: which eight experts a row takes
must not turn on a bf16 rounding of the router's own making), the
attention's softmax and the loss. The residual stream between layers is
in the compute dtype, as in every other net of this package.

Named scopes inside a block, under the layer's own: ``attn`` (norm,
projections, head norms, rotation, output projection, and inside it
``block_attention`` round the attention itself, whatever backend runs),
``route`` (norm, router, top-k, the sort of the pairs, and what
``ops/grouped.py`` does to move rows in XLA: a gather a block of pairs
before its kernels, or a gather and a scatter-add a chunk inside its
loop) and ``experts`` (the grouped products and the gating between
them: on a TPU one Pallas call a block forward and two backward, which
add their rows to the result themselves; the chunk loop's dots
elsewhere).
``observability/opindex.py`` places a device op by the innermost scope
it is asked about.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers.base import Layer
from deeplearning4j_tpu.ops import attention as att
from deeplearning4j_tpu.ops import grouped
from deeplearning4j_tpu.ops import initializers as init_mod


def _rms_norm(x, g, eps):
    """In float32, returns float32."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return xf * jax.lax.rsqrt(ms + float(eps)) * g.astype(jnp.float32)


def _project(x, w, cd):
    return jnp.einsum("btf,fg->btg", x.astype(cd), w.astype(cd))


def _rotate(x, theta):
    """Rotary positions over the whole head (rotate-half), float32.
    ``x`` [b, 2L, h, dh]: both halves of the rows sit at 0..L-1."""
    t, dh = x.shape[1], x.shape[3]
    pos = (jnp.arange(t, dtype=jnp.int32) % (t // 2)).astype(jnp.float32)
    inv = float(theta) ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angle = pos[:, None] * inv[None, :]                      # [t, dh/2]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[None, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def expert_chunk_rows(rows: int, experts_per_token: int,
                      n_experts: int) -> int:
    """The pairs one held expert expects from a layer that routes
    ``rows`` rows, half as many again to spare, in whole tiles of 128 and
    at most 1,024: the grain ``ops/grouped.py`` is told.

    Its chunk loop (the CPU, widths off the lane tile) runs chunks of so
    many pairs of one expert: an expert near its expected load is then
    one chunk whatever the batch, and a step's time follows the number
    of chunks that hold a pair (0.14% of the rate a chunk on the v5e
    when the loop ran there, PERF.md Findings PR 31). Its kernels (a
    TPU) take this many pairs times the held experts as one block of
    the sorted pairs, so the usual step is one block a layer and a
    biased router pays a second; their row tile is the kernels' own
    (``grouped.TILE``), and nothing else about them is sized here."""
    expected = rows * experts_per_token / n_experts
    return 128 * min(max(math.ceil(1.5 * expected / 128), 1), 8)


_LIMB = 30      # bits of the low limb of ``expert_rows_total``


def rows_total(limbs):
    """``expert_rows_total`` [2, experts] (host side) as int64 counts."""
    return (limbs[1].astype("int64") << _LIMB) + limbs[0]


class _DecoderLayer(Layer):
    """What the layers here share: the seeded init of a matrix, and no
    streaming state (they train and answer ``net.output``)."""

    is_recurrent_stateful = True    # so that set_streaming reaches them

    @property
    def streaming(self):
        return False

    @streaming.setter
    def streaming(self, flag):
        if flag:
            raise NotImplementedError(
                f"{type(self).__name__} '{self.name}' has no streaming "
                "path: rnn_time_step and truncated BPTT are not supported "
                "on the decoder layers (ROADMAP Queue 2 item 10)")

    def _init(self, key, shape, fan_in, fan_out):
        w_fn = init_mod.resolve(self.resolve("weight_init", "xavier"))
        return w_fn(key, shape, fan_in, fan_out, self.param_dtype)


class TokenEmbeddingLayer(_DecoderLayer):
    def init_params(self, key):
        n_in, n_out = int(self.conf.n_in), int(self.conf.n_out)
        return {"W": self._init(key, (n_in, n_out), n_in, n_out)}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        if not jnp.issubdtype(x.dtype, jnp.integer):
            raise TypeError(
                f"TokenEmbedding '{self.name}' takes integer ids [b, t], "
                f"got {x.dtype}{tuple(x.shape)}")
        return (jnp.take(params["W"], x, axis=0).astype(self.compute_dtype),
                state)


class RmsNormLayer(_DecoderLayer):
    def init_params(self, key):
        return {"g": jnp.ones((int(self.conf.n_out),), self.param_dtype)}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        y = _rms_norm(x, params["g"], self.conf.eps)
        return y.astype(self.compute_dtype), state


class RoutedExpertsLayer(_DecoderLayer):
    def __init__(self, conf, input_type, global_conf, policy):
        super().__init__(conf, input_type, global_conf, policy)
        self.held = int(conf.n_experts if conf.experts_held is None
                        else conf.experts_held)
        if not 0 <= conf.first_expert <= conf.n_experts - self.held:
            raise ValueError(
                f"{type(conf).__name__} '{conf.name}': experts "
                f"{conf.first_expert}..{conf.first_expert + self.held - 1} "
                f"are not among {conf.n_experts}")

    def init_params(self, key):
        d, f = int(self.conf.n_out), int(self.conf.expert_width)
        n, held = int(self.conf.n_experts), self.held
        kr, kg, ku, kd = jax.random.split(key, 4)
        return {
            "ln_g": jnp.ones((d,), self.param_dtype),
            "Wr": self._init(kr, (d, n), d, n),
            "Wg": self._init(kg, (held, d, f), d, f),
            "Wu": self._init(ku, (held, d, f), d, f),
            "Wd": self._init(kd, (held, f, d), f, d),
        }

    def init_state(self):
        # the total in two int32 limbs (low 30 bits, the rest): one would
        # wrap after 33k steps of the worst case
        return {"expert_rows": jnp.zeros((self.held,), jnp.int32),
                "expert_rows_total": jnp.zeros((2, self.held), jnp.int32)}

    def _route(self, params, a):
        """The normed rows [R, d] (float32) and the pairs held here,
        sorted by expert: rows, weights, and the count of each expert."""
        k = int(self.conf.experts_per_token)
        w = _rms_norm(a, params["ln_g"], self.conf.eps).reshape(
            -1, a.shape[-1])
        r = jax.nn.softmax(jnp.dot(
            w, params["Wr"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST), axis=-1)
        top, chosen = jax.lax.top_k(r, k)
        coef = top / jnp.sum(top, axis=-1, keepdims=True)
        local = chosen.astype(jnp.int32) - int(self.conf.first_expert)
        key = jnp.where((local >= 0) & (local < self.held), local,
                        self.held).reshape(-1)
        order = jnp.argsort(key, stable=True)
        counts = jnp.sum(
            key[:, None] == jnp.arange(self.held, dtype=jnp.int32)[None, :],
            axis=0, dtype=jnp.int32)
        return (w, (order // k).astype(jnp.int32),
                coef.reshape(-1)[order], counts)

    def _experts(self, params, state, a):
        cd = a.dtype
        with jax.named_scope("route"):
            w, rows, coef, counts = self._route(params, a)
        chunk = expert_chunk_rows(w.shape[0], self.conf.experts_per_token,
                                  self.conf.n_experts)
        with jax.named_scope("experts"):
            y = grouped.expert_ffn(
                w.astype(cd), rows, coef, counts, params["Wg"].astype(cd),
                params["Wu"].astype(cd), params["Wd"].astype(cd),
                chunk=chunk)
        with jax.named_scope("route"):
            out = a + y.reshape(a.shape).astype(cd)
            low = state["expert_rows_total"][0] + counts
            total = jnp.stack([
                low & ((1 << _LIMB) - 1),
                state["expert_rows_total"][1] + (low >> _LIMB)])
        return out, {"expert_rows": counts, "expert_rows_total": total}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return self._experts(params, state, x.astype(self.compute_dtype))


class MoeDecoderBlockLayer(RoutedExpertsLayer):
    def __init__(self, conf, input_type, global_conf, policy):
        super().__init__(conf, input_type, global_conf, policy)
        if conf.n_heads % conf.n_kv_heads:
            raise ValueError(
                f"MoeDecoderBlock '{conf.name}': {conf.n_heads} query heads "
                f"cannot share {conf.n_kv_heads} key/value heads evenly")

    def init_params(self, key):
        d = int(self.conf.n_out)
        hq, hkv, dh = (int(self.conf.n_heads), int(self.conf.n_kv_heads),
                       int(self.conf.head_dim))
        k_experts, kq, kk, kv, ko = jax.random.split(key, 5)
        params = super().init_params(k_experts)
        params.update({
            "attn_ln_g": jnp.ones((d,), self.param_dtype),
            "Wq": self._init(kq, (d, hq * dh), d, hq * dh),
            "Wk": self._init(kk, (d, hkv * dh), d, hkv * dh),
            "Wv": self._init(kv, (d, hkv * dh), d, hkv * dh),
            "Wo": self._init(ko, (hq * dh, d), hq * dh, d),
            "q_norm_g": jnp.ones((dh,), self.param_dtype),
            "k_norm_g": jnp.ones((dh,), self.param_dtype),
        })
        return params

    def _attention(self, params, x):
        conf, cd = self.conf, x.dtype
        b, t, _ = x.shape
        if t % 2:
            raise ValueError(
                f"MoeDecoderBlock '{self.name}' takes a noised and a clean "
                f"copy of each sequence, an even number of rows; got {t}")
        hq, hkv, dh = int(conf.n_heads), int(conf.n_kv_heads), int(
            conf.head_dim)
        u = _rms_norm(x, params["attn_ln_g"], conf.eps).astype(cd)
        q = _project(u, params["Wq"], cd).reshape(b, t, hq, dh)
        k = _project(u, params["Wk"], cd).reshape(b, t, hkv, dh)
        v = _project(u, params["Wv"], cd).reshape(b, t, hkv, dh)
        q = _rotate(_rms_norm(q, params["q_norm_g"], conf.eps),
                    conf.rope_theta).astype(cd)
        k = _rotate(_rms_norm(k, params["k_norm_g"], conf.eps),
                    conf.rope_theta).astype(cd)
        with jax.named_scope("block_attention"):
            o = att.block_diffusion_mha(q, k, v, seq_len=t // 2,
                                        block_len=int(conf.block_len))
        return x + _project(o.reshape(b, t, hq * dh), params["Wo"], cd)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        with jax.named_scope("attn"):
            a = self._attention(params, x.astype(self.compute_dtype))
        return self._experts(params, state, a)


class TokenOutputLayer(_DecoderLayer):
    def init_params(self, key):
        n_in, n_out = int(self.conf.n_in), int(self.conf.n_out)
        return {"W": self._init(key, (n_in, n_out), n_in, n_out)}

    def _logits(self, params, x):
        cd = self.compute_dtype
        noised = x[:, :x.shape[1] // 2]
        return jnp.einsum("btf,fg->btg", noised.astype(cd),
                          params["W"].astype(cd),
                          preferred_element_type=jnp.float32)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return self.activation_fn(self._logits(params, x)), state

    def loss(self, params, x, labels, *, train=False, rng=None, mask=None):
        logits = self._logits(params, x)                 # float32 [b, L, V]
        if not jnp.issubdtype(labels.dtype, jnp.integer):
            raise TypeError(
                f"TokenOutput '{self.name}' takes integer labels [b, t], "
                f"got {labels.dtype}{tuple(labels.shape)}")
        nll = (jax.nn.logsumexp(logits, axis=-1)
               - jnp.take_along_axis(logits, labels[..., None],
                                     axis=-1)[..., 0])
        if mask is not None:
            nll = nll * mask.astype(jnp.float32)
        return jnp.sum(nll) / math.prod(labels.shape)
